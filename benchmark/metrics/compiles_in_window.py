"""Compile, program counter: persistent-cache hits plus misses
(`compile.cache.cache_stats()`) after the window minus before: every
compilation request the window made. Should be 0."""


def read(run):
    return run["compiles"]
