"""Runs one example script as a user would (shared by the three
test_examples*.py files: one file of 35 subprocess examples serialises
on one xdist worker under --dist loadfile, three files spread them)."""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_example(rel, *argv, timeout=420):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # examples set cpu themselves via --cpu
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "example", rel), "--cpu",
         *argv],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, "example %s failed:\n%s\n%s" % (
        rel, r.stdout[-2000:], r.stderr[-2000:])
    return r.stdout
