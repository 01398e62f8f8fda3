"""Input, host clock: mean per step that the loop blocked taking its next
staged batch (`next(feed)`: the prefetcher's queue, or the synchronous
`mx.nd.array(..., ctx)` of the Gluon loop)."""


def read(run):
    steps = run["steps"]
    return 1e3 * sum(s[1] - s[0] for s in steps) / len(steps)
