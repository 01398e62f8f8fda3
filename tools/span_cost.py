#!/usr/bin/env python3
"""What the trace plane costs the host: microseconds a recorded span, a
span with no context, a step context, a `StepRoot` iteration and a
`StepTimer` step, each over a loop of `--n` (docs/observability.md
"Step spans"; the targets are 5 us a recorded span and a step context).

    python tools/span_cost.py [--n 20000]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _us(n, fn):
    t0 = time.perf_counter()
    for i in range(n):
        fn(i)
    return 1e6 * (time.perf_counter() - t0) / n


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=20000)
    n = ap.parse_args(argv).n
    from mxnet_tpu.observability import trace
    from mxnet_tpu.observability.telemetry import StepTimer

    def one_span(_i):
        with trace.trace_span("x"):
            pass

    out = {}
    with trace.trace_span("root", ctx=trace.step_trace_context("cost", 0)):
        out["recorded_span_us"] = _us(n, one_span)
    trace.detach()
    out["span_without_context_us"] = _us(n, one_span)
    out["step_context_us"] = _us(
        n, lambda i: trace.step_trace_context("cost", i))
    root = trace.StepRoot("cost")

    def iteration(i):
        root.begin(i)
        root.end(i + 1)

    out["step_root_iteration_us"] = _us(n, iteration)
    trace.detach()
    timer = StepTimer("cost")

    def timed(_i):
        timer.begin_step()
        timer.end_step()

    out["steptimer_step_us"] = _us(max(1, n // 10), timed)
    trace.detach()
    trace.reset_ring()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
