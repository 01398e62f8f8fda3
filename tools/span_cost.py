#!/usr/bin/env python3
"""What the trace plane costs the host: microseconds a recorded span, a
span with no context, a step context, a `StepRoot` iteration and a
`StepTimer` step, each over a loop of `--n` (docs/observability.md
"Step spans"; the targets are 5 us a recorded span and a step context);
of a root's iteration, the OS's account (one `getrusage` and the attrs
made from two readings); and a garbage collection's share: the
collector's callback (start and stop) and its drain, into a `gc` span
(generation 1 or 2) or into the root's `gc0` totals (generation 0).

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


def _gc_cost(trace, n):
    """us a collection: the callback alone, then the drain of a queue of
    such collections, generation 1 (a span each) and generation 0."""
    info = {"generation": 1, "collected": 0, "uncollectable": 0}
    batch = 256
    out = {}
    with trace.trace_span("root", ctx=trace.step_trace_context("cost", 0)):
        def callback(_i):
            trace._on_gc("start", info)
            trace._on_gc("stop", info)

        out["gc_callback_us"] = _us(n, callback)
        trace._gc_pending.clear()
        for gen in (1, 0):
            info["generation"] = gen
            secs = 0.0
            for _ in range(max(1, n // batch)):
                for _ in range(batch):
                    callback(0)
                t0 = time.perf_counter()
                trace._drain_gc()
                trace._gc0_totals()
                secs += time.perf_counter() - t0
            out["gc_drain_gen%d_us" % gen] = 1e6 * secs / (
                max(1, n // batch) * batch)
    trace.detach()
    return out


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
    last = [trace._rusage()]

    def os_account(_i):
        now = trace._rusage()
        trace._os_account(last[0], now)
        last[0] = now

    out["root_os_account_us"] = _us(n, os_account)
    out.update(_gc_cost(trace, n))
    trace.reset_ring()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
