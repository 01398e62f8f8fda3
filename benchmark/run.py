#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: it fails, printing no result, unless JAX finds a TPU with
the chips the cell asks for and a device kind that `peaks.json` knows. It
builds the model with weights from `--seed`, warms only the cell's own
shapes, measures for `--seconds`, checks the timed path's first steps
against the plain reference, and prints one JSON object as the last line
of standard output. Everything that belongs to one configuration, cell,
loop or metric is a file found by its name; this file holds no such name.
"""
import argparse
import json
import os
import sys
import time

_IMPORTED = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips, peaks):
    """The cell's devices and the kind's peaks, or an exit with no result."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit("benchmark: no accelerator: JAX found platform %r (%s); "
                 "nothing is measured on it" % (dev.platform, dev.device_kind))
    if len(devices) < chips:
        sys.exit("benchmark: the cell needs %d chips, JAX found %d"
                 % (chips, len(devices)))
    if dev.device_kind not in peaks:
        sys.exit("benchmark: device kind %r is not in peaks.json (%s)"
                 % (dev.device_kind, sorted(peaks)))
    return devices[:chips], peaks[dev.device_kind]


def main(argv=None):
    args = _args(argv)
    import harness
    started = harness.process_start() or _IMPORTED
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        sys.exit("benchmark: BENCHMARK.json has no workload %r" % args.workload)
    cell, config = harness.load_cell(bench, args.workload)
    devices, peak = find_chips(int(entry["chips"]),
                               harness.load_json("peaks.json"))
    result = harness.run_cell(cell, config, bench, args.seed, args.seconds,
                              bool(args.trace), devices, peak, started)
    print(json.dumps(result), flush=True)    # `compared` comes last on it
    return 0


if __name__ == "__main__":
    sys.exit(main())
