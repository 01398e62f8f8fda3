#!/usr/bin/env python3
"""Run one benchmark cell untraced on several seeds, each seed in a
process of its own (`benchmark/run.py`'s way: one process, the chip, one
result), and read the host account (`benchmark/host_account.py`) at the
end of each window besides, so that every stalled step of every window is
printed with what covered it: a collection, the OS, another thread, or a
wait in the runtime.

    python3 tools/stall_hunt.py --workload <cell> --seeds 1 2 3 \\
        [--seconds 20] [--out .bench_trace/stall_hunt]

Writes each run's result line to `<out>/<cell>.jsonl` and its standard
error to `<out>/<cell>_<seed>.err`; prints one line a run (samples a
second, the host account's four numbers, the stalls) and the stall
table's lines. Needs the chip the cell asks for, as `run.py` does.
"""
import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
READERS = ("host_gc_ms", "stall_ms", "feed_wait_ms", "step_host_ms")
# the lines of a child's standard error that are printed: the window, the
# host account and the stall table
KEEP = ("window:", "host_account:", "spans a held", "root attrs", "stall:",
        "  self ms on", "  other threads'", "  gc:", "  root:", "  cover:")


def one(workload, seed, seconds):
    """The child: one untraced run with the host account's readers read
    beside the end-to-end metrics; the result is the last line."""
    for p in (BENCH, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import harness
    import run as bench_run
    started = harness.process_start()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["end_to_end"] = bench["end_to_end"] + [
        {"name": name, "unit": "ms"} for name in READERS]
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    cell, config = harness.load_cell(bench, workload)
    devices, peak = bench_run.find_chips(int(entry["chips"]),
                                         harness.load_json("peaks.json"))
    result = harness.run_cell(cell, config, bench, seed, seconds, False,
                              devices, peak, started)
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default=os.path.join(ROOT, ".bench_trace",
                                                  "stall_hunt"))
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one:
        return one(args.workload, args.seeds[0], args.seconds)
    os.makedirs(args.out, exist_ok=True)
    failed = 0
    for seed in args.seeds:
        err_path = os.path.join(args.out, "%s_%d.err" % (args.workload, seed))
        with open(err_path, "w") as err:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--one",
                 "--workload", args.workload, "--seeds", str(seed),
                 "--seconds", str(args.seconds)],
                cwd=ROOT, stdout=subprocess.PIPE, stderr=err, text=True)
        lines = proc.stdout.strip().splitlines()
        if proc.returncode or not lines:
            failed += 1
            print("seed %d: exit %d, no result (%s)"
                  % (seed, proc.returncode, err_path), flush=True)
            continue
        result = json.loads(lines[-1])
        with open(os.path.join(args.out, args.workload + ".jsonl"), "a") as f:
            f.write(json.dumps(dict(result, seed=seed)) + "\n")
        got = {k: v["value"] for k, v in result["metrics"].items()}
        print("seed %d: correct %s, %s" % (seed, result["correct"], " ".join(
            "%s %.4f" % (k, got[k]) for k in
            ("train_samples_per_s", "setup_s") + READERS if k in got)),
            flush=True)
        with open(err_path) as f:
            for line in f:
                if line.startswith(KEEP):
                    print("  " + line.rstrip(), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
