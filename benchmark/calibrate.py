#!/usr/bin/env python3
"""Read, on the chip, the numbers that a cell's limits are set from.

    python3 benchmark/calibrate.py --workload <name> --seeds 11 12 13 [--controls 3]

One process, for each seed: the program's first steps through the cell's
own loop against the plain reference (the lower readings); and, for the
first `--controls` seeds, the reference put in the program's place in the
precision below the configuration's (`control` in the cell's file) and
with each fault a cell can have planted in it (half of the batch left
out; the state left unchanged; on several chips, one chip's rows alone:
the exchange left out).
The upper readings are the smallest of those. One JSON line a seed, also
appended to chiprun_out/calibrate_<cell>.jsonl. Not part of a run.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--plant", nargs="+",
                    help="only these of the planted variants (by name)")
    ap.add_argument("--dump", action="store_true",
                    help="also write every leaf's norms of every variant")
    args = ap.parse_args(argv)

    import harness
    import check
    import traffic
    import run as _run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config = harness.load_cell(bench, args.workload)
    devices, _ = _run.find_chips(int(cell["chips"]),
                                 harness.load_json("peaks.json"))
    from mxnet_tpu.compile import cache
    cache.enable_cache()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out",
                            "calibrate_%s.jsonl" % args.workload)
    cell = dict(cell, pool=harness.FIRST_STEPS)
    loop_mod = harness.load_file("loops", cell["loop"])
    for n, seed in enumerate(args.seeds):
        pool = traffic.make_pool(cell, config, seed)
        line = {"cell": args.workload, "seed": seed}
        loop = loop_mod.Loop(cell, config, seed, devices)
        cell["_shapes"] = {k: tuple(v.shape) for k, v in loop.weights.items()}
        if not args.no_program:
            feed = iter(loop.feed(traffic.cycle(pool)))
            prog = harness.first_steps(loop, feed)
            del feed
        loop.close()
        del loop
        gc.collect()
        ref = harness.reference_readings(config, cell, seed, pool, devices)
        line["ref_losses"] = ref["losses"]
        def plain(r):
            out = {k: v for k, v in r.items()
                   if k not in ("grad", "variances")}
            if r is not ref and r.get("variances"):
                out["var_diffs"] = check.leaf_diffs(r["variances"],
                                                    ref["variances"])
            return out

        dump = {"reference": plain(ref)}
        if not args.no_program:
            line["program"], line["program_leaf"] = check.readings(prog, ref, cell["_shapes"])
            line["program_losses"] = prog["losses"]
            dump["program"] = plain(prog)
        if n < args.controls:
            planted = {"control_" + cell["control"]:
                       dict(mode=cell["control"])}
            if cell["control"] == "fp8":
                # a second witness: the reference in the cell's own precision
                planted["witness_bfloat16"] = dict(mode="bfloat16")
            planted["fault_half_batch"] = dict(rows=int(cell["batch"]) // 2)
            planted["fault_unchanged"] = dict(unchanged=True)
            if len(devices) > 1:
                planted["fault_no_exchange"] = dict(
                    rows=int(cell["batch"]) // len(devices))
            if args.plant:
                planted = {k: v for k, v in planted.items()
                           if k in args.plant}
            for name, kw in planted.items():
                other = harness.reference_readings(config, cell, seed, pool,
                                                   devices, **kw)
                line[name], line[name + "_leaf"] = check.readings(other, ref, cell["_shapes"])
                dump[name] = plain(other)
                del other
        if args.dump:
            with open(os.path.join(ROOT, "chiprun_out", "leaves_%s_%d.json"
                                   % (args.workload, seed)), "w") as f:
                json.dump(dump, f)
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
