"""Times one held-range expert layer alone on the chip: the plain loop over
tiles of `ops/moe.py` and the Pallas kernels of `ops/moe_kernels.py`.

    python3 tools/moe_probe.py [--cell lfm2,qwen3next,kimi]
        [--rows 128] [--iters 5] [--profile 0]

Each cell is one sparse layer at that benchmark cell's widths (tokens N,
H, I, experts held E of E_all, top-k), at two loads of held rows a layer:
the window's first steps and its traced tail (`PERF.md` §5, §7):

- lfm2: N 8192, H 2048, I 1792, 8 of 32, k 4; 8,200 and 23,000 rows;
- qwen3next: N 8192, H 2048, I 512, 16 of 512, k 10; 2,560 and 3,400,
  the tail with one held expert at 5 times the mean of all 512;
- kimi: N 4096, H 2304, I 1024, 8 of 256, k 8; 1,024 and 1,500.

The assignments are drawn from a seed with those held counts (every
token's k experts distinct), so both paths see the same rows. For each
load and path: ms of the forward and of the forward and backward of a
weighted sum, each the mean of `--iters` calls after a warm-up, fenced by
`block_until_ready`, and us a held row (the forward and backward's ms
over the held rows); beside them the kernels' largest distance from the
plain loop in y and in each gradient, beside the largest value.
`--rows` lists the kernels' row tiles to time, each as `kernel_R<rows>`
(the program takes `moe_kernels.shape`'s). `--profile N` traces N calls
of the forward and backward of each path at each load and adds the
device operations that took the most time, in us a call (`ops`).
One JSON line at the end, and the same in `chiprun_out/moe_probe.json`.
Refuses to time anything off a TPU.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import moe_kernels as mk

# N, H, I, E_all, E held, k, (held rows at the start, at the tail), the
# tail's hottest held expert over the mean of all experts (0: no skew)
_CELLS = {"lfm2": (8192, 2048, 1792, 32, 8, 4, (8200, 23000), 0),
          "qwen3next": (8192, 2048, 512, 512, 16, 10, (2560, 3400), 5),
          "kimi": (4096, 2304, 1024, 256, 8, 8, (1024, 1500), 0)}


def _ms(fn, args, iters):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3


def _ops(fn, args, calls, top=12):
    """The `top` device operations of `calls` traced calls of fn, as
    [HLO text (its start), us a call], the longest first."""
    import tempfile
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import trace_reduce
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        trace = trace_reduce.load(d)
    took = {}
    for name, s, e in trace.devices[0].ops:
        key = name[:160]
        took[key] = took.get(key, 0.0) + (e - s)
    rows = sorted(took.items(), key=lambda kv: -kv[1])[:top]
    return [[k, round(v * 1e6 / calls, 1)] for k, v in rows]


def _assignments(rng, N, E_all, E, k, held, hot):
    """top_i (N, k): `held` assignments on experts 0..E-1, the first
    `hot` times the mean of all experts where hot > 0, the rest spread
    evenly; every token's k distinct, the unheld ones from E on."""
    counts = np.full(E, held // E)
    counts[: held % E] += 1
    if hot:
        counts[0] = min(int(hot * N * k / E_all), held - (E - 1), N)
        rest = held - counts[0]
        counts[1:] = rest // (E - 1)
        counts[1: 1 + rest % (E - 1)] += 1
    top_i = np.full((N, k), -1, np.int64)
    taken = np.zeros(N, np.int64)
    for e in range(E):
        room = np.flatnonzero(taken < k)
        # tokens with the most room first, ties at random
        pick = room[np.lexsort((rng.random(room.size), taken[room]))][
            : counts[e]]
        top_i[pick, taken[pick]] = e
        taken[pick] += 1
    for n in range(N):
        free = rng.choice(np.arange(E, E_all), k - taken[n], replace=False)
        top_i[n, taken[n]:] = free
    return top_i.astype(np.int32), counts.astype(np.float32)


def _layer(kernels, tile=256):
    def held(x, wg, wu, wd, top_i, top_w, counts):
        return moe._held(x, wg, wu, wd, top_i, top_w, counts, 0, tile,
                         kernels)
    return held


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="lfm2,qwen3next,kimi")
    ap.add_argument("--rows", default=str(mk._ROWS),
                    help="the kernels' row tiles to time, comma-separated")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--profile", type=int, default=0,
                    help="calls to trace a path and load (0: none)")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("moe_probe: times are the chip's; found %s"
                         % dev.platform)
    res = {"device": dev.device_kind, "cells": {}}
    for cell in args.cell.split(","):
        N, H, I, E_all, E, k, loads, hot = _CELLS[cell]
        keys = jax.random.split(jax.random.PRNGKey(0), 5)
        bf = jnp.bfloat16
        x = jax.random.normal(keys[0], (N, H), bf)
        wg, wu = ((0.02 * jax.random.normal(kk, (E, I, H))).astype(bf)
                  for kk in keys[1:3])
        wd = (0.02 * jax.random.normal(keys[3], (E, H, I))).astype(bf)
        c = jax.random.normal(keys[4], (N, H), jnp.float32)
        out = res["cells"][cell] = {
            "N": N, "H": H, "I": I, "E": E, "k": k,
            "chunk_fwd": mk.shape(H, I, False)[1],
            "chunk_bwd": mk.shape(H, I, True)[1]}
        rng = np.random.default_rng(0)
        runs = []
        for when, held in zip(("start", "tail"), loads):
            top_i, counts = _assignments(rng, N, E_all, E, k, held,
                                         hot if when == "tail" else 0)
            top_w = jax.nn.softmax(jnp.asarray(
                rng.standard_normal((N, k)), jnp.float32), axis=-1)
            out[when] = {"held_rows": int(counts.sum()),
                         "max_over_mean": float(counts.max()
                                                / (N * k / E_all))}
            runs.append((when, held, (x, wg, wu, wd, top_w,
                                      jnp.asarray(top_i),
                                      jnp.asarray(counts))))
        plain = {}
        for path in ["plain"] + ["kernel_R%s" % r
                                 for r in args.rows.split(",")]:
            if path != "plain":
                mk._ROWS = int(path[len("kernel_R"):])
                jax.clear_caches()       # the kernels' jit keys miss _ROWS
            held_fn = _layer(path != "plain")

            def fwd(x, wg, wu, wd, top_w, top_i, counts, held_fn=held_fn):
                return held_fn(x, wg, wu, wd, top_i, top_w, counts)

            def loss(x, wg, wu, wd, top_w, top_i, counts, fwd=fwd):
                y = fwd(x, wg, wu, wd, top_w, top_i, counts)
                return (y.astype(jnp.float32) * c).sum(), y
            fwd = jax.jit(fwd)
            grad = jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True))
            for when, held, ins in runs:
                row = out[when]
                try:
                    f_ms = _ms(fwd, ins, args.iters)
                    both = _ms(grad, ins, args.iters)
                    row[path] = {"fwd_ms": round(f_ms, 4),
                                 "fwd_bwd_ms": round(both, 4),
                                 "us_a_row": round(both * 1e3 / held, 4)}
                    (_, y), g = grad(*ins)
                    got = [y] + list(g)
                    if args.profile:
                        row[path]["ops"] = _ops(grad, ins, args.profile)
                except Exception as e:  # noqa: BLE001 — one path, not the run
                    row[path] = {"error": str(e)[:600]}
                    got = None
                print(cell, when, path, row[path], flush=True)
                if path == "plain":
                    plain[when] = got
                elif got is not None and plain.get(when) is not None:
                    row[path + "_vs_plain"] = {
                        name: [float(jnp.abs(a.astype(jnp.float32)
                                             - b.astype(jnp.float32)).max()),
                               float(jnp.abs(a.astype(jnp.float32)).max())]
                        for name, a, b in zip(
                            ("y", "dx", "dwg", "dwu", "dwd", "dtop_w"),
                            plain[when], got)}
                    print(cell, when, path, row[path + "_vs_plain"],
                          flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_probe.json"), "w") as f:
        json.dump(res, f)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
