"""Times one attention call alone on the chip: `flash_attention`'s three
kernels at a list of block sizes, the three-op composition it replaced
in GPT-2 (`_attention_plain`) and, as a yardstick only,
`jax.experimental.pallas.ops.tpu.flash_attention`.

    chiprun -- python3 tools/attention_probe.py [--shape 8,12,1024,64]
        [--dtype bfloat16] [--blocks 256x256,512x1024]

Every time is the mean of `--iters` calls after a warm-up, fenced by
`block_until_ready`, in ms. `three_op`, `jax_flash_*` and `kernel` (the
blocks the op would choose) are (forward, forward + backward of a
weighted sum), `kernel_max_error` its largest distance from the reference
at `highest`; `fwd_dq_dkv_<block_q>x<block_k>` times
the three kernels one by one. One JSON line at the end, and the same in
`chiprun_out/attention_probe.json`. Refuses to time anything off a TPU.
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

from mxnet_tpu.ops import pallas_kernels as pk


def _ms(fn, args, iters):
    out = fn(*args)
    jax.block_until_ready(out)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e3, 4)


def _pair(attn, qkv, w, iters):
    """(forward ms, forward + backward ms) of attn(q, k, v)."""
    grad = jax.value_and_grad(
        lambda *a: (attn(*a).astype(jnp.float32) * w).sum(),
        argnums=(0, 1, 2))
    return _ms(jax.jit(attn), qkv, iters), _ms(jax.jit(grad), qkv, iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,12,1024,64")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks", default="128x128,256x256,512x512,"
                    "512x1024,1024x1024")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("attention_probe: times are the chip's; found %s"
                         % dev.platform)
    B, H, T, D = (int(x) for x in args.shape.split(","))
    dt, causal, n = jnp.dtype(args.dtype), bool(args.causal), args.iters
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v, do = (jax.random.normal(kk, (B, H, T, D), dt)
                   for kk in keys[:4])
    w = jax.random.normal(keys[4], (B, H, T, D), jnp.float32)
    res = {"device": dev.device_kind, "shape": [B, H, T, D],
           "dtype": args.dtype, "causal": causal, "ms": {}}

    def record(name, fn):
        try:
            res["ms"][name] = fn()
        except Exception as e:  # noqa: BLE001 — one variant, not the run
            res["ms"][name] = {"error": str(e)[:400]}
        print(name, res["ms"][name], flush=True)

    record("three_op", lambda: _pair(
        lambda *a: pk._attention_plain(*a, causal), (q, k, v), w, n))

    def yardstick(block):
        from jax.experimental.pallas.ops.tpu import flash_attention as fa
        b = min(block, T)
        sizes = fa.BlockSizes(
            block_q=b, block_k_major=b, block_k=b, block_b=1,
            block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
            block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)
        return _pair(lambda *a: fa.flash_attention(
            *a, causal=causal, sm_scale=D ** -0.5, block_sizes=sizes),
            (q, k, v), w, n)
    for b in (128, 512):
        record("jax_flash_%d" % b, lambda b=b: yardstick(b))

    record("kernel", lambda: _pair(
        lambda *a: pk.flash_attention(*a, causal), (q, k, v), w, n))
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(lambda *a: pk._attn_reference(*a, causal))(q, k, v)
    record("kernel_max_error", lambda: float(jnp.abs(
        pk.flash_attention(q, k, v, causal).astype(jnp.float32)
        - exact.astype(jnp.float32)).max()))

    for spec in args.blocks.split(","):
        bq, bk = (int(x) for x in spec.split("x"))
        if T % bq or T % bk:
            continue

        def parts():
            last = (causal, bq, bk, dt, pk._interpret())
            fwd = jax.jit(lambda *a: pk._flash_fwd(*a, *last))
            o, lse = fwd(q, k, v)
            flat = [x.reshape(B * H, T, D) for x in (q, k, v, do)]
            delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                            -1).reshape(B * H, 1, T)
            rest = (*flat, lse, delta)
            return [_ms(fwd, (q, k, v), n)] + [
                _ms(jax.jit(lambda *a: f(*a, *last)), rest, n)
                for f in (pk._flash_dq, pk._flash_dkv)]
        record("fwd_dq_dkv_%s" % spec, parts)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "attention_probe.json"),
              "w") as f:
        json.dump(res, f)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
