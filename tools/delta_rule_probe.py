"""Times one gated delta rule call alone on the chip: the plain path (XLA
operations and a `lax.scan`) and the Pallas kernels of
`ops/delta_rule_kernels.py`.

    chiprun -- python3 tools/delta_rule_probe.py [--decay head|channel]
        [--shape B,T,Hk,Hv,D] [--dtype bfloat16] [--chunk 64]

`--decay head` is one log decay a head (Qwen3-Next's Gated DeltaNet,
default shape 1,8192,16,32,128), `--decay channel` one a key channel
(Kimi Delta Attention, g of rank 4, default shape 1,4096,32,32,128).
Every time is the mean of `--iters` calls after
a warm-up, fenced by `block_until_ready`, in ms. `plain` and `kernel` are
(forward, forward + backward of a weighted sum in all five arguments) of
`gated_delta_rule`'s two paths; `fwd_save_bwd` times the kernels one by
one: the forward alone, the forward that also writes what the backward
is handed (the tiles' states and inverses), and the backward. `*_max_error` is the largest distance of a path's
output from the token-by-token recurrence in float32 at `highest`, beside
the largest value there (`recurrence_max`). One JSON line at the end, and
the same in `chiprun_out/delta_rule_probe.json`. Refuses to time anything
off a TPU.
"""
import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax import lax

from mxnet_tpu.ops import delta_rule_kernels as dk
from mxnet_tpu.ops import linear_attention as la


def _ms(fn, args, iters):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / iters * 1e3, 4)


def _pair(rule, args, w, iters):
    """(forward ms, forward + backward ms) of rule(q, k, v, g, beta)."""
    grad = jax.value_and_grad(
        lambda *a: (rule(*a).astype(jnp.float32) * w).sum(),
        argnums=(0, 1, 2, 3, 4))
    return _ms(jax.jit(rule), args, iters), _ms(jax.jit(grad), args, iters)


def _recurrence(q, k, v, g, beta):
    """Token by token in float32, as the equations have it."""
    B, T, Hk, Dk = q.shape
    Hv = v.shape[2]
    f32 = jnp.float32
    q, k = (jnp.repeat(la._l2norm(x), Hv // Hk, axis=2) for x in (q, k))

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        # one decay a head, or one a key channel: a row of the state
        S = jnp.exp(g_t).reshape(g_t.shape[:2] + (-1, 1)) * S
        d = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0)
               for x in (q * Dk ** -0.5, k, v, g, beta))
    _, o = lax.scan(token, jnp.zeros((B, Hv, Dk, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 1)


_SHAPES = {"head": "1,8192,16,32,128", "channel": "1,4096,32,32,128"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--decay", choices=sorted(_SHAPES), default="head")
    ap.add_argument("--shape", help="B,T,Hk,Hv,D; default by --decay")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--chunk", type=int, default=64)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("delta_rule_probe: times are the chip's; found %s"
                         % dev.platform)
    channel = args.decay == "channel"
    B, T, Hk, Hv, D = (int(x) for x in
                       (args.shape or _SHAPES[args.decay]).split(","))
    dt, C, n = jnp.dtype(args.dtype), args.chunk, args.iters
    if not dk.tiles(T, D, D, C, dt):
        raise SystemExit("delta_rule_probe: the kernels do not take this "
                         "shape (delta_rule_kernels.tiles)")
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    q, k = (jax.random.normal(kk, (B, T, Hk, D), dt) for kk in keys[:2])
    v, do = (jax.random.normal(kk, (B, T, Hv, D), dt) for kk in keys[2:4])
    if channel:     # the cell's decays: exp(g) 0.90-0.994 a token and channel
        g = -0.05 * jax.nn.softplus(
            1.4 * jax.random.normal(keys[4], (B, T, Hv, D)))
    else:           # the cell's decays: exp(g) 0.975-0.993 a token
        g = -0.01 * jax.nn.softplus(
            jax.random.normal(keys[4], (B, T, Hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (B, T, Hv)))
    w = jax.random.normal(keys[6], (B, T, Hv, D), jnp.float32)
    five = (q, k, v, g, beta)
    res = {"device": dev.device_kind, "decay": args.decay,
           "shape": [B, T, Hk, Hv, D], "dtype": args.dtype, "chunk": C,
           "ms": {}}

    def record(name, fn):
        try:
            res["ms"][name] = fn()
        except Exception as e:  # noqa: BLE001 — one variant, not the run
            res["ms"][name] = {"error": str(e)[:400]}
        print(name, res["ms"][name], flush=True)

    plain = functools.partial(la._plain_channels if channel else la._plain,
                              C=C, carry_state=True)
    kernel = functools.partial(la._through_kernels, C=C, carry_state=True)
    record("plain", lambda: _pair(plain, five, w, n))
    record("kernel", lambda: _pair(kernel, five, w, n))

    with jax.default_matmul_precision("highest"):
        exact = jax.jit(_recurrence)(*five)
    record("recurrence_max", lambda: float(jnp.abs(exact).max()))
    for name, rule in (("plain", plain), ("kernel", kernel)):
        record(name + "_max_error", lambda rule=rule: float(jnp.abs(
            jax.jit(rule)(*five).astype(jnp.float32) - exact).max()))

    def parts():
        qn, kn = (la._l2norm(x).astype(dt) for x in (q, k))
        rows = lambda x: x.transpose(0, 2, 1).reshape(B, Hv, T // C, C)  # noqa: E731
        c = (jnp.cumsum(g.reshape(B, T // C, C, Hv, D), 2) if channel
             else jnp.cumsum(rows(g), -1))
        ins = (qn, kn, v, c, rows(beta))
        fwd, save = (jax.jit(lambda *a, s=s: dk._fwd_on(
            *a, True, s, dk._interpret())) for s in (False, True))
        bwd = jax.jit(lambda *a: dk._bwd_on(*a, True, dk._interpret()))
        return [_ms(fwd, ins, n), _ms(save, ins, n),
                _ms(bwd, (*ins, *save(*ins)[1], do), n)]
    record("fwd_save_bwd", parts)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "delta_rule_probe.json"),
              "w") as f:
        json.dump(res, f)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
