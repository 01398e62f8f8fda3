"""Gated delta-rule linear attention (Gated DeltaNet) and the small ops
around it: a causal depthwise convolution, RMS norms, a gated RMS norm.

The recurrence, per value head, with a matrix state S (Dk x Dv) from
zero and per token t a log decay g_t <= 0 and a step beta_t in (0, 1):

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;
    o_t = S^T q_t

`gated_delta_rule` computes it in chunks of C tokens. With c_t the
running sum of g inside a chunk, S0 the state the chunk starts from and
G[t, s] = exp(c_t - c_s):

    (I + A) D = beta (V - exp(c) K S0),   A[t, s] = beta_t G[t, s] k_t.k_s  (s < t)
    o_t = exp(c_t) S0^T q_t + sum_{s <= t} G[t, s] (k_s.q_t) d_s
    S_C = exp(c_C) S0 + sum_s exp(c_C - c_s) k_s d_s^T

so D = U - W S0 with U = (I + A)^-1 (beta V) and W = (I + A)^-1
(beta exp(c) K), which do not depend on the state; S is then carried in
float32 through four matrix products a chunk. Nothing is ever T x T.

Two paths compute this, chosen from the shape and the mesh the call is
traced with (`_kernel_shard`; counted in `linear_attention.delta.path`).
Where the heads are whole 128-lane tiles the Pallas kernels of
`ops/delta_rule_kernels.py` do a chunk's whole work in VMEM with the
state in scratch across the chunks, and their backward is a second
kernel that walks the chunks in reverse with the state's cotangent in
scratch and takes `jax.vjp` of the forward's own tile function.
Everywhere else (`_plain`) U and W are solved for every chunk at once (a
unit lower-triangular solve in float32), a `lax.scan` over the chunks
carries S, and the backward is JAX's transpose of that program: a
reverse scan, the state's cotangent carried the other way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec

from ..observability import registry as _obs
from . import delta_rule_kernels
from .registry import register

__all__ = ["gated_delta_rule", "causal_conv1d", "rms_norm", "gated_rms_norm"]

_L2_EPS = 1e-6


def _precision(dtype):
    """float32 operands multiply as float32 (the TPU's default is one
    bfloat16 pass); bfloat16 operands multiply as they are."""
    return lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + _L2_EPS)


DELTA_PATH = _obs.counter(
    "linear_attention.delta.path",
    "Times gated_delta_rule was traced into a program, by what its shape "
    "and the mesh chose (label path: kernel = the Pallas kernels of "
    "ops/delta_rule_kernels.py, Dk and Dv multiples of 128 and the chunk "
    "of a sublane tile; plain = the XLA operations and a lax.scan)")


def _kernel_shard(B, T, Dk, Dv, C, dtype):
    """How the kernels take this call, or None where they do not: the
    shape has to tile (`delta_rule_kernels.tiles`), and XLA cannot split
    a custom call, so under a mesh (`parallel.use_mesh`) whose only axis
    wider than one is `dp` they run per shard of the batch and under any
    other mesh the plain path runs, which XLA partitions. Returns what
    wraps the function of (q, k, v, g, beta)."""
    from ..parallel.mesh import current_mesh, shard_map_compat
    from .pallas_kernels import _axis_bound
    if not delta_rule_kernels.tiles(T, Dk, Dv, C, dtype):
        return None
    mesh = current_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1] if mesh else []
    # one device, or traced inside a shard_map over every wide axis: the
    # arrays here are one device's already
    if all(_axis_bound(a) for a in wide):
        return lambda fn: fn
    if wide != ["dp"] or B % mesh.shape["dp"]:
        return None
    spec = PartitionSpec("dp")
    return lambda fn: shard_map_compat(fn, mesh, (spec,) * 5, spec)


def _through_kernels(q, k, v, g, beta, C, carry_state):
    """What stays outside the kernels: the normalisation of q and k and
    the running sum of g inside each chunk, one small XLA pass each."""
    B, T, _, Dk = q.shape
    Hv, cd = v.shape[2], v.dtype

    def rows(x):                          # (B,T,Hv) -> (B,Hv,N,C)
        return x.astype(jnp.float32).transpose(0, 2, 1).reshape(
            B, Hv, T // C, C)

    return delta_rule_kernels.delta_rule(
        (_l2norm(q) * (Dk ** -0.5)).astype(cd), _l2norm(k).astype(cd), v,
        jnp.cumsum(rows(g), axis=-1), rows(beta), carry_state)


def gated_delta_rule(q, k, v, g, beta, chunk=64, carry_state=True):
    """q, k: (B, T, Hk, Dk); v: (B, T, Hv, Dv) with Hv a multiple of Hk
    (key head h // (Hv // Hk) serves value head h); g, beta: (B, T, Hv)
    float32. q and k are L2-normalised over the head here and q scaled
    by Dk^-1/2. Returns o (B, T, Hv, Dv) in v's dtype. Matrix products
    take their operands in v's dtype (the normalised q and k too) and add
    up in float32; the decays, the state and the triangular solve are
    float32 (the kernels make (I + A)^-1 from float32 products at
    `HIGHEST` instead of solving). `carry_state=False` starts every
    chunk from a zero state: the fault the tests plant.
    The path is chosen from the shape and the mesh the call is traced
    with (`_kernel_shard`) and counted in `linear_attention.delta.path`."""
    B, T, _, Dk = q.shape
    C = min(int(chunk), T)
    if T % C:
        raise ValueError("gated_delta_rule: %d tokens do not divide into "
                         "chunks of %d" % (T, C))
    shard = _kernel_shard(B, T, Dk, v.shape[3], C, v.dtype)
    DELTA_PATH.inc(path="plain" if shard is None else "kernel")
    if shard is None:
        return _plain(q, k, v, g, beta, C, carry_state)
    return shard(functools.partial(_through_kernels, C=C,
                                   carry_state=carry_state))(q, k, v, g, beta)


def _plain(q, k, v, g, beta, C, carry_state):
    """The chunked rule as XLA operations: every chunk's U and W from
    one batched triangular solve, then a `lax.scan` over the chunks."""
    B, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[2], v.shape[3]
    N = T // C
    cd = v.dtype
    prec = _precision(cd)
    f32 = jnp.float32

    def heads(x, h):                      # (B,T,h,D) -> (B,Hv,N,C,D)
        x = jnp.repeat(x, Hv // h, axis=2) if h != Hv else x
        return x.transpose(0, 2, 1, 3).reshape(B, Hv, N, C, x.shape[-1])

    # normalised in float32, kept in the operands' dtype
    qn = heads((_l2norm(q) * (Dk ** -0.5)).astype(cd), Hk)
    kn = heads(_l2norm(k).astype(cd), Hk)
    vv = heads(v, Hv).astype(f32)
    gg = g.astype(f32).transpose(0, 2, 1).reshape(B, Hv, N, C)
    bb = beta.astype(f32).transpose(0, 2, 1).reshape(B, Hv, N, C)

    c = jnp.cumsum(gg, axis=-1)                          # (B,Hv,N,C)
    t_idx = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # exp(c_t - c_s) for s <= t; the masked part would overflow
    diff = jnp.where(s_idx <= t_idx, c[..., :, None] - c[..., None, :], -jnp.inf)
    G = jnp.exp(diff)
    kk = jnp.einsum("bhntd,bhnsd->bhnts", kn, kn, precision=prec,
                    preferred_element_type=f32)
    A = jnp.where(s_idx < t_idx, bb[..., :, None] * G * kk, 0.0)
    rhs = jnp.concatenate(
        [bb[..., None] * vv, (bb * jnp.exp(c))[..., None] * kn.astype(f32)],
        axis=-1)
    UW = lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    U, W = UW[..., :Dv], UW[..., Dv:].astype(cd)
    qk = jnp.einsum("bhntd,bhnsd->bhnts", qn, kn, precision=prec,
                    preferred_element_type=f32)
    P = (G * qk).astype(cd)                              # s <= t by G
    q_in = (jnp.exp(c)[..., None] * qn.astype(f32)).astype(cd)
    c_end = c[..., -1]                                   # (B,Hv,N)
    k_out = (jnp.exp(c_end[..., None] - c)[..., None]
             * kn.astype(f32)).astype(cd)
    decay = jnp.exp(c_end)

    def body(S, xs):
        U_n, W_n, P_n, q_n, k_n, d_n = xs
        Sc = S.astype(cd)
        delta = U_n - jnp.einsum("bhtk,bhkv->bhtv", W_n, Sc,
                                 precision=prec, preferred_element_type=f32)
        dc = delta.astype(cd)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_n, Sc, precision=prec,
                        preferred_element_type=f32)
             + jnp.einsum("bhts,bhsv->bhtv", P_n, dc, precision=prec,
                          preferred_element_type=f32))
        S_new = d_n[..., None, None] * S + jnp.einsum(
            "bhtk,bhtv->bhkv", k_n, dc, precision=prec,
            preferred_element_type=f32)
        return (S_new if carry_state else S), o

    per_chunk = lambda x: jnp.moveaxis(x, 2, 0)          # noqa: E731
    S0 = jnp.zeros((B, Hv, Dk, Dv), f32)
    _, o = lax.scan(body, S0, tuple(per_chunk(x) for x in
                                    (U, W, P, q_in, k_out, decay)))
    o = jnp.moveaxis(o, 0, 2).reshape(B, Hv, T, Dv)
    return o.transpose(0, 2, 1, 3).astype(cd)


def _taps(xp, w, T):
    """sum_j w[:, j] * xp[:, j:j + T], float32."""
    return sum(xp[:, j:j + T, :].astype(jnp.float32)
               * w[:, j].astype(jnp.float32) for j in range(w.shape[1]))


@jax.custom_vjp
def _depthwise_causal(x, w):
    K = w.shape[1]
    return _taps(jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0))), w,
                 x.shape[1]).astype(x.dtype)


def _dc_fwd(x, w):
    return _depthwise_causal(x, w), (x, w)


def _dc_bwd(res, dy):
    """dx is the same taps run backwards in time over dy; dw[:, j] sums
    dy[t] x[t - (K - 1) + j] over the batch and time. Each is one pass
    over the inputs as they are stored (autodiff's transpose of the
    forward's pads and casts made a float32 copy a tap)."""
    x, w = res
    K, T = w.shape[1], x.shape[1]
    dyp = jnp.pad(dy, ((0, 0), (0, K - 1), (0, 0)))
    dx = _taps(dyp, w[:, ::-1], T).astype(x.dtype)
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(dy.astype(jnp.float32)
                            * xp[:, j:j + T, :].astype(jnp.float32),
                            axis=(0, 1)) for j in range(K)], axis=1)
    return dx, dw.astype(w.dtype)


_depthwise_causal.defvjp(_dc_fwd, _dc_bwd)


def causal_conv1d(x, w, activation=None):
    """Depthwise causal convolution over time. x: (B, T, C); w: (C, K);
    y[t] = sum_j w[:, j] x[t - (K - 1) + j], zeros before the start."""
    y = _depthwise_causal(x, w)
    if activation == "silu":
        y = jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype)
    elif activation is not None:
        raise ValueError("causal_conv1d: unknown activation %r" % activation)
    return y


def rms_norm(x, w, eps=1e-6, offset=0.0):
    """x / sqrt(mean(x^2) + eps) * (offset + w) over the last axis, in
    float32, returned in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (offset + w.astype(jnp.float32))).astype(x.dtype)


def gated_rms_norm(x, z, w, eps=1e-6):
    """w * x / sqrt(mean(x^2) + eps) * silu(z) over the last axis."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * w.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------
@register("_contrib_gated_delta_rule", num_outputs=2, visible_outputs=1,
          aux_write={1: 7}, counters={7: ("linear_attention.chunks",)})
def _gated_delta_rule_op(q, k, v, a, b, A_log, dt_bias, stats, *, chunk=64):
    """Gated DeltaNet's mixer core. a, b: (B, T, Hv) the decay's and the
    step's pre-activations; g = -exp(A_log) softplus(a + dt_bias) and
    beta = sigmoid(b) in float32. `stats` (1,) is a device counter: the
    chunks scanned in the last step."""
    f32 = jnp.float32
    g = -jnp.exp(A_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))
    beta = jax.nn.sigmoid(b.astype(f32))
    o = gated_delta_rule(q, k, v, g, beta, chunk=chunk)
    chunks = q.shape[0] * (q.shape[1] // min(int(chunk), q.shape[1]))
    return o, jnp.full(stats.shape, chunks, stats.dtype)


@register("_contrib_causal_conv1d")
def _causal_conv1d_op(x, weight, *, activation=None):
    return causal_conv1d(x, weight, activation)


@register("_contrib_rms_norm")
def _rms_norm_op(x, weight, *, eps=1e-6, offset=0.0):
    return rms_norm(x, weight, eps, offset)


@register("_contrib_gated_rms_norm")
def _gated_rms_norm_op(x, z, weight, *, eps=1e-6):
    return gated_rms_norm(x, z, weight, eps)
