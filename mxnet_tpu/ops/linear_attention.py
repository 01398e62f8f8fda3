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
traced with (`_kernel_shard`; counted in `linear_attention.delta.path`),
whatever g's rank (below).
Where the heads are whole 128-lane tiles the Pallas kernels of
`ops/delta_rule_kernels.py` do a chunk's whole work in VMEM with the
state in scratch across the chunks, and their backward is a second
kernel that walks the chunks in reverse with the state's cotangent in
scratch and takes `jax.vjp` of the forward's own tile function.
Everywhere else (`_plain`) U and W are solved for every chunk at once (a
unit lower-triangular solve in float32), a `lax.scan` over the chunks
carries S, and the backward is JAX's transpose of that program: a
reverse scan, the state's cotangent carried the other way.

A decay may also be given per key channel, g (B, T, Hv, Dk): the state's
row d then decays by exp(g_t[d]) (Kimi Delta Attention). The decay no
longer factors out of the products of keys,

    A[t, s] = beta_t sum_d k_t[d] k_s[d] exp(c_t[d] - c_s[d])   (s < t)

so each path has a statement of a chunk of its own for that call, chosen
by the same rule and counted alike: the kernels
`gated_delta_rule_channels_fwd` / `_bwd` where the shape tiles, and
`_plain_channels` elsewhere, a `lax.scan` over the chunks whose step does
one chunk's whole work under `jax.checkpoint`. Both keep every exponent
at or below zero (their docstrings say how). The plain path holds
O(T H D) arrays and the chunks' states and nothing of a chunk's C x C
systems beyond the chunk in flight; it is the kernels' reference in the
tests and what an odd-shaped or sharded caller gets.
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
    "ops/delta_rule_kernels.py, for one decay a head or one a key "
    "channel, Dk and Dv multiples of 128 and the chunk of a sublane tile; "
    "plain = the XLA operations and a lax.scan)")


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
    the running sum of g inside each chunk, one small XLA pass each. A
    decay a key channel is summed where it lies, (B, N, C, Hv, Dk): no
    repeat and no transpose of heads."""
    B, T, _, Dk = q.shape
    Hv, cd = v.shape[2], v.dtype

    def rows(x):                          # (B,T,Hv) -> (B,Hv,N,C)
        return x.astype(jnp.float32).transpose(0, 2, 1).reshape(
            B, Hv, T // C, C)

    qn, kn = (_l2norm(q) * (Dk ** -0.5)).astype(cd), _l2norm(k).astype(cd)
    if g.ndim == 4:                       # a channel: where it lies
        c = jnp.cumsum(g.astype(jnp.float32).reshape(
            B, T // C, C, Hv, Dk), axis=2)
    else:
        c = jnp.cumsum(rows(g), axis=-1)
    return delta_rule_kernels.delta_rule(qn, kn, v, c, rows(beta),
                                         carry_state)


def gated_delta_rule(q, k, v, g, beta, chunk=64, carry_state=True):
    """q, k: (B, T, Hk, Dk); v: (B, T, Hv, Dv) with Hv a multiple of Hk
    (key head h // (Hv // Hk) serves value head h); beta: (B, T, Hv) and
    g: (B, T, Hv), one log decay a head, or (B, T, Hv, Dk), one a key
    channel (the state's row d decays by exp(g[d])); float32. q and k
    are L2-normalised over the head here and q scaled by Dk^-1/2.
    Returns o (B, T, Hv, Dv) in v's dtype. Matrix products take their
    operands in v's dtype (the normalised q and k too) and add up in
    float32; the decays, the state and the triangular solve are float32
    (the kernels make (I + A)^-1 from float32 products at `HIGHEST`
    instead of solving, and with a decay a channel multiply the tokens
    of one 16-row block in float32 at `HIGHEST` where the plain path
    sums them pair by pair). `carry_state=False` starts every chunk from
    a zero state: the fault the tests plant.
    The path is chosen from the shape and the mesh the call is traced
    with (`_kernel_shard`), whatever g's rank, and counted in
    `linear_attention.delta.path`."""
    B, T, _, Dk = q.shape
    C = min(int(chunk), T)
    if T % C:
        raise ValueError("gated_delta_rule: %d tokens do not divide into "
                         "chunks of %d" % (T, C))
    shard = _kernel_shard(B, T, Dk, v.shape[3], C, v.dtype)
    DELTA_PATH.inc(path="plain" if shard is None else "kernel")
    if shard is None:
        plain = _plain_channels if g.ndim == 4 else _plain
        return plain(q, k, v, g, beta, C, carry_state)
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


_SUB = 16        # rows of a chunk that share one reference row


def _plain_channels(q, k, v, g, beta, C, carry_state):
    """The chunked rule with one decay a key channel, g (B, T, Hv, Dk).
    With c_t (Dk) the running sum of g inside the chunk:

        A[t, s] = beta_t sum_d k_t[d] k_s[d] exp(c_t[d] - c_s[d])   (s < t)
        (I + A) D = beta (V - (K * exp(c)) S0)
        o_t = (q_t * exp(c_t))^T S0 + sum_{s <= t} P[t, s] d_s,
        P[t, s] = sum_d q_t[d] k_s[d] exp(c_t[d] - c_s[d])
        S_C = Diag(exp(c_C)) S0 + sum_s (k_s * exp(c_C - c_s)) d_s^T

    Written as (k_t exp(c_t)) . (k_s exp(-c_s)) the second factor
    overflows float32 once a channel decays by e^88 inside a chunk. Here
    no exponent is ever above zero: the chunk's rows are cut into blocks
    of 16 (the published kernels' way), and for the columns BEFORE row
    block i both factors are taken against the block's first row r,
    (k_t exp(c_t - c_r)) . (k_s exp(c_r - c_s)), each at most one in size
    (a matrix product, operands in v's dtype, float32 sums); INSIDE a
    block exp(c_t - c_s) is formed for each pair and channel and summed
    as it is (float32 elementwise, 16 x 16 x Dk a block). One `lax.scan`
    step does one chunk, all heads at once, under `jax.checkpoint`: the
    backward pass keeps the state each chunk started from and computes
    the chunk again. Decays, solve and state are float32."""
    B, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[2], v.shape[3]
    N = T // C
    sub = _SUB if C % _SUB == 0 else C
    R = C // sub
    cd = v.dtype
    prec = _precision(cd)
    f32 = jnp.float32

    def chunks(x, h=Hv):                  # (B,T,h,...) -> (N,B,Hv,C,...)
        x = jnp.repeat(x, Hv // h, axis=2) if h != Hv else x
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    # normalised in float32, kept in the operands' dtype
    xs = (chunks((_l2norm(q) * (Dk ** -0.5)).astype(cd), Hk),
          chunks(_l2norm(k).astype(cd), Hk), chunks(v),
          chunks(g.astype(f32)), chunks(beta.astype(f32)))

    t_idx = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_idx = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # column s lies before row block i; rows and columns of one block
    before = (lax.broadcasted_iota(jnp.int32, (R, C), 1)
              < sub * lax.broadcasted_iota(jnp.int32, (R, C), 0))
    tri = (lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
           <= lax.broadcasted_iota(jnp.int32, (sub, sub), 0))
    same = jnp.eye(R, dtype=bool)

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=prec,
                          preferred_element_type=f32)

    @jax.checkpoint
    def chunk(S, x):
        q_n, k_n, v_n, g_n, b_n = x       # (B,Hv,C,D), (B,Hv,C)
        c = jnp.cumsum(g_n, axis=2)       # <= 0, falling with t
        qf, kf = q_n.astype(f32), k_n.astype(f32)
        blocks = lambda a: a.reshape(B, Hv, R, sub, Dk)  # noqa: E731
        cb, qb, kb = blocks(c), blocks(qf), blocks(kf)
        since_start = jnp.exp(c)                         # <= 1
        ref = cb[:, :, :, :1]                            # block i's first row
        fall = jnp.exp(cb - ref)                         # rows, <= 1
        # columns before block i, against its first row: <= 1, else 0
        k_cols = (kf[:, :, None] * jnp.exp(jnp.where(
            before[:, :, None], ref - c[:, :, None], -jnp.inf))).astype(cd)
        spec = "bhrtd,bhrsd->bhrts"
        kk = mm(spec, (kb * fall).astype(cd), k_cols).reshape(B, Hv, C, C)
        qk = mm(spec, (qb * fall).astype(cd), k_cols).reshape(B, Hv, C, C)
        # inside a block, pair by pair
        E = jnp.exp(jnp.where(tri[:, :, None], cb[:, :, :, :, None]
                              - cb[:, :, :, None, :], -jnp.inf))
        kE = kb[:, :, :, None, :] * E                    # (B,Hv,R,sub,sub,Dk)

        def on_diagonal(rows):
            inner = jnp.sum(rows[:, :, :, :, None] * kE, axis=-1)
            return jnp.where(same[:, None, :, None], inner[:, :, :, :, None],
                             0.0).reshape(B, Hv, C, C)

        kk, qk = kk + on_diagonal(kb), qk + on_diagonal(qb)
        A = jnp.where(s_idx < t_idx, b_n[..., None] * kk, 0.0)
        P = jnp.where(s_idx <= t_idx, qk, 0.0).astype(cd)
        Sc = S.astype(cd)
        rhs = b_n[..., None] * (v_n.astype(f32) - mm(
            "bhtk,bhkv->bhtv", (kf * since_start).astype(cd), Sc))
        delta = lax.linalg.triangular_solve(
            A + jnp.eye(C, dtype=f32), rhs, left_side=True, lower=True,
            unit_diagonal=True)
        dc = delta.astype(cd)
        o = (mm("bhtk,bhkv->bhtv", (qf * since_start).astype(cd), Sc)
             + mm("bhts,bhsv->bhtv", P, dc))
        c_end = c[:, :, -1:]                             # (B,Hv,1,Dk)
        S_new = jnp.swapaxes(jnp.exp(c_end), 2, 3) * S + mm(
            "bhtk,bhtv->bhkv", (kf * jnp.exp(c_end - c)).astype(cd), dc)
        return (S_new if carry_state else S), o.astype(cd)

    _, o = lax.scan(chunk, jnp.zeros((B, Hv, Dk, Dv), f32), xs)
    # (N,B,Hv,C,Dv) -> (B,T,Hv,Dv)
    return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, T, Hv, Dv)


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


_GATES = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


def gated_rms_norm(x, z, w, eps=1e-6, activation="silu"):
    """w * x / sqrt(mean(x^2) + eps) * act(z) over the last axis, act
    SiLU or the sigmoid."""
    if activation not in _GATES:
        raise ValueError("gated_rms_norm: unknown activation %r" % activation)
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * w.astype(jnp.float32) * _GATES[activation](z.astype(jnp.float32))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# registrations
# ---------------------------------------------------------------------------
@register("_contrib_gated_delta_rule", num_outputs=2, visible_outputs=1,
          aux_write={1: 7}, counters={7: ("linear_attention.chunks",)})
def _gated_delta_rule_op(q, k, v, a, b, A_log, dt_bias, stats, *, chunk=64):
    """Gated DeltaNet's mixer core. b: (B, T, Hv) the step's
    pre-activation, a the decay's: (B, T, Hv) with dt_bias (Hv,), one
    decay a head, or (B, T, Hv, Dk) with dt_bias (Hv * Dk,), one a key
    channel (Kimi Delta Attention); A_log (Hv,) either way.
    g = -exp(A_log) softplus(a + dt_bias) and beta = sigmoid(b) in
    float32. `stats` (1,) is a device counter: the chunks scanned in the
    last step."""
    f32 = jnp.float32
    A = jnp.exp(A_log.astype(f32))
    if a.ndim == 4:
        A, dt_bias = A[:, None], dt_bias.reshape(a.shape[2:])
    g = -A * jax.nn.softplus(a.astype(f32) + dt_bias.astype(f32))
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
def _gated_rms_norm_op(x, z, weight, *, eps=1e-6, activation="silu"):
    return gated_rms_norm(x, z, weight, eps, activation)
