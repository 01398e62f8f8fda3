"""Pallas TPU kernels for hot ops.

The reference hand-writes CUDA kernels for its hot paths (mshadow
kernels, cuDNN calls — SURVEY.md N5/N16); the TPU analog is Pallas.
XLA already fuses elementwise chains into matmuls, so kernels here
target the cases XLA does NOT fuse well:

- flash_attention: O(T) -memory fused attention (whole q-block x kv
  sweep in VMEM, online softmax) — the single-chip twin of
  parallel/ring_attention (which distributes the same math over the
  'sp' axis).
- layer_norm: one-pass fused mean/var/normalize/affine per row block.

On non-TPU backends (the CPU test mesh) kernels run under
`interpret=True`, so tests validate the same code path end to end.
Patterns follow /opt/skills/guides/pallas_guide.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register

__all__ = ["flash_attention", "pallas_layer_norm",
           "fused_sgd_momentum", "conv1x1_bn_stats"]

_NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal,
                      scale, q_blocks_offset):
    """One (batch*head, q-block) program: sweep kv blocks with online
    softmax. Refs are (BLOCK_Q, D) for q/o and (T, D) for k/v."""
    q = q_ref[0].astype(jnp.float32) * scale     # (BQ, D)
    T = k_ref.shape[1]
    BQ = q.shape[0]
    iq = pl.program_id(1)
    n_k = T // block_k

    def body(ik, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[0, pl.ds(ik * block_k, block_k), :] \
            .astype(jnp.float32)                  # (BK, D)
        v = v_ref[0, pl.ds(ik * block_k, block_k), :] \
            .astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)   # (BQ, BK)
        if causal:
            rows = iq * BQ + lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (BQ, block_k), 1)
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((BQ, q.shape[1]), jnp.float32)
    m0 = jnp.full((BQ,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((BQ,), jnp.float32)
    if causal:
        # only sweep kv blocks that intersect the causal triangle
        n_sweep = jnp.minimum(((iq + 1) * BQ + block_k - 1) // block_k,
                              n_k)
        acc, m, l = lax.fori_loop(0, n_sweep, body, (acc0, m0, l0))
    else:
        acc, m, l = lax.fori_loop(0, n_k, body, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0] = out.astype(o_ref.dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k):
    B, H, T, D = q.shape
    q3 = q.reshape(B * H, T, D)
    k3 = k.reshape(B * H, T, D)
    v3 = v.reshape(B * H, T, D)
    bq = min(block_q, T)
    bk = min(block_k, T)
    assert T % bq == 0 and T % bk == 0, \
        "flash_attention: T must divide block sizes (pad the sequence)"
    scale = 1.0 / (D ** 0.5)
    kernel = functools.partial(_flash_fwd_kernel, block_k=bk,
                               causal=causal, scale=scale,
                               q_blocks_offset=0)
    grid = (B * H, T // bq)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, T, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        interpret=_interpret(),
    )(q3, k3, v3)
    return out.reshape(B, H, T, D)


def _attn_reference(q, k, v, causal):
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Tq, Tk = s.shape[-2:]
        mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool))
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, block_q=128, block_k=128):
    """Fused attention, q/k/v: (B, H, T, D). Pallas forward; the
    backward recomputes the scores one block of `block_q` query rows at
    a time (ops/attention.py), so neither pass holds a T x T array."""
    return _flash_fwd(q, k, v, causal, block_q, block_k)


def _fa_fwd(q, k, v, causal, block_q, block_k):
    return _flash_fwd(q, k, v, causal, block_q, block_k), (q, k, v)


def _fa_bwd(causal, block_q, block_k, res, g):
    from .attention import blocked_causal_attention
    q, k, v = res
    t = lambda x: x.transpose(0, 2, 1, 3)                # noqa: E731
    _, vjp = jax.vjp(lambda a, b, c: t(blocked_causal_attention(
        t(a), t(b), t(c), block_q, causal=causal)), q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------
def _ln_kernel(x_ref, g_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * lax.rsqrt(var + eps)
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def pallas_layer_norm(x, gamma, beta, eps=1e-5, block_rows=128):
    """Fused LayerNorm over the last axis; x: (..., D)."""
    shape = x.shape
    D = shape[-1]
    x2 = x.reshape(-1, D)
    N = x2.shape[0]
    br = min(block_rows, N)
    pad = (-N) % br
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, D), x2.dtype)], axis=0)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=(x2.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        interpret=_interpret(),
    )(x2, gamma, beta)
    if pad:
        out = out[:N]
    return out.reshape(shape)


# ---------------------------------------------------------------------------
# op registrations (nd.contrib.flash_attention / sym.contrib...)
# ---------------------------------------------------------------------------
@register("_contrib_flash_attention")
def _flash_attention_op(q, k, v, *, causal=False, block_q=128,
                        block_k=128):
    return flash_attention(q, k, v, causal, block_q, block_k)


# ---------------------------------------------------------------------------
# fused optimizer update (PERF.md §2: the conv-dW + SGD "multiply/
# subtract" fusion family is the dominant HBM-bound step component;
# this kernel is the hand-written comparison point for the roofline —
# one pass reading w/g/m and writing w'/m' at minimum possible bytes)
# ---------------------------------------------------------------------------
def _sgd_mom_kernel(w_ref, g_ref, m_ref, ow_ref, om_ref, *, lr,
                    momentum, wd, rescale):
    w = w_ref[...]
    g = g_ref[...] * rescale + wd * w
    m = momentum * m_ref[...].astype(g.dtype) + g
    om_ref[...] = m.astype(om_ref.dtype)
    ow_ref[...] = (w - lr * m.astype(w.dtype)).astype(ow_ref.dtype)


def fused_sgd_momentum(w, g, m, lr, momentum=0.9, wd=0.0, rescale=1.0,
                      block_rows=256):
    """Momentum-SGD update as one Pallas pass: m' = momentum·m +
    rescale·g + wd·w; w' = w − lr·m'. Returns (w', m').

    Arrays of any shape are flattened and padded to (rows, 128) VPU
    lanes; already-aligned 2D inputs take the zero-copy path (the MFU
    probe feeds those). m may be a wider dtype than w (fp32 momentum
    with bf16 weights): accumulation happens in the promoted dtype and
    each output is cast back to its input's dtype. Elementwise
    traffic = 3 reads + 2 writes — the same as XLA's fused update, so
    any measured win/loss against the XLA version is scheduling, not
    algorithm (tools/mfu_probe.py records the outcome either way)."""
    orig_shape, n = w.shape, w.size
    cols = 128
    # small tensors get one small block, not a 32k-element round-up
    block_rows = max(8, min(block_rows, -(-n // cols)))
    aligned = (w.ndim == 2 and w.shape[1] == cols
               and w.shape[0] % block_rows == 0)

    def prep(x):
        if aligned:
            return x
        flat = jnp.ravel(x)
        rows = -(-n // cols)
        pad_rows = -(-rows // block_rows) * block_rows
        flat = jnp.pad(flat, (0, pad_rows * cols - n))
        return flat.reshape(pad_rows, cols)

    W, G, M = prep(w), prep(g), prep(m)
    kernel = functools.partial(_sgd_mom_kernel, lr=lr, momentum=momentum,
                               wd=wd, rescale=rescale)
    blocks = W.shape[0] // block_rows
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    ow, om = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(W.shape, W.dtype),
                   jax.ShapeDtypeStruct(M.shape, M.dtype)),
        grid=(blocks,),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        interpret=_interpret(),
    )(W, G, M)
    if aligned:
        return ow, om
    unpad = lambda x: x.reshape(-1)[:n].reshape(orig_shape)  # noqa: E731
    return unpad(ow), unpad(om)


# ---------------------------------------------------------------------------
# 1x1-conv + BN-statistics epilogue fusion
# ---------------------------------------------------------------------------
def _conv1x1_bn_kernel(x_ref, w_ref, y_ref, s_ref, ss_ref):
    i = pl.program_id(0)
    y = jnp.dot(x_ref[:].astype(jnp.float32),
                w_ref[:].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)

    @pl.when(i == 0)
    def _init():
        s_ref[:] = jnp.zeros_like(s_ref)
        ss_ref[:] = jnp.zeros_like(ss_ref)

    # TPU grids run sequentially, so read-modify-write accumulation
    # across grid steps is well-defined (same contract the guide's
    # reduction pattern relies on)
    s_ref[:] += jnp.sum(y, axis=0)
    ss_ref[:] += jnp.sum(y * y, axis=0)


def conv1x1_bn_stats(x, w, block_rows=256):
    """y = x @ w with the BN batch statistics accumulated in the SAME
    kernel (per-channel sum / sum-of-squares as each output block is
    produced), so the statistics pass costs zero extra HBM reads of y.

    This is the VERDICT-r4 'BN-stat fusion into the producer epilogue'
    prototype: the profiler trace pinned convert_reduce_fusion (BN
    stats, a full re-read of every conv output) at ~5 ms/step of the
    46 ms ResNet-50 step. 1x1 convs — the majority of ResNet-50's
    layers — ARE matmuls, so their epilogue is ours to own.

    x: (M, Cin) row-major activations (N*H*W flattened), w: (Cin, Cout).
    Returns (y, mean, var) with fp32 statistics. Numerics: stats use the
    single-pass E[x^2]-E[x]^2 form, matching ops/nn.py's BatchNorm.
    Measured on-chip by tools/mfu_probe.py (stage 'bn_fusion'); wire
    into the conv path only if it beats the XLA schedule there.
    """
    M, Cin = x.shape
    Cout = w.shape[1]
    br = min(block_rows, M)
    pad = (-M) % br
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    blocks = xp.shape[0] // br
    y, s, ss = pl.pallas_call(
        _conv1x1_bn_kernel,
        out_shape=(jax.ShapeDtypeStruct(xp.shape[:1] + (Cout,), x.dtype),
                   jax.ShapeDtypeStruct((Cout,), jnp.float32),
                   jax.ShapeDtypeStruct((Cout,), jnp.float32)),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((br, Cin), lambda i: (i, 0)),
                  pl.BlockSpec((Cin, Cout), lambda i: (0, 0))],
        out_specs=(pl.BlockSpec((br, Cout), lambda i: (i, 0)),
                   pl.BlockSpec((Cout,), lambda i: (0,)),
                   pl.BlockSpec((Cout,), lambda i: (0,))),
        interpret=_interpret(),
    )(xp, w)
    if pad:
        y = y[:M]
        # padded rows contribute zeros to s and ss — correct the count
    mean = s / M
    var = jnp.maximum(ss / M - mean * mean, 0.0)
    return y, mean, var
