"""Pallas TPU kernels for hot ops.

The reference hand-writes CUDA kernels for its hot paths (mshadow
kernels, cuDNN calls — SURVEY.md N5/N16); the TPU analog is Pallas.
XLA already fuses elementwise chains into matmuls, so kernels here
target the cases XLA does NOT fuse well:

- flash_attention: attention whose scores never leave VMEM, forward
  and backward (GPTDecoder's attention wherever its shape tiles) — the
  single-chip twin of parallel/ring_attention (which distributes the
  same math over the 'sp' axis).
- layer_norm: one-pass fused mean/var/normalize/affine per row block.

On non-TPU backends (the CPU test mesh) kernels run under
`interpret=True`, so tests validate the same code path end to end.
Patterns follow /opt/skills/guides/pallas_guide.md.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from ..observability import registry as _obs
from .registry import register

__all__ = ["flash_attention", "pallas_layer_norm",
           "fused_sgd_momentum", "conv1x1_bn_stats"]

_NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
# Three kernels, each a grid over (batch*head, outer block, inner block)
# whose innermost axis is the reduction, with the accumulators in VMEM
# scratch: the forward sweeps kv blocks for a q block (online softmax, and
# writes the rows' log-sum-exp beside o), dQ sweeps kv blocks for a q
# block, dK/dV sweep q blocks for a kv block. Both backward kernels
# recompute p = exp(s - lse) from q, k and the saved log-sum-exp, so no
# T x T array exists outside VMEM in either pass. Products take their
# operands in the inputs' dtype (_ambient_precision) and add up in float32;
# scores, mask, exponentials and sums are float32. Under the
# causal mask a block wholly above the diagonal is skipped (its index map
# repeats the last block needed, so nothing is fetched for it), a block
# wholly below it is not masked, and a masked score is _NEG_INF before the
# exponential, so it contributes exactly 0.
_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b


def _ambient_precision(dtype):
    """bfloat16 operands multiply as they are. float32 operands multiply
    as `jnp.matmul` would multiply them where the kernel is traced: at
    `jax.default_matmul_precision`, which unset is the backend's own
    (float32 on the CPU, one bfloat16 pass on the TPU) and at `highest`
    is float32 everywhere."""
    if dtype != jnp.float32:
        return None
    ambient = jax.config.jax_default_matmul_precision
    return lax.Precision(ambient) if ambient else lax.Precision.DEFAULT


def _scale_in_q(D):
    """1/sqrt(D) is a power of two for D = 4^n: q * scale is then exact in
    any float dtype and replaces a multiply of every score."""
    return D & (D - 1) == 0 and (D.bit_length() - 1) % 2 == 0


def _causal_tiles(run, causal, iq, ik, bq, bk):
    """Call run(masked) for the (iq, ik) tile as the causal mask needs:
    not at all above the diagonal, unmasked wholly below it."""
    if not causal:
        run(False)
        return
    needed = ik * bk <= iq * bq + (bq - 1)
    below = ik * bk + (bk - 1) <= iq * bq
    pl.when(jnp.logical_and(needed, below))(lambda: run(False))
    pl.when(jnp.logical_and(needed, jnp.logical_not(below)))(
        lambda: run(True))


def _scores(q, k, scale, prec, masked, iq, ik, transposed):
    """(bq, bk) scaled scores of a tile, or their transpose (bk, bq)."""
    bq, bk, D = q.shape[0], k.shape[0], q.shape[1]
    if _scale_in_q(D):
        q = q * jnp.asarray(scale, q.dtype)
    a, b = (k, q) if transposed else (q, k)
    s = lax.dot_general(a, b, _NT, precision=prec,
                        preferred_element_type=jnp.float32)
    if not _scale_in_q(D):
        s = s * scale
    if masked:
        rows = iq * bq + lax.broadcasted_iota(jnp.int32, s.shape,
                                              1 if transposed else 0)
        cols = ik * bk + lax.broadcasted_iota(jnp.int32, s.shape,
                                              0 if transposed else 1)
        s = jnp.where(cols <= rows, s, _NEG_INF)
    return s


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                      l_ref, *, causal, scale):
    iq, ik, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    prec = _ambient_precision(q_ref.dtype)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def run(masked):
        s = _scores(q_ref[...], k_ref[...], scale, prec, masked, iq, ik,
                    False)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], _NN, precision=prec,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _causal_tiles(run, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _store():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)
        # the rows' statistics are a column here and a row of T in HBM
        lse = jnp.broadcast_to(m_ref[...] + jnp.log(l), (bq, 128))
        lse_ref[...] = lse.T[:1]


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, acc_ref, *, causal, scale):
    iq, ik, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    prec = _ambient_precision(q_ref.dtype)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def run(masked):
        k = k_ref[...]
        s = _scores(q_ref[...], k, scale, prec, masked, iq, ik, False)
        p = jnp.exp(s - lse_ref[0][:, None])
        dp = lax.dot_general(do_ref[...], v_ref[...], _NT, precision=prec,
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, None])
        acc_ref[...] += lax.dot_general(
            ds.astype(k.dtype), k, _NN, precision=prec,
            preferred_element_type=jnp.float32)

    _causal_tiles(run, causal, iq, ik, bq, bk)

    @pl.when(ik == nk - 1)
    def _store():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, causal, scale):
    """Works on the transposed tile (bk, bq): the rows' log-sum-exp and
    delta are then rows of the tile as they are rows of T in HBM, and all
    four products are a @ b or a @ b.T."""
    ik, iq, nq = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    prec = _ambient_precision(q_ref.dtype)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def run(masked):
        q, do = q_ref[...], do_ref[...]
        st = _scores(q, k_ref[...], scale, prec, masked, iq, ik, True)
        pt = jnp.exp(st - lse_ref[...])
        dv_acc[...] += lax.dot_general(
            pt.astype(do.dtype), do, _NN, precision=prec,
            preferred_element_type=jnp.float32)
        dpt = lax.dot_general(v_ref[...], do, _NT, precision=prec,
                              preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[...])
        dk_acc[...] += lax.dot_general(
            dst.astype(q.dtype), q, _NN, precision=prec,
            preferred_element_type=jnp.float32)

    _causal_tiles(run, causal, iq, ik, bq, bk)

    @pl.when(iq == nq - 1)
    def _store():
        dk_ref[...] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_blocks(T, block_q, block_k, widest_k=512):
    """(block_q, block_k) for T rows: the caller's, or the largest power
    of two from 128 to 512 (`widest_k` for block_k) that divides T, and
    all of T where none does. Measured at (8, 12, 1024, 64) bfloat16 on
    the v5e (tools/attention_probe.py, PERF.md): blocks of 128 cost four
    times those of 512, the step's 0.35 us more than its work; a forward
    block 1024 keys wide amortises the row statistics and is a sixth
    faster; the backward kernels are fastest at 512 x 512."""
    def pick(given, widest):
        if given is not None:
            return min(int(given), T)
        sizes = [b for b in (1024, 512, 256, 128) if b <= widest]
        return next((b for b in sizes if T % b == 0), T)
    bq, bk = pick(block_q, 512), pick(block_k, widest_k)
    if T % bq or T % bk:
        raise ValueError("flash_attention: %d rows do not divide into "
                         "blocks of %d and %d" % (T, bq, bk))
    return bq, bk


def _flash_call(kernel, name, grid, ins, outs, scratch, interpret):
    """One of the three kernels over (B*H, outer blocks, inner blocks).
    `ins` / `outs`: (array or ShapeDtypeStruct, block rows, index map of
    the row block), the arrays (B*H, T, D) or (B*H, 1, T); `scratch`: the
    shapes of the float32 accumulators."""
    def spec(x, rows, index):
        if x.shape[1] == 1:             # a row of T
            return pl.BlockSpec((None, 1, rows),
                                lambda b, i, j: (b, 0, index(i, j)))
        return pl.BlockSpec((None, rows, x.shape[2]),
                            lambda b, i, j: (b, index(i, j), 0))
    return pl.pallas_call(
        kernel, name=name, grid=grid,
        in_specs=[spec(*a) for a in ins],
        out_specs=[spec(*a) for a in outs],
        out_shape=[jax.ShapeDtypeStruct(a[0].shape, a[0].dtype)
                   for a in outs],
        scratch_shapes=[pltpu.VMEM(shape, jnp.float32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*[a[0] for a in ins])


def _kv_of_q(causal, bq, bk):
    """Index map of the kv block of step (q block i, j): above the
    diagonal, stay on the last block the q block needs."""
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)


def _outer(i, j):
    return i


def _rounded_once(interpret, *xs):
    """Compiled for the TPU at the default precision, every product would
    round its float32 operands to bfloat16 tile by tile: round them once
    here instead, and the kernels read half the bytes. Interpreted (off
    the chip) and at `highest` the operands go in as they are."""
    if xs[0].dtype == jnp.float32 and not interpret \
            and _ambient_precision(jnp.float32) == lax.Precision.DEFAULT:
        return tuple(x.astype(jnp.bfloat16) for x in xs)
    return xs


def _flash_fwd(q, k, v, causal, block_q, block_k, out_dtype, interpret):
    """o (B, H, T, D) and the rows' log-sum-exp (B*H, 1, T) float32."""
    B, H, T, D = q.shape
    bq, bk = _flash_blocks(T, block_q, block_k, widest_k=1024)
    q3, k3, v3 = (x.reshape(B * H, T, D) for x in (q, k, v))
    inner = _kv_of_q(causal, bq, bk)
    o, lse = _flash_call(
        functools.partial(_flash_fwd_kernel, causal=causal,
                          scale=1.0 / math.sqrt(D)),
        "flash_attention_fwd", (B * H, T // bq, T // bk),
        [(q3, bq, _outer), (k3, bk, inner), (v3, bk, inner)],
        [(jax.ShapeDtypeStruct(q3.shape, out_dtype), bq, _outer),
         (jax.ShapeDtypeStruct((B * H, 1, T), jnp.float32), bq, _outer)],
        [(bq, D), (bq, 1), (bq, 1)], interpret)
    return o.reshape(B, H, T, D), lse


def _flash_dq(q3, k3, v3, do3, lse, delta, causal, bq, bk, out_dtype,
              interpret):
    BH, T, D = q3.shape
    inner = _kv_of_q(causal, bq, bk)
    return _flash_call(
        functools.partial(_flash_dq_kernel, causal=causal,
                          scale=1.0 / math.sqrt(D)),
        "flash_attention_dq", (BH, T // bq, T // bk),
        [(q3, bq, _outer), (k3, bk, inner), (v3, bk, inner),
         (do3, bq, _outer), (lse, bq, _outer), (delta, bq, _outer)],
        [(jax.ShapeDtypeStruct(q3.shape, out_dtype), bq, _outer)],
        [(bq, D)], interpret)[0]


def _flash_dkv(q3, k3, v3, do3, lse, delta, causal, bq, bk, out_dtype,
               interpret):
    BH, T, D = q3.shape
    # above the diagonal, stay on the first q block the kv block needs
    inner = (lambda i, j: jnp.maximum(j, (i * bk) // bq)) \
        if causal else (lambda i, j: j)
    out = jax.ShapeDtypeStruct(k3.shape, out_dtype)
    return _flash_call(
        functools.partial(_flash_dkv_kernel, causal=causal,
                          scale=1.0 / math.sqrt(D)),
        "flash_attention_dkv", (BH, T // bk, T // bq),
        [(q3, bq, inner), (k3, bk, _outer), (v3, bk, _outer),
         (do3, bq, inner), (lse, bq, inner), (delta, bq, inner)],
        [(out, bk, _outer), (out, bk, _outer)], [(bk, D), (bk, D)],
        interpret)


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
def flash_attention(q, k, v, causal=False, block_q=None, block_k=None):
    """Fused attention, q/k/v: (B, H, T, D): the Pallas kernels above at
    whatever shape and blocks they are given (blocks from T where they
    are None; interpreted off the chip). The residuals of the backward
    are q, k, v, o and the rows' log-sum-exp: neither pass holds a T x T
    array, and none is kept between them."""
    return _fa_fwd(q, k, v, causal, block_q, block_k)[0]


# Both passes are jitted so that the layers of a model share one traced
# function each: a Pallas kernel is lowered where it is called, and GPT-2
# small's 36 calls cost the step's set-up 10 s in every process, cache hit
# or not. `interpret` is an argument because it keys jit's cache.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _fa_fwd_on(q, k, v, causal, block_q, block_k, interpret):
    operands = _rounded_once(interpret, q, k, v)
    o, lse = _flash_fwd(*operands, causal, block_q, block_k, q.dtype,
                        interpret)
    return o, (*operands, o, lse)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _fa_bwd_on(q, k, v, o, lse, g, causal, block_q, block_k, interpret):
    """dq, dk, dv in g's dtype from what the forward kept."""
    B, H, T, D = q.shape
    bq, bk = _flash_blocks(T, block_q, block_k)
    delta = jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32),
                    axis=-1).reshape(B * H, 1, T)
    args = [x.reshape(B * H, T, D)
            for x in (q, k, v, *_rounded_once(interpret, g))] \
        + [lse, delta, causal, bq, bk, g.dtype, interpret]
    dq = _flash_dq(*args)
    dk, dv = _flash_dkv(*args)
    return tuple(x.reshape(B, H, T, D) for x in (dq, dk, dv))


def _fa_fwd(q, k, v, causal, block_q, block_k):
    return _fa_fwd_on(q, k, v, causal, block_q, block_k, _interpret())


def _fa_bwd(causal, block_q, block_k, res, g):
    return _fa_bwd_on(*res, g, causal, block_q, block_k, _interpret())


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
FLASH_PATH = _obs.counter(
    "attention.flash.path",
    "Times _contrib_flash_attention was traced into a program, by what "
    "its shape chose (label path: kernel = the Pallas kernels, T a "
    "multiple of the block and D of 64; plain = batch_dot, softmax, "
    "batch_dot)")

def _attention_plain(q, k, v, causal):
    """batch_dot, softmax over the masked scores, batch_dot: the graph
    ops' own functions in the dtypes the graph gives them, so a model
    that called the three ops reads the same bits through this one."""
    from .nn import _softmax
    from .tensor import _batch_dot
    s = _batch_dot(q, k, transpose_b=True) * (1.0 / math.sqrt(q.shape[-1]))
    if causal:
        T = q.shape[-2]
        rows = lax.broadcasted_iota(jnp.int32, (T, T), 0)
        cols = lax.broadcasted_iota(jnp.int32, (T, T), 1)
        # gluon/model_zoo/gpt.py's additive mask, (allowed - 1) * 1e30
        s = s + jnp.where(cols <= rows, 0.0, _NEG_INF).astype(jnp.float32)
    return _batch_dot(_softmax(s, axis=-1), v)


def _axis_bound(name):
    try:
        lax.axis_size(name)
        return True
    except NameError:
        return False


@register("_contrib_flash_attention")
def _flash_attention_op(q, k, v, *, causal=False, block_q=None,
                        block_k=None):
    """Attention over (B, H, T, D). The shape chooses: the kernels where
    T is a multiple of the block (128 unless given) and D of 64, else
    the three graph ops' composition. XLA cannot split a custom call, so
    under a mesh (`parallel.use_mesh`) whose only axis wider than one is
    `dp` the kernels run per shard of the batch, and under any other
    mesh the plain path runs, which XLA partitions as it did."""
    from ..parallel.mesh import current_mesh, shard_map_compat
    B, _, T, D = q.shape
    tiles = D % 64 == 0 and T % (block_q or 128) == 0 \
        and T % (block_k or 128) == 0
    mesh = current_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1] if mesh else []
    # one device, or traced inside a shard_map over every wide axis: the
    # arrays here are one device's already
    shard = not all(_axis_bound(a) for a in wide)
    if shard:
        tiles = tiles and wide == ["dp"] and B % mesh.shape["dp"] == 0
    FLASH_PATH.inc(path="kernel" if tiles else "plain")
    if not tiles:
        return _attention_plain(q, k, v, causal)
    fn = lambda q, k, v: flash_attention(   # noqa: E731
        q, k, v, causal, block_q, block_k)
    if shard:
        spec = PartitionSpec("dp")
        fn = shard_map_compat(fn, mesh, (spec, spec, spec), spec)
    return fn(q, k, v)


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
