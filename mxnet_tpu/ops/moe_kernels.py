"""Pallas TPU kernels for the held-range expert layer (`ops/moe.py`).

The held assignments, sorted by expert as the plain loop sorts them, are
cut into tiles of R rows of one expert each, and every held expert has
at least one tile (an expert with no rows has one empty tile, so that its
weight gradients are written as zeros). A grid step takes one tile:

- its expert's matrices are blocks whose index comes from the
  scalar-prefetched `tile_expert`, so the consecutive tiles of one
  expert keep them in VMEM without a new DMA;
- its token rows are read into VMEM by one DMA a row, from the token
  indices in SMEM, and waited for once a set bit of their count; the
  next tile's rows are read while this tile's products run;
- what it adds to the tokens' rows (y in the forward, dx in the
  backward) is a read-modify-write of those rows in float32: a token has
  at most one row a tile, and a tile's reads wait for the previous
  tile's writes, so two tiles that share a token never lose a sum.

The grid's tile axis is the live tiles alone: its length is the
prefetched `n_tiles`, a value of the data. So the work follows the rows
that land on the held experts; the tables are sized by the most tiles a
layer can have, N min(k, E) / R plus one a held expert, and no array of
that bound's rows times H is made. They are built without a gather or a
scatter (`layout`).

- `held_fwd`: y += w * W_down (silu(W_gate x) * (W_up x)), the tile's
  rows at their routing weights w.
- `held_bwd`: the tile transposed: dx, each expert's three weight
  gradients summed in float32 in VMEM over its consecutive tiles and
  written once in the weights' dtype, and each row's gradient of w.

Precision is the plain loop's: bfloat16 operands, float32 sums; act,
dout, dgate and dup are rounded to x's dtype where the loop rounds them;
y and dx are summed in float32 and cast once. Rows move through HBM as
float32 in an (N, 1, H) layout: a DMA moves whole tiles of its array's
layout, and neither the (8, 128) tiling of a 2-D array nor the packed
one of a bfloat16 array has a tile of one row.

Where an expert's three blocks and, in the backward, their float32 sums
do not fit the VMEM a call takes (25/32 of the core's, as JAX describes
the chip), I is cut into chunks (an outer grid axis); each chunk then
reads the rows and adds to y or dx again. The row tile and the chunk
follow from H, I and the VMEM alone (`shape`). Off the chip the kernels
run interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["tiles", "shape", "layout", "held_fwd", "held_bwd"]

_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
_TN = (((0,), (0,)), ((), ()))          # a.T @ b
_F32 = jnp.float32
_ROWS = 128                              # rows a tile: the probe's best
_BLOCK = 256                             # rows a step of the layout copies


def _interpret():
    return jax.default_backend() != "tpu"


def _vmem_capacity():
    """Bytes of VMEM a core has: the chip's, as JAX describes its kind;
    the v5e's where the kernels are interpreted or compiled off the chip
    (for a described v5e); none on a TPU JAX does not describe, so that
    no pass fits and the plain loop keeps the layer."""
    if jax.default_backend() != "tpu":
        return 128 * 2 ** 20
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return 0


def tiles(H, I, dtype):
    """Whether the kernels take this layer: bfloat16, widths of whole
    128-lane tiles, and a chunk of I whose backward fits the VMEM."""
    return (jnp.dtype(dtype) == jnp.bfloat16 and H % 128 == 0
            and I % 128 == 0 and shape(H, I, True) is not None)


def _vmem(H, R, Ib, backward):
    """Bytes of VMEM a grid step holds, roughly: the three blocks twice
    (the next expert's are read ahead), in the backward their gradients
    twice and their float32 sums, the row buffers and the products."""
    blocks = 3 * Ib * H * 2
    rows, cols = R * H * 4, R * Ib * 4
    if backward:
        return 6 * blocks + 10 * rows + 8 * cols
    return 2 * blocks + 6 * rows + 4 * cols


def shape(H, I, backward):
    """(rows a tile R, the chunk of I) of a pass over a layer of widths H
    and I: a tile of 128 rows, and the widest chunk of whole 128-lane
    tiles whose pass fits 25/32 of the core's VMEM (100 MiB of the
    v5e's 128); None where no chunk fits."""
    R, budget = _ROWS, _vmem_capacity() * 25 // 32
    for n in range(1, I // 128 + 1):
        Ib = I // n
        if I % n == 0 and Ib % 128 == 0 and \
                _vmem(H, R, Ib, backward) <= budget:
            return R, Ib
    return None


@jax.custom_vjp
def _by_expert(key, w):
    """(the places 0..n-1 sorted by key, stably; w in that order): one
    sort carries the weights, and their gradient goes back by a sort on
    the order, where a gather's would be a scatter of n scalars."""
    _, order, w = lax.sort((key, lax.iota(jnp.int32, key.shape[0]), w),
                           num_keys=1, is_stable=True)
    return order, w


def _by_expert_fwd(key, w):
    order, w = _by_expert(key, w)
    return (order, w), order


def _by_expert_bwd(order, cts):
    return None, lax.sort((order, cts[1]), num_keys=1)[1]


_by_expert.defvjp(_by_expert_fwd, _by_expert_bwd)


def layout(key, counts, top_w, R):
    """The tiles of the held assignments: `key` (N k,) each flat
    assignment's (token * k + choice) held expert, E where it is not
    held; `counts` (E,) the held experts' rows; top_w (N, k). Returns the
    scalar tables (tile_expert, live rows, n_tiles, token of each row
    slot (T * R,)) and the routing weight of each slot (T, 1, R), 0 where
    none lives (JAX carries its cotangent back to top_w). T, the tiles at
    the worst, is static.

    No table is gathered: the slots of expert e hold a contiguous run of
    the sorted assignments, at a shift of its own, so each table is E
    shifted copies of a sorted array, each kept where its expert's slots
    are. A gather of T R scalars, and its gradient's scatter, cost more
    than the kernels at a layer of few rows an expert (Qwen3-Next's)."""
    N, k = top_w.shape
    E = counts.shape[0]
    T = -(-(N * min(k, E)) // R) + E
    S = T * R
    counts = counts.astype(jnp.int32)
    first = jnp.cumsum(counts) - counts
    span = jnp.maximum(-(-counts // R), 1)        # an empty expert: one tile
    tile_end = jnp.cumsum(span)
    t = jnp.arange(T, dtype=jnp.int32)
    # past the live tiles, the last expert's: its blocks stay in VMEM
    expert = jnp.minimum(jnp.sum(tile_end[None, :] <= t[:, None], axis=1),
                         E - 1).astype(jnp.int32)
    mine = expert[:, None] == jnp.arange(E)                       # (T, E)
    done = (t - jnp.sum(jnp.where(mine, tile_end - span, 0), axis=1)) * R
    live = jnp.clip(jnp.sum(jnp.where(mine, counts, 0), axis=1) - done,
                    0, R).astype(jnp.int32)
    order, w_sorted = _by_expert(key, top_w.reshape(N * k))
    # slot s of expert e holds the sorted place s + first[e] - (its first
    # tile) * R, which is at most s: in the arrays padded by S, s + shift[e]
    shift = S + first - (tile_end - span) * R
    tokens = jnp.pad(order // k, (S, S))
    weights = jnp.pad(w_sorted, (S, S))
    e_slot = jnp.repeat(expert, R)
    token, w = jnp.zeros(S, jnp.int32), jnp.zeros(S, w_sorted.dtype)
    for e in range(E):
        here = e_slot == e
        token = jnp.where(here, lax.dynamic_slice(tokens, (shift[e],), (S,)),
                          token)
        w = jnp.where(here, lax.dynamic_slice(weights, (shift[e],), (S,)), w)
    alive = (jnp.arange(R, dtype=jnp.int32) < live[:, None]).reshape(S)
    return ((expert, live, tile_end[-1:].astype(jnp.int32),
             jnp.where(alive, token, 0)),
            jnp.where(alive, w, 0.0).reshape(T, 1, R).astype(_F32))


def _dot(a, b, dims, prec=None):
    return lax.dot_general(a, b, dims, precision=prec,
                           preferred_element_type=_F32)


def _copies(src, dst, token_ref, base, n, sem, to_rows):
    """Start n row DMAs: token rows of src into rows 0..n-1 of dst, or
    (to_rows) the other way."""
    def body(r, c):
        row = token_ref[base + r]
        if to_rows:
            pltpu.make_async_copy(src.at[r], dst.at[row], sem).start()
        else:
            pltpu.make_async_copy(src.at[row], dst.at[r], sem).start()
        return c
    lax.fori_loop(0, n, body, 0)


def _wait(buf, n, sem):
    """Wait for n row DMAs on sem, each the size of one of buf's rows:
    one wait a set bit of n, on a copy of that many rows (a DMA
    semaphore counts bytes), not one a row."""
    for b in range(buf.shape[0].bit_length()):
        m = 1 << b

        @pl.when((n & m) != 0)
        def _bit(m=m):
            rows = buf.at[pl.ds(0, m)]
            pltpu.make_async_copy(rows, rows, sem).wait()


class _Rows:
    """The row traffic of one grid step: `reads` are row arrays in HBM
    gathered into double-buffered (2, R, 1, H) scratch, the next tile's
    ahead; `acc` is the (N, 1, H) float32 array the tile adds to, read
    into and written from an (R, 1, H) scratch."""

    def __init__(self, t, n, live_ref, token_ref, R, reads, bufs, acc, abuf,
                 sems):
        self.t, self.n, self.R = t, n, R
        self.live_ref, self.token_ref = live_ref, token_ref
        self.reads, self.bufs, self.acc, self.abuf = reads, bufs, acc, abuf
        self.sems = sems                  # (2 * len(reads) + 2,) DMA
        self.slot = t % 2

    def _gather(self, t, slot):
        for i, (src, buf) in enumerate(zip(self.reads, self.bufs)):
            _copies(src, buf.at[slot], self.token_ref, t * self.R,
                    self.live_ref[t], self.sems.at[2 * i + slot], False)

    def read(self):
        """This tile's rows, as (R, H) float32 values each (rows past
        the live ones are stale), with the next tile's on their way and
        this tile's rows of `acc` on theirs."""
        t, slot = self.t, self.slot
        live = self.live_ref[t]

        @pl.when(t == 0)
        def _first():
            self._gather(t, slot)

        for i, buf in enumerate(self.bufs):
            _wait(buf.at[slot], live, self.sems.at[2 * i + slot])

        @pl.when(t + 1 < self.n)
        def _ahead():
            self._gather(t + 1, 1 - slot)

        done, back = self.sems.at[-2], self.sems.at[-1]

        @pl.when(t > 0)
        def _previous_writes():
            _wait(self.abuf, self.live_ref[t - 1], back)

        _copies(self.acc, self.abuf, self.token_ref, t * self.R, live, done,
                False)
        H = self.abuf.shape[-1]
        return [buf[slot].reshape(self.R, H) for buf in self.bufs]

    def add(self, rows):
        """acc's rows += rows (R, H) float32, written back; the last live
        tile waits for its writes."""
        t, live = self.t, self.live_ref[self.t]
        _wait(self.abuf, live, self.sems.at[-2])
        self.abuf[...] = self.abuf[...] + rows.reshape(self.abuf.shape)
        _copies(self.abuf, self.acc, self.token_ref, t * self.R, live,
                self.sems.at[-1], True)

        @pl.when(t + 1 == self.n)
        def _drain():
            _wait(self.abuf, live, self.sems.at[-1])


def _column(row):
    """(1, R) -> (R, 1) without a transpose."""
    R = row.shape[1]
    eye = (lax.broadcasted_iota(jnp.int32, (R, R), 0)
           == lax.broadcasted_iota(jnp.int32, (R, R), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _forward(xt, wg, wu, wd):
    """One tile through one expert's chunk: (gate, up, act in xt's
    dtype, out), float32 but act."""
    gate, up = _dot(xt, wg, _NT), _dot(xt, wu, _NT)
    act = (jax.nn.silu(gate) * up).astype(xt.dtype)
    return gate, up, act, _dot(act, wd, _NT)


def _fwd_kernel(expert_ref, live_ref, n_ref, token_ref, w_ref, x_hbm, wg_ref,
                wu_ref, wd_ref, _, y_hbm, xbuf, ybuf, sems, *, R):
    t, n = pl.program_id(1), n_ref[0]
    cd = wg_ref.dtype
    rows = _Rows(t, n, live_ref, token_ref, R, [x_hbm], [xbuf], y_hbm, ybuf,
                 sems)
    x, = rows.read()
    out = _forward(x.astype(cd), wg_ref[...], wu_ref[...], wd_ref[...])[3]
    rows.add(_column(w_ref[...]) * out)


def _bwd_kernel(expert_ref, live_ref, n_ref, token_ref, w_ref, x_hbm, dy_hbm,
                wg_ref, wu_ref, wd_ref, _, dx_hbm, dwg_ref, dwu_ref, dwd_ref,
                dw_ref, xbuf, dybuf, dxbuf, g_acc, u_acc, d_acc, sems, *, R):
    t, n = pl.program_id(1), n_ref[0]
    cd = wg_ref.dtype
    e = expert_ref[t]

    @pl.when(jnp.logical_or(t == 0, expert_ref[jnp.maximum(t - 1, 0)] != e))
    def _first_of_its_expert():
        for acc in (g_acc, u_acc, d_acc):
            acc[...] = jnp.zeros_like(acc)

    rows = _Rows(t, n, live_ref, token_ref, R, [x_hbm, dy_hbm],
                 [xbuf, dybuf], dx_hbm, dxbuf, sems)
    x, dy = rows.read()
    # rows past the live ones hold stale values: zero, so that the sums
    # over the tile's rows take nothing from them
    live = lax.broadcasted_iota(jnp.int32, x.shape, 0) < live_ref[t]
    xt = jnp.where(live, x, 0.0).astype(cd)
    dy = jnp.where(live, dy, 0.0)
    wg, wu, wd = wg_ref[...], wu_ref[...], wd_ref[...]
    gate, up, act, out = _forward(xt, wg, wu, wd)
    # the gradient of each row's weight, summed over H as a (1, R) row
    ones = jnp.ones((8, dy.shape[1]), _F32)
    dw_ref[...] = _dot(ones, dy * out, _NT, lax.Precision.HIGHEST)[:1]
    dout = (dy * _column(w_ref[...])).astype(cd)
    dact = _dot(dout, wd, _NN)
    sig = jax.nn.sigmoid(gate)
    dgate = (dact * up * sig * (1.0 + gate * (1.0 - sig))).astype(cd)
    dup = (dact * gate * sig).astype(cd)
    g_acc[...] += _dot(dgate, xt, _TN)
    u_acc[...] += _dot(dup, xt, _TN)
    d_acc[...] += _dot(dout, act, _TN)
    rows.add(_dot(dgate, wg, _NN) + _dot(dup, wu, _NN))

    @pl.when(jnp.logical_or(t + 1 == n,
                            expert_ref[jnp.minimum(t + 1, n - 1)] != e))
    def _last_of_its_expert():
        dwg_ref[...] = g_acc[...].astype(dwg_ref.dtype)
        dwu_ref[...] = u_acc[...].astype(dwu_ref.dtype)
        dwd_ref[...] = d_acc[...].astype(dwd_ref.dtype)


def _specs(Ib, H, R):
    """Block specs over the grid (chunk j, live tile t) with the four
    scalar tables after: the chunk of the tile's expert's three
    matrices, and the tile's row of weights or of their gradients."""
    gu = pl.BlockSpec((None, Ib, H), lambda j, t, e, *_: (e[t], j, 0))
    d = pl.BlockSpec((None, H, Ib), lambda j, t, e, *_: (e[t], 0, j))
    w = pl.BlockSpec((None, 1, R), lambda j, t, *_: (t, 0, 0))
    row = pl.BlockSpec((None, None, 1, R), lambda j, t, *_: (j, t, 0, 0))
    return gu, d, w, row


def _params(interpret):
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_capacity() * 29 // 32),
        interpret=interpret)


def _relayout_kernel(a_ref, o_ref):
    o_ref[...] = a_ref[...].astype(o_ref.dtype).reshape(o_ref.shape)


def _relayout(a, shape, dtype, interpret):
    """a (N, H) -> (N, 1, H) or back, in `dtype`, by blocks of rows in
    VMEM: XLA's copy between the two tilings of HBM costs ~2 ms a call
    at N 8192, H 2048 (measured on one v5e chip), this a pass over the bytes."""
    N, H = a.shape[0], a.shape[-1]

    def spec(rank):
        return pl.BlockSpec((_BLOCK,) + (1,) * (rank - 2) + (H,),
                            lambda i: (i,) + (0,) * (rank - 1))
    return pl.pallas_call(
        _relayout_kernel, name="moe_held_rows",
        grid=(pl.cdiv(N, _BLOCK),), in_specs=[spec(a.ndim)],
        out_specs=spec(len(shape)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret)(a)


def _as_rows(a, interpret):
    """(N, H) -> (N, 1, H) float32: a row is a whole tile of its layout."""
    return _relayout(a, (a.shape[0], 1, a.shape[1]), _F32, interpret)


# Both passes are jitted so that a model's layers share one traced
# function each (a Pallas kernel is lowered where it is called);
# `interpret` is an argument because it keys jit's cache.
@functools.partial(jax.jit, static_argnums=(6, 7))
def held_fwd(x, wg, wu, wd, scalars, w, R, interpret):
    """y (N, H) in x's dtype: every held row through its expert, times
    its weight, summed by token in float32. scalars and w as `layout`
    makes them."""
    (N, H), I = x.shape, wg.shape[1]
    _, Ib = shape(H, I, False)
    gu, d, wspec, _ = _specs(Ib, H, R)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, R=R), name="moe_held_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(I // Ib, scalars[2][0]),
            in_specs=[wspec, anywhere, gu, gu, d, anywhere],
            out_specs=anywhere,
            scratch_shapes=[pltpu.VMEM((2, R, 1, H), _F32),
                            pltpu.VMEM((R, 1, H), _F32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=jax.ShapeDtypeStruct((N, 1, H), _F32),
        input_output_aliases={9: 0}, **_params(interpret))(
            *scalars, w, _as_rows(x, interpret), wg, wu, wd,
            jnp.zeros((N, 1, H), _F32))
    return _relayout(y, (N, H), x.dtype, interpret)


@functools.partial(jax.jit, static_argnums=(7, 8))
def held_bwd(x, wg, wu, wd, scalars, w, dy, R, interpret):
    """(dx (N, H) in x's dtype, dwg, dwu, dwd in the weights' dtype, the
    gradient of each row slot's weight (T, R) float32)."""
    (N, H), I = x.shape, wg.shape[1]
    _, Ib = shape(H, I, True)
    J, T = I // Ib, w.shape[0]
    gu, d, wspec, row = _specs(Ib, H, R)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    dx, dwg, dwu, dwd, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, R=R), name="moe_held_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(J, scalars[2][0]),
            in_specs=[wspec, anywhere, anywhere, gu, gu, d, anywhere],
            out_specs=[anywhere, gu, gu, d, row],
            scratch_shapes=[pltpu.VMEM((2, R, 1, H), _F32),
                            pltpu.VMEM((2, R, 1, H), _F32),
                            pltpu.VMEM((R, 1, H), _F32),
                            pltpu.VMEM((Ib, H), _F32),
                            pltpu.VMEM((Ib, H), _F32),
                            pltpu.VMEM((H, Ib), _F32),
                            pltpu.SemaphoreType.DMA((6,))]),
        out_shape=[jax.ShapeDtypeStruct((N, 1, H), _F32),
                   jax.ShapeDtypeStruct(wg.shape, wg.dtype),
                   jax.ShapeDtypeStruct(wu.shape, wu.dtype),
                   jax.ShapeDtypeStruct(wd.shape, wd.dtype),
                   jax.ShapeDtypeStruct((J, T, 1, R), _F32)],
        input_output_aliases={10: 0}, **_params(interpret))(
            *scalars, w, _as_rows(x, interpret),
            _as_rows(dy.astype(x.dtype), interpret), wg, wu,
            wd, jnp.zeros((N, 1, H), _F32))
    return (_relayout(dx, (N, H), x.dtype, interpret), dwg, dwu, dwd,
            jnp.sum(dw, axis=0).reshape(T, R))
