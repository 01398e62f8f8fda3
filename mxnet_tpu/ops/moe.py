"""A dropless expert layer that is told which experts it holds.

`moe_held_ffn` routes every token over ALL the experts of the layer in
float32 and computes, for the experts `held_start .. held_start + E - 1`
whose weights it is given, their part of the result. The router's rule
is an argument (`route_top_k`): the scores are a softmax over the
experts or a sigmoid of each; the k experts are chosen by the score, or
by the score plus a bias that plays no part in the weights; the k
weights are renormalised to sum to one (over their sum plus `eps`, where
that is given) and multiplied by `scale`:

    y[n] = sum over the token's chosen experts e that are held of
           w[n, e] * W_down[e] (silu(W_gate[e] x[n]) * (W_up[e] x[n]))

What the absent experts would add is left out; nothing stands in for the
chips that hold them. No token is dropped: the assignments that land on
the held range are sorted by expert and cut into tiles of one expert
each, and two paths compute them, chosen from what the op sees when it
is traced (`_kernels_take`; counted in `moe.held.path`):

- the Pallas kernels of `ops/moe_kernels.py`, on a TPU where x and the
  matrices are bfloat16 and H and I whole 128-lane tiles with a chunk
  of I that fits the core's VMEM: one grouped pass over the held rows a
  direction, an expert's matrices read once for its consecutive tiles
  and its weight gradients summed in VMEM;
- everywhere else the plain loop: the tiles are `tile` rows, and a loop
  over the tiles that hold a row (its trip count comes from the data,
  every shape is static) gathers a tile's rows, multiplies them by that
  one expert's matrices and adds the weighted result back to the
  tokens' rows; the backward pass is the same loop with the tile's
  products transposed.

Both take their operands in x's dtype and sum in float32, with the same
rounding points. `parallel/moe.py` is the other
kind of layer: a fixed capacity, tokens over it dropped, a dispatch
tensor, experts exchanged over a mesh axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..observability import registry as _obs
from . import moe_kernels
from .linear_attention import _precision
from .registry import register

__all__ = ["route_top_k", "moe_held_ffn", "shared_expert_ffn"]


def _kept(x):
    """Named for a rematerialised group to keep (graph.REMAT_KEEP): the
    router's choices are small, and computing them again would sort
    every token's scores a second time."""
    return checkpoint_name(x, "mx.keep")


def _mm(a, b, contract, prec):
    """a . b over one axis of each, float32 sums."""
    return lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                           precision=prec,
                           preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _top_k(probs, k):
    """(the k largest of each row, their columns). Both are named for a
    rematerialised group to keep, so a second pass never sorts again,
    and the cotangent goes back through a mask of the columns (compares
    and sums; a gather of N k scalars took 3 ms on the chip)."""
    return _top_k_fwd(probs, k)[0]


def _top_k_fwd(probs, k):
    top_w, top_i = lax.top_k(probs, k)
    top_w, top_i = _kept(top_w), _kept(top_i.astype(jnp.int32))
    # the second residual is there for its shape: the row's width
    return (top_w, top_i), (top_i, jnp.zeros((0, probs.shape[-1]), jnp.int8))


def _top_k_bwd(k, res, cts):
    top_i, width = res
    chosen = top_i[..., None] == jnp.arange(width.shape[-1])     # (N, k, E)
    return (jnp.sum(jnp.where(chosen, cts[0][..., None], 0.0), axis=-2),)


_top_k.defvjp(_top_k_fwd, _top_k_bwd)


_SCORES = {"softmax": functools.partial(jax.nn.softmax, axis=-1),
           "sigmoid": jax.nn.sigmoid}


def route_top_k(x, router_w, top_k, score="softmax", bias=None, scale=1.0,
                eps=0.0):
    """(indices (N, k) int32, weights (N, k) float32 summing to `scale`,
    and each expert's count of assignments (E_all,) float32). The scores
    s are `score` of the logits, a softmax over the experts or a sigmoid
    of each; the k experts are those with the largest s, or with the
    largest s + bias where a `bias` (E_all,) is given, and a weight is
    the chosen expert's s (without the bias) over the sum of the k (plus
    `eps` where it is not 0: LFM2's 1e-6), times `scale`. The logits, the
    scores and the weights are float32 whatever x's dtype."""
    if score not in _SCORES:
        raise ValueError("route_top_k: unknown score %r" % score)
    # operands as they are stored (bfloat16 values multiply exactly into
    # the float32 sum; float32 ones at full precision)
    logits = _mm(x, router_w.astype(x.dtype), (1, 1), _precision(x.dtype))
    s = _SCORES[score](logits)
    if bias is None:
        top_w, top_i = _top_k(s, int(top_k))
    else:
        # chosen with the bias, weighed without: the chosen columns' s by
        # a mask (compares and sums, as the backward of `_top_k`)
        _, top_i = _top_k(lax.stop_gradient(s + bias.astype(s.dtype)),
                          int(top_k))
        chosen = top_i[..., None] == jnp.arange(s.shape[-1])     # (N, k, E)
        top_w = jnp.sum(jnp.where(chosen, s[..., None, :], 0.0), axis=-1)
    counts = jnp.sum(top_i.reshape(-1, 1) == jnp.arange(router_w.shape[0]),
                     axis=0).astype(jnp.float32)
    total = jnp.sum(top_w, axis=-1, keepdims=True)
    top_w = top_w / (total if eps == 0.0 else total + eps)
    return top_i, (top_w if scale == 1.0 else top_w * scale), counts


def _held_key(top_i, held_start, n_held):
    """Each flat assignment's (token * k + choice) held expert, n_held
    where it is not held."""
    N, k = top_i.shape
    local = top_i.reshape(N * k) - held_start
    return jnp.where((local >= 0) & (local < n_held), local, n_held)


def _tiles(top_i, counts_held, held_start, n_held, tile):
    """Lay the held assignments out in tiles of one expert each. Returns
    (order, first, tile_expert, tile_first, counts, n_tiles): the flat
    assignments (token * k + choice) sorted by held expert, those that
    are not held last; the place in `order` of each expert's first; each
    tile's expert and each expert's first tile; the held counts; the
    number of tiles that hold a row. Tile t of expert e reads the places
    first[e] + (t - tile_first[e]) * tile + j, j < tile, as far as the
    expert's count goes: a contiguous slice of `order`."""
    N, k = top_i.shape
    E = n_held
    # padded by a tile: the last tile's slice may run past the end
    order = jnp.pad(jnp.argsort(_held_key(top_i, held_start, E),
                                stable=True).astype(jnp.int32), (0, tile))
    counts = counts_held.astype(jnp.int32)
    first = jnp.cumsum(counts) - counts
    tiles_e = -(-counts // tile)
    tile_end = jnp.cumsum(tiles_e)
    tile_first = tile_end - tiles_e
    most = -(-(N * min(k, E)) // tile) + E            # tiles at the worst
    tile_expert = jnp.minimum(jnp.searchsorted(
        tile_end, jnp.arange(most, dtype=jnp.int32), side="right"),
        E - 1).astype(jnp.int32)
    return order, first, tile_expert, tile_first, counts, tile_end[-1]


def _tile_rows(t, lay, k, n_tokens, tile):
    """(expert, assignments (tile,), token rows (tile,), live (tile,)) of
    tile t; a slot past the expert's count reads token `n_tokens`, which
    no array has: gathers fill it with 0 and scatters drop it."""
    order, first, tile_expert, tile_first, counts = lay
    e = tile_expert[t]
    done = (t - tile_first[e]) * tile
    a = lax.dynamic_slice(order, (first[e] + done,), (tile,))
    live = jnp.arange(tile, dtype=jnp.int32) < counts[e] - done
    return e, a, jnp.where(live, a // k, n_tokens), live


def _tile_forward(xt, wg, wu, wd, prec):
    """One tile through one expert: (gate, up, act, out), float32."""
    gate = _mm(xt, wg, (1, 1), prec)
    up = _mm(xt, wu, (1, 1), prec)
    act = jax.nn.silu(gate) * up
    return gate, up, act, _mm(act.astype(xt.dtype), wd, (1, 1), prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _held_products(x, wg, wu, wd, top_w, lay, n_tiles, tile):
    """y (N, H): every held assignment's token through its expert, times
    the assignment's weight, added up by token."""
    return _held_fwd(x, wg, wu, wd, top_w, lay, n_tiles, tile)[0]


def _held_fwd(x, wg, wu, wd, top_w, lay, n_tiles, tile):
    N, H = x.shape
    k = top_w.shape[1]
    prec = _precision(x.dtype)
    flat_w = top_w.reshape(N * k)

    def body(t, y):
        e, a, r, live = _tile_rows(t, lay, k, N, tile)
        w = jnp.where(live, flat_w[a], 0.0)
        xt = x.at[r].get(mode="fill", fill_value=0)
        out = _tile_forward(xt, wg[e], wu[e], wd[e], prec)[3]
        return y.at[r].add(out * w[:, None], mode="drop")

    y = lax.fori_loop(0, n_tiles, body, jnp.zeros((N, H), jnp.float32))
    return y.astype(x.dtype), (x, wg, wu, wd, top_w, lay, n_tiles)


def _held_bwd(tile, res, dy):
    x, wg, wu, wd, top_w, lay, n_tiles = res
    N, k = top_w.shape
    prec = _precision(x.dtype)
    cd = x.dtype
    dy = dy.astype(cd)
    flat_w = top_w.reshape(N * k)

    def body(t, carry):
        dx, dwg, dwu, dwd, dw = carry
        e, a, r, live = _tile_rows(t, lay, k, N, tile)
        w = jnp.where(live, flat_w[a], 0.0)
        xt = x.at[r].get(mode="fill", fill_value=0)
        dyt = dy.at[r].get(mode="fill", fill_value=0)
        gate, up, act, out = _tile_forward(xt, wg[e], wu[e], wd[e], prec)
        dw = dw.at[jnp.where(live, a, N * k)].add(
            jnp.sum(dyt.astype(jnp.float32) * out, axis=-1), mode="drop")
        dout = (dyt.astype(jnp.float32) * w[:, None]).astype(cd)
        dact = _mm(dout, wd[e], (1, 0), prec)                 # (tile, I)
        sig = jax.nn.sigmoid(gate)
        dgate = (dact * up * sig * (1.0 + gate * (1.0 - sig))).astype(cd)
        dup = (dact * gate * sig).astype(cd)
        dxt = _mm(dgate, wg[e], (1, 0), prec) + _mm(dup, wu[e], (1, 0), prec)
        dx = dx.at[r].add(dxt, mode="drop")
        dwg = dwg.at[e].add(_mm(dgate, xt, (0, 0), prec))
        dwu = dwu.at[e].add(_mm(dup, xt, (0, 0), prec))
        dwd = dwd.at[e].add(_mm(dout, act.astype(cd), (0, 0), prec))
        return dx, dwg, dwu, dwd, dw

    f32 = jnp.float32
    dx, dwg, dwu, dwd, dw = lax.fori_loop(0, n_tiles, body, (
        jnp.zeros(x.shape, f32), jnp.zeros(wg.shape, f32),
        jnp.zeros(wu.shape, f32), jnp.zeros(wd.shape, f32),
        jnp.zeros((N * k,), f32)))
    return (dx.astype(x.dtype), dwg.astype(wg.dtype), dwu.astype(wu.dtype),
            dwd.astype(wd.dtype), dw.reshape(N, k).astype(top_w.dtype),
            None, None)


_held_products.defvjp(_held_fwd, _held_bwd)


HELD_PATH = _obs.counter(
    "moe.held.path",
    "Times _contrib_moe_held_ffn was traced into a program, a layer each, "
    "by what its shape chose (label path: kernel = the Pallas kernels of "
    "ops/moe_kernels.py, on a TPU with bfloat16 x and matrices, H and I "
    "multiples of 128, a chunk of I that fits the core's VMEM and no mesh "
    "axis the call is not already inside; "
    "plain = the loop over tiles of `tile` rows)")


def _kernels_take(x, w_gate, w_up, w_down):
    """Whether the kernels take this call: on a TPU (where the kernels
    are not interpreted), x and the three matrices bfloat16 with H and I
    whole 128-lane tiles whose backward fits the VMEM, and the arrays
    one device's (no mesh, or traced inside a shard_map over every mesh
    axis wider than one). A sharded batch keeps the plain loop, which
    XLA partitions: the layout of the held rows spans the batch."""
    from ..parallel.mesh import current_mesh
    from .pallas_kernels import _axis_bound
    I, H = w_gate.shape[1:]
    if moe_kernels._interpret() or not (
            moe_kernels.tiles(H, I, x.dtype)
            and all(a.dtype == x.dtype for a in (w_gate, w_up, w_down))):
        return False
    mesh = current_mesh()
    wide = [a for a in mesh.axis_names if mesh.shape[a] > 1] if mesh else []
    return all(_axis_bound(a) for a in wide)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held_kernels(x, wg, wu, wd, scalars, w, R):
    """`_held_products` through the kernels: w is each row slot's routing
    weight (`moe_kernels.layout`), whose cotangent JAX carries back to
    top_w through the layout."""
    return moe_kernels.held_fwd(x, wg, wu, wd, scalars, w, R,
                                moe_kernels._interpret())


def _kernels_fwd(x, wg, wu, wd, scalars, w, R):
    return (_held_kernels(x, wg, wu, wd, scalars, w, R),
            (x, wg, wu, wd, scalars, w))


def _kernels_bwd(R, res, dy):
    x, wg, wu, wd, scalars, w = res
    dx, dwg, dwu, dwd, dw = moe_kernels.held_bwd(
        x, wg, wu, wd, scalars, w, dy, R, moe_kernels._interpret())
    return dx, dwg, dwu, dwd, None, dw.reshape(w.shape)


_held_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _held(x, w_gate, w_up, w_down, top_i, top_w, counts_held, held_start,
          tile, kernels):
    """y (N, H) of the routed assignments top_i, top_w (N, k) on the held
    experts, counts_held (E,) theirs: through the kernels, or the loop
    over tiles of `tile` rows."""
    E = w_gate.shape[0]
    if kernels:
        R = moe_kernels.shape(x.shape[1], w_gate.shape[1], True)[0]
        scalars, w = moe_kernels.layout(_held_key(top_i, held_start, E),
                                        counts_held, top_w, R)
        return _held_kernels(x, w_gate, w_up, w_down, scalars, w, R)
    *lay, n_tiles = _tiles(top_i, counts_held, held_start, E, tile)
    return _held_products(x, w_gate, w_up, w_down, top_w, tuple(lay),
                          n_tiles, tile)


def moe_held_ffn(x, router_w, w_gate, w_up, w_down, top_k, held_start=0,
                 tile=256, score="softmax", bias=None, scale=1.0, eps=0.0):
    """x: (N, H); router_w: (E_all, H); w_gate, w_up: (E, I, H) and
    w_down: (E, H, I), the held experts'; `score`, `bias` (E_all,),
    `scale` and `eps` are the router's rule (`route_top_k`). Returns
    (y (N, H) in x's dtype, rows that landed on held experts, max over
    mean of all experts' counts)."""
    E = w_gate.shape[0]
    tile = int(tile)
    top_i, top_w, counts = route_top_k(x, router_w, int(top_k), score, bias,
                                       float(scale), float(eps))
    counts_held = lax.dynamic_slice(counts, (int(held_start),), (E,))
    kernels = _kernels_take(x, w_gate, w_up, w_down)
    HELD_PATH.inc(path="kernel" if kernels else "plain")
    y = _held(x, w_gate, w_up, w_down, top_i, top_w, counts_held,
              int(held_start), tile, kernels)
    return y, jnp.sum(counts_held), jnp.max(counts) / jnp.mean(counts)


def shared_expert_ffn(x, w_gate, w_up, w_down, w_sgate=None):
    """sigmoid(x . w_sgate) * W_down (silu(W_gate x) * (W_up x)), or
    without `w_sgate` the expert alone; the matrices are (out, in);
    float32 sums, x's dtype between products."""
    prec = _precision(x.dtype)
    gate = _mm(x, w_gate, (x.ndim - 1, 1), prec)
    up = _mm(x, w_up, (x.ndim - 1, 1), prec)
    act = (jax.nn.silu(gate) * up).astype(x.dtype)
    out = _mm(act, w_down, (x.ndim - 1, 1), prec)
    if w_sgate is None:
        return out.astype(x.dtype)
    sg = jax.nn.sigmoid(_mm(x, w_sgate, (x.ndim - 1, 1), prec))
    return (sg * out).astype(x.dtype)


@register("_contrib_moe_held_ffn", num_outputs=2, visible_outputs=1,
          aux_write={1: 5},
          counters={5: ("moe.assignments.held", "moe.load.max_over_mean")})
def _moe_held_ffn_op(x, router_weight, gate_weight, up_weight, down_weight,
                     stats, *router_bias, top_k, held_start=0, tile=256,
                     score="softmax", scale=1.0, with_bias=False, eps=0.0):
    """The routed experts' part of a sparse layer, for the experts held
    here (see the module). x: (..., H). `stats` (2,) is a device counter:
    the held rows of the last step and its load's max over mean. `score`,
    `scale` and `eps` are the router's rule; `with_bias=True` adds the
    input `router_bias` (E_all,), which is added to the scores for the
    choice of experts alone."""
    lead = x.shape[:-1]
    y, held, load = moe_held_ffn(x.reshape(-1, x.shape[-1]), router_weight,
                                 gate_weight, up_weight, down_weight, top_k,
                                 held_start, tile, score,
                                 router_bias[0] if with_bias else None, scale,
                                 eps)
    return (y.reshape(lead + (x.shape[-1],)),
            jnp.stack([held, load]).astype(stats.dtype))


@register("_contrib_shared_expert_ffn")
def _shared_expert_ffn_op(x, gate_weight, up_weight, down_weight,
                          *expert_gate_weight, gated=True):
    """`gated=False`: no `expert_gate_weight` input, the expert alone."""
    return shared_expert_ffn(x, gate_weight, up_weight, down_weight,
                             expert_gate_weight[0] if gated else None)
