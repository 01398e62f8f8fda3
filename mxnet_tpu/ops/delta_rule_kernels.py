"""Pallas TPU kernels for the chunked gated delta rule (Gated DeltaNet).

`ops/linear_attention.py` states the recurrence and its chunked form.
Here a chunk's whole work happens on tiles in VMEM, and the matrix state
of a head stays in VMEM scratch across the sequential axis of the grid:
between the op's inputs and its output nothing of size C x C or T x D
goes to HBM except what the backward is handed on purpose, the state and
the inverses each grid step starts from.

A grid step takes a *tile* of L = 128 tokens (two chunks of 64; one chunk
where C does not divide 128) of the `Hv // Hk` value heads of one key
head, so q and k are read once where they lie, (B, T, Hk*Dk), and never
repeated. What does not depend on the state (k k^T, q k^T, the decays,
A, its inverse, U and W) is made for the tile's chunks at once, as
L x L matrices masked to the chunks' diagonal blocks: a product of two
128 x 128 matrices costs the MXU what one of two 64 x 64 does. The
chunks' states then follow one another inside the step.

- `gated_delta_rule_fwd` walks the tiles forward with S in scratch.
- `gated_delta_rule_bwd` walks them backward with dS in scratch; a
  tile's backward is `jax.vjp` of the one tile function (`_tile`) traced
  on the loaded tiles, so the mathematics is stated once. It is handed
  the inverses the forward made, and applies d(T^-1) = -T^-1 dT T^-1.

(I + A)^-1 for the unit lower triangular I + A of a chunk is made from
float32 products by merging diagonal blocks pairwise, from blocks of two
(whose inverse is I - A) up to the chunk: inv = D - D L D for the
block-diagonal inverse D so far and the part L of A between the blocks of
a pair. Ten products for C = 64, every intermediate as small as the
inverse itself. (The finite Neumann product (I - A)(I + A^2)(I + A^4)...
costs the same ten and cancels terms of size C(n - 1, k) over a block of
n: with keys that all but coincide it is wrong by 2e-4 over blocks of
16, and this by 2e-7.)

Precision is the plain path's: products take their operands in v's dtype
and add up in float32; decays, inverse and state are float32, and a
product of two float32 matrices is `HIGHEST` whatever v's dtype (the
inverse, U and W, and their transposes in the backward).
Off the chip the kernels run interpreted.

With one decay a key channel (Kimi Delta Attention; c of rank 5) the
decay enters the contractions over the channels, so that rule has its
own statement of a tile, `_systems_channels` and `_tile_channels`, and
kernels `gated_delta_rule_channels_fwd` / `_bwd` around them; the
inverse, the masks, the grid, the scratch and the calls are shared.
`_pair_sums` says how A and P are made with no exponent above zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["delta_rule", "tiles"]

_NT = (((1,), (1,)), ((), ()))          # a @ b.T
_NN = (((1,), (0,)), ((), ()))          # a @ b
_TN = (((0,), (0,)), ((), ()))          # a.T @ b
_F32 = jnp.float32
_HI = lax.Precision.HIGHEST
_LANES = 128


def _interpret():
    return jax.default_backend() != "tpu"


def tiles(T, Dk, Dv, chunk, dtype):
    """Whether the kernels take this shape: heads of whole 128-lane
    tiles, whole chunks, and a chunk of whole sublane tiles of `dtype`
    (8 rows of float32, 16 of bfloat16) that halves down to single rows
    (the masks and the merges of the inverse shift by powers of two)."""
    C = min(int(chunk), T)
    rows = 8 * (4 // jnp.dtype(dtype).itemsize)
    return (Dk % 128 == 0 and Dv % 128 == 0 and T % C == 0
            and C % rows == 0 and C & (C - 1) == 0)


def _chunks_a_tile(C, N):
    """Chunks a grid step takes: as many as fill 128 lanes, where C
    divides 128 and that many divide the sequence's N chunks."""
    m = _LANES // C if _LANES % C == 0 else 1
    return m if N % m == 0 else 1


def _dot(a, b, dims, prec=_HI):
    return lax.dot_general(a, b, dims, precision=prec,
                           preferred_element_type=_F32)


def _iotas(L):
    return (lax.broadcasted_iota(jnp.int32, (L, L), 0),
            lax.broadcasted_iota(jnp.int32, (L, L), 1))


def _same_block(t, s, side):
    shift = side.bit_length() - 1       # side is a power of two
    return (t >> shift) == (s >> shift)


def _unit_lower_inverse(A, C):
    """(I + A)^-1 for a float32 A (L, L) that is strictly lower
    triangular inside diagonal blocks of C and zero outside them."""
    t, s = _iotas(A.shape[0])
    # a unit lower block of two has the inverse I - A
    inv = jnp.where(t == s, 1.0, 0.0) - jnp.where(_same_block(t, s, 2), A, 0.0)
    side = 2
    while side < C:                     # merge the blocks pairwise
        between = jnp.logical_and(_same_block(t, s, 2 * side),
                                  jnp.logical_not(_same_block(t, s, side)))
        DL = _dot(inv, jnp.where(between, A, 0.0), _NN)
        inv = inv - _dot(DL, inv, _NN)
        side *= 2
    return inv


def _inverse_bwd(inv, g):
    """d(T^-1) = -T^-1 dT T^-1: A's cotangent from its inverse's. What
    falls outside A's pattern is dropped by the mask that made A."""
    return -_dot(_dot(inv, g, _TN), inv, _NT)


def _column(row, pick):
    """(1, L) -> (L, 1) without a transpose: row[s] where pick[t, s],
    summed along the lanes."""
    return jnp.sum(jnp.where(pick, row, 0.0), axis=1, keepdims=True)


def _masks(L, C):
    """t == s, and s <= t and s < t inside a chunk."""
    t, s = _iotas(L)
    if C == L:
        return t == s, s <= t, s < t
    chunk = _same_block(t, s, C)
    return (t == s, jnp.logical_and(chunk, s <= t),
            jnp.logical_and(chunk, s < t))


def _decays(c_row, b_row, masks):
    """The columns of c and beta and G[t, s] = exp(c_t - c_s) for s <= t
    inside a chunk; the masked part would overflow."""
    eye, lower, _ = masks
    c_col, b_col = _column(c_row, eye), _column(b_row, eye)
    return c_col, b_col, jnp.exp(jnp.where(lower, c_col - c_row, -jnp.inf))


def _systems(k, cs, bs, cd, C):
    """A of every head: beta_t G[t, s] k_t.k_s for s < t inside a chunk."""
    masks = _masks(k.shape[0], C)
    kc = k.astype(cd)
    kk = _dot(kc, kc, _NT, _HI if cd == _F32 else None)
    As = []
    for c_row, b_row in zip(cs, bs):
        _, b_col, G = _decays(c_row, b_row, masks)
        As.append(jnp.where(masks[2], b_col * G * kk, 0.0))
    return As


def _tile(q, k, vs, cs, bs, Ss, invs, cd, C, carry):
    """One tile of L tokens (L // C chunks) of the `len(vs)` value heads
    of one key head. q, k: (L, Dk) float32 holding values of `cd`; vs:
    (L, Dv) each, in `cd`; cs, bs: (1, L) float32 rows, the running sum
    of g inside each chunk and beta; Ss: (Dk, Dv) float32 states the tile
    starts from; invs: (I + A)^-1 of every head, (L, L) float32. Returns
    the float32 outputs (L, Dv) and the states the tile ends with: the
    docstring of `ops/linear_attention.py`, statement for statement."""
    L, Dv = q.shape[0], vs[0].shape[1]
    prec = _HI if cd == _F32 else None
    masks = _masks(L, C)
    lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)
    token = lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    qk = _dot(q.astype(cd), k.astype(cd), _NT, prec)
    cat = (lambda xs: xs[0] if len(xs) == 1
           else jnp.concatenate(xs, axis=0))
    outs, states = [], []
    for v, c_row, b_row, S, inv in zip(vs, cs, bs, Ss, invs):
        c_col, b_col, G = _decays(c_row, b_row, masks)
        e_col = jnp.exp(c_col)
        # U and W of all the tile's chunks, one product
        UW = _dot(inv, jnp.concatenate(
            [b_col * v.astype(_F32), (b_col * e_col) * k], axis=1), _NN)
        U, W = UW[:, :Dv], UW[:, Dv:].astype(cd)
        P = (G * qk).astype(cd)                          # s <= t by G
        q_in = (e_col * q).astype(cd)
        # c at the end of each chunk, (1, 1), and of each token's chunk
        c_ends = [jnp.sum(jnp.where(lane == j + C - 1, c_row, 0.0), axis=1,
                          keepdims=True) for j in range(0, L, C)]
        end_col = c_ends[-1]
        for j in reversed(range(1, L // C)):
            end_col = jnp.where(token < j * C, c_ends[j - 1], end_col)
        k_out = (jnp.exp(end_col - c_col) * k).astype(cd)
        deltas, from_state = [], []
        for j, c_end in enumerate(c_ends):               # chunk after chunk
            rows = slice(j * C, (j + 1) * C)
            # W S and q S, one product
            WqS = _dot(jnp.concatenate([W[rows], q_in[rows]], axis=0),
                       S.astype(cd), _NN, prec)
            dc = (U[rows] - WqS[:C]).astype(cd)
            from_state.append(WqS[C:])
            if carry:                   # else every chunk starts from zero
                S = jnp.exp(c_end) * S + _dot(k_out[rows], dc, _TN, prec)
            deltas.append(dc)
        outs.append(cat(from_state) + _dot(P, cat(deltas), _NN, prec))
        states.append(S)
    return outs, states


def _head_tiles(v_ref, c_ref, b_ref, n, rep):
    """The value tiles and the (1, L) rows of tile n, a head at a time."""
    Dv = v_ref.shape[1] // rep
    vs = [v_ref[:, r * Dv:(r + 1) * Dv] for r in range(rep)]
    cs = [c_ref[r, pl.ds(n, 1), :] for r in range(rep)]
    bs = [b_ref[r, pl.ds(n, 1), :] for r in range(rep)]
    return vs, cs, bs


def _fwd_kernel(q_ref, k_ref, v_ref, c_ref, b_ref, o_ref, *rest, rep, C,
                carry, save):
    *kept, s_ref = rest                 # the outputs where `save`; S
    n = pl.program_id(2)
    Dv = v_ref.shape[1] // rep
    cd = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    vs, cs, bs = _head_tiles(v_ref, c_ref, b_ref, n, rep)
    q, k = q_ref[...].astype(_F32), k_ref[...].astype(_F32)
    Ss = [s_ref[r] for r in range(rep)]
    invs = [_unit_lower_inverse(A, C) for A in _systems(k, cs, bs, cd, C)]
    if save:
        for r in range(rep):
            kept[0][r] = Ss[r]
            kept[1][r] = invs[r]
    outs, states = _tile(q, k, vs, cs, bs, Ss, invs, cd, C, carry)
    for r in range(rep):
        o_ref[:, r * Dv:(r + 1) * Dv] = outs[r].astype(o_ref.dtype)
        s_ref[r] = states[r]


def _bwd_kernel(q_ref, k_ref, v_ref, c_ref, b_ref, s0_ref, inv_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dc_ref, db_ref, ds_ref, *, rep, C,
                carry):
    step, N = pl.program_id(2), pl.num_programs(2)
    n = N - 1 - step
    Dv = v_ref.shape[1] // rep
    cd = v_ref.dtype

    @pl.when(step == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    vs, cs, bs = _head_tiles(v_ref, c_ref, b_ref, n, rep)
    q, k = q_ref[...].astype(_F32), k_ref[...].astype(_F32)
    invs = [inv_ref[r] for r in range(rep)]
    _, pull = jax.vjp(
        functools.partial(_tile, cd=cd, C=C, carry=carry), q, k, vs, cs, bs,
        [s0_ref[r] for r in range(rep)], invs)
    dos = [do_ref[:, r * Dv:(r + 1) * Dv].astype(_F32) for r in range(rep)]
    dq, dk, dvs, dcs, dbs, dSs, dinvs = pull(
        (dos, [ds_ref[r] for r in range(rep)]))
    # through the inverses the forward made, to what A was made from
    _, pull = jax.vjp(functools.partial(_systems, cd=cd, C=C), k, cs, bs)
    dk_A, dcs_A, dbs_A = pull(
        [_inverse_bwd(inv, g) for inv, g in zip(invs, dinvs)])
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = (dk + dk_A).astype(dk_ref.dtype)
    for r in range(rep):
        dv_ref[:, r * Dv:(r + 1) * Dv] = dvs[r].astype(dv_ref.dtype)
        dc_ref[r, pl.ds(n, 1), :] = dcs[r] + dcs_A[r]
        db_ref[r, pl.ds(n, 1), :] = dbs[r] + dbs_A[r]
        ds_ref[r] = dSs[r]


# ---------------------------------------------------------------------------
# one decay a key channel (Kimi Delta Attention): the decay enters the
# contractions over the key channels, so these share no statement with
# `_systems` and `_tile`; the inverse, the masks and the calls are theirs
# ---------------------------------------------------------------------------
_ROUNDED = 16    # tokens a block of this many rows apart multiply in v's dtype


def _boundary_groups(x, b):
    """(L, D) as groups of whole sublane tiles that hold whole blocks of
    2b rows, each row's place in its group, and the first row r of the
    upper half of every block of a group."""
    L, D = x.shape
    G = max(2 * b, 8)
    x = x.reshape(L // G, G, D)
    return x, lax.broadcasted_iota(jnp.int32, x.shape, 1), range(b, G, 2 * b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _boundary(c, b):
    """c (L, D) -> c[r(t)] at every row t, r(t) the first row of the upper
    half of t's block of 2b rows: slices and broadcasts along sublanes."""
    x, place, firsts = _boundary_groups(c, b)
    out = None
    for r in firsts:
        row = jnp.broadcast_to(x[:, r:r + 1, :], x.shape)
        out = row if out is None else jnp.where(place >= r - b, row, out)
    return out.reshape(c.shape)


def _boundary_fwd(c, b):
    return _boundary(c, b), None


def _boundary_bwd(b, _, g):
    """Row r takes the sum over its block; every other row nothing."""
    x, place, firsts = _boundary_groups(g, b)
    out = jnp.zeros_like(x)
    for r in firsts:
        block = jnp.logical_and(place >= r - b, place < r + b)
        total = jnp.sum(jnp.where(block, x, 0.0), axis=1, keepdims=True)
        out = jnp.where(place == r, total, out)
    return (out.reshape(g.shape),)


_boundary.defvjp(_boundary_fwd, _boundary_bwd)


def _pair_sums(q, k, c, cd, C):
    """sum_d k_t[d] k_s[d] exp(c_t[d] - c_s[d]) for s < t inside a chunk
    and the same with q_t for s <= t, float32 (L, L), zero elsewhere,
    with no exponent above zero. The decay is factored along the binary
    tree the inverse is merged along: at level b (C/2, ..., 2, 1) the
    pairs with t in the upper and s in the lower half of one block of 2b
    rows share the boundary r between the halves, and
    (k_t exp(c_t - c_r)) . (k_s exp(c_r - c_s)) has a factor of t alone
    and one of s alone, each at most one: a level is one masked product
    (q's rows under k's). The levels cover every s < t of a chunk once.
    Halves of 16 rows and more multiply in v's dtype, as the plain path
    multiplies tokens of different 16-row blocks; smaller ones, which
    the plain path sums pair by pair in float32, in float32 at
    `HIGHEST`."""
    L = q.shape[0]
    token = lax.broadcasted_iota(jnp.int32, q.shape, 0)
    t = lax.broadcasted_iota(jnp.int32, (2 * L, L), 0) & (L - 1)
    s = lax.broadcasted_iota(jnp.int32, (2 * L, L), 1)
    both = jnp.zeros((2 * L, L), _F32)
    b = C // 2
    while b:
        ref = _boundary(c, b)
        fall = jnp.exp(jnp.where((token & b) != 0, c - ref, ref - c))  # <= 1
        cols = k * fall
        rows = jnp.concatenate([cols, q * fall], axis=0)
        if b >= _ROUNDED:
            level = _dot(rows.astype(cd), cols.astype(cd), _NT,
                         _HI if cd == _F32 else None)
        else:
            level = _dot(rows, cols, _NT)
        halves = jnp.logical_and((t & b) != 0, (s & b) == 0)
        both = jnp.where(jnp.logical_and(_same_block(t, s, 2 * b), halves),
                         level, both)
        b //= 2
    kk, qk = both[:L], both[L:]
    return kk, jnp.where(jnp.equal(*_iotas(L)),
                         jnp.sum(q * k, axis=1, keepdims=True), qk)


def _systems_channels(q, k, cs, bs, cd, C):
    """A and P of every head, float32: A[t, s] = beta_t sum_d k_t[d]
    k_s[d] exp(c_t[d] - c_s[d]) for s < t inside a chunk, P the same
    with q_t and without beta for s <= t."""
    eye = _masks(q.shape[0], C)[0]
    As, Ps = [], []
    for c, b_row in zip(cs, bs):
        kk, qk = _pair_sums(q, k, c, cd, C)
        As.append(_column(b_row, eye) * kk)
        Ps.append(qk)
    return As, Ps


def _tile_channels(q, k, vs, cs, bs, Ss, invs, Ps, cd, C, carry):
    """`_tile` with one decay a key channel: cs (L, Dk) float32 each, the
    running sum of g inside each chunk; Ps what `_systems_channels` made.
    `ops/linear_attention.py:_plain_channels`' docstring statement for
    statement and in its order, so that what it rounds to v's dtype is
    rounded here, once: the right-hand side from (K exp(c)) S0, then the
    float32 inverse applied a chunk at a time."""
    L, Dk = q.shape
    Dv = vs[0].shape[1]
    prec = _HI if cd == _F32 else None
    eye = _masks(L, C)[0]
    channel = jnp.equal(*_iotas(Dk))
    token = lax.broadcasted_iota(jnp.int32, q.shape, 0)
    cat = (lambda xs: xs[0] if len(xs) == 1
           else jnp.concatenate(xs, axis=0))
    outs, states = [], []
    for v, c, b_row, S, inv, P in zip(vs, cs, bs, Ss, invs, Ps):
        b_col = _column(b_row, eye)
        since_start = jnp.exp(c)                         # <= 1
        k_in, q_in = (k * since_start).astype(cd), (q * since_start).astype(cd)
        # c at the end of each chunk, (1, Dk), and of each token's chunk
        c_ends = [jnp.sum(jnp.where(token == j + C - 1, c, 0.0), axis=0,
                          keepdims=True) for j in range(0, L, C)]
        end_row = c_ends[-1]
        for j in reversed(range(1, L // C)):
            end_row = jnp.where(token < j * C, c_ends[j - 1], end_row)
        k_out = (k * jnp.exp(end_row - c)).astype(cd)
        vf = v.astype(_F32)
        deltas, from_state = [], []
        for j, c_end in enumerate(c_ends):               # chunk after chunk
            rows = slice(j * C, (j + 1) * C)
            # (K exp(c)) S and (q exp(c)) S, one product
            KqS = _dot(jnp.concatenate([k_in[rows], q_in[rows]], axis=0),
                       S.astype(cd), _NN, prec)
            rhs = b_col[rows] * (vf[rows] - KqS[:C])
            # the inverse's rows of this chunk are zero at the others'
            dc = _dot(inv[rows], cat([
                rhs if i == j else jnp.zeros_like(rhs)
                for i in range(L // C)]), _NN).astype(cd)
            from_state.append(KqS[C:])
            if carry:                   # else every chunk starts from zero
                S = (_column(jnp.exp(c_end), channel) * S
                     + _dot(k_out[rows], dc, _TN, prec))
            deltas.append(dc)
        outs.append(cat(from_state)
                    + _dot(P.astype(cd), cat(deltas), _NN, prec))
        states.append(S)
    return outs, states


def _channel_tiles(v_ref, c_ref, b_ref, n, rep):
    """The value and decay tiles and beta's (1, L) row of tile n, a head
    at a time."""
    Dv, Dk = v_ref.shape[1] // rep, c_ref.shape[1] // rep
    return ([v_ref[:, r * Dv:(r + 1) * Dv] for r in range(rep)],
            [c_ref[:, r * Dk:(r + 1) * Dk] for r in range(rep)],
            [b_ref[r, pl.ds(n, 1), :] for r in range(rep)])


def _channels_fwd_kernel(q_ref, k_ref, v_ref, c_ref, b_ref, o_ref, *rest,
                         rep, C, carry, save):
    *kept, s_ref = rest                 # the outputs where `save`; S
    n = pl.program_id(2)
    Dv = v_ref.shape[1] // rep
    cd = v_ref.dtype

    @pl.when(n == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    vs, cs, bs = _channel_tiles(v_ref, c_ref, b_ref, n, rep)
    q, k = q_ref[...].astype(_F32), k_ref[...].astype(_F32)
    Ss = [s_ref[r] for r in range(rep)]
    As, Ps = _systems_channels(q, k, cs, bs, cd, C)
    invs = [_unit_lower_inverse(A, C) for A in As]
    if save:
        for r in range(rep):
            kept[0][r] = Ss[r]
            kept[1][r] = invs[r]
    outs, states = _tile_channels(q, k, vs, cs, bs, Ss, invs, Ps, cd, C,
                                  carry)
    for r in range(rep):
        o_ref[:, r * Dv:(r + 1) * Dv] = outs[r].astype(o_ref.dtype)
        s_ref[r] = states[r]


def _channels_bwd_kernel(q_ref, k_ref, v_ref, c_ref, b_ref, s0_ref, inv_ref,
                         do_ref, dq_ref, dk_ref, dv_ref, dc_ref, db_ref,
                         ds_ref, *, rep, C, carry):
    step, N = pl.program_id(2), pl.num_programs(2)
    n = N - 1 - step
    Dv, Dk = v_ref.shape[1] // rep, c_ref.shape[1] // rep
    cd = v_ref.dtype

    @pl.when(step == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    vs, cs, bs = _channel_tiles(v_ref, c_ref, b_ref, n, rep)
    q, k = q_ref[...].astype(_F32), k_ref[...].astype(_F32)
    invs = [inv_ref[r] for r in range(rep)]
    (_, Ps), pull_systems = jax.vjp(
        functools.partial(_systems_channels, cd=cd, C=C), q, k, cs, bs)
    _, pull = jax.vjp(
        functools.partial(_tile_channels, cd=cd, C=C, carry=carry), q, k, vs,
        cs, bs, [s0_ref[r] for r in range(rep)], invs, Ps)
    dos = [do_ref[:, r * Dv:(r + 1) * Dv].astype(_F32) for r in range(rep)]
    dq, dk, dvs, dcs, dbs, dSs, dinvs, dPs = pull(
        (dos, [ds_ref[r] for r in range(rep)]))
    # through the inverses the forward made, to what A and P were made from
    dq_A, dk_A, dcs_A, dbs_A = pull_systems(
        ([_inverse_bwd(inv, g) for inv, g in zip(invs, dinvs)], dPs))
    dq_ref[...] = (dq + dq_A).astype(dq_ref.dtype)
    dk_ref[...] = (dk + dk_A).astype(dk_ref.dtype)
    for r in range(rep):
        dv_ref[:, r * Dv:(r + 1) * Dv] = dvs[r].astype(dv_ref.dtype)
        dc_ref[:, r * Dk:(r + 1) * Dk] = dcs[r] + dcs_A[r]
        db_ref[r, pl.ds(n, 1), :] = dbs[r] + dbs_A[r]
        ds_ref[r] = dSs[r]


def _specs(L, Dk, Dv, rep, N, tile_of):
    """Block specs over the grid (b, key head, step): a tile of q or k,
    of the group's value heads, the group's rows of c or beta (all N
    tiles, fetched once a head), and a tile's states or inverses."""
    qk = pl.BlockSpec((None, L, Dk), lambda b, h, i: (b, tile_of(i), h))
    v = pl.BlockSpec((None, L, rep * Dv), lambda b, h, i: (b, tile_of(i), h))
    rows = pl.BlockSpec((None, rep, N, L), lambda b, h, i: (b, h, 0, 0))

    def square(rows, cols):
        return pl.BlockSpec((None, rep, None, rows, cols),
                            lambda b, h, i: (b, h, tile_of(i), 0, 0))
    return qk, v, rows, square(Dk, Dv), square(L, L)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch,
          interpret):
    return pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)


def _dims(q, v, c):
    """Sizes, and c's and beta's rows a tile: (B, Hv, N, C) chunks as
    (B, Hv, N // m, m * C) tiles of m chunks."""
    B, T, Hk, Dk = q.shape
    Hv, Dv = v.shape[2:]
    N, C = c.shape[2:]
    m = _chunks_a_tile(C, N)
    return B, T, Hk, Dk, Hv, Dv, N // m, C, m * C


def _by_decay(c, dims, rows, tile_of):
    """What the decay's rank decides: the kernels (forward, backward),
    what their names end in, c's shape as they read it and its block
    spec. One decay a head, c of rank 4: the rows of tiles beta has. One
    a key channel, c of rank 5: (B, T, Hv * Dk) where it lies, a tile
    (L, rep * Dk) of the group's heads."""
    B, T, Hk, Dk, Hv, _, N, _, L = dims
    if c.ndim == 5:
        spec = pl.BlockSpec((None, L, Hv // Hk * Dk),
                            lambda b, h, i: (b, tile_of(i), h))
        return (_channels_fwd_kernel, _channels_bwd_kernel, "channels_",
                (B, T, Hv * Dk), spec)
    return _fwd_kernel, _bwd_kernel, "", (B, Hv, N, L), rows


# Both passes are jitted so that a model's layers share one traced
# function each (a Pallas kernel is lowered where it is called);
# `interpret` is an argument because it keys jit's cache.
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _fwd_on(q, k, v, c, beta, carry, save, interpret):
    """o (B, T, Hv, Dv) in v's dtype and, where `save`, what the backward
    is handed, float32: the state every tile starts from, (B, Hv, N, Dk,
    Dv), and its inverses, (B, Hv, N, L, L)."""
    dims = B, T, Hk, Dk, Hv, Dv, N, C, L = _dims(q, v, beta)
    rep = Hv // Hk
    qk, vspec, rows, state, inverse = _specs(L, Dk, Dv, rep, N, lambda i: i)
    kernel, _, decay, c_shape, cspec = _by_decay(c, dims, rows, lambda i: i)
    o_shape = jax.ShapeDtypeStruct((B, T, Hv * Dv), v.dtype)
    kept = [jax.ShapeDtypeStruct((B, Hv, N, Dk, Dv), _F32),
            jax.ShapeDtypeStruct((B, Hv, N, L, L), _F32)] if save else []
    out = _call(
        functools.partial(kernel, rep=rep, C=C, carry=carry, save=save),
        "gated_delta_rule_%sfwd" % decay, (B, Hk, N),
        [qk, qk, vspec, cspec, rows],
        [vspec] + ([state, inverse] if save else []), [o_shape] + kept,
        (rep, Dk, Dv), interpret)(
            q.reshape(B, T, Hk * Dk), k.reshape(B, T, Hk * Dk),
            v.reshape(B, T, Hv * Dv), c.reshape(c_shape),
            beta.reshape(B, Hv, N, L))
    return out[0].reshape(B, T, Hv, Dv), tuple(out[1:])


@functools.partial(jax.jit, static_argnums=(8, 9))
def _bwd_on(q, k, v, c, beta, states, inverses, do, carry, interpret):
    dims = B, T, Hk, Dk, Hv, Dv, N, C, L = _dims(q, v, beta)
    rep = Hv // Hk
    qk, vspec, rows, state, inverse = _specs(L, Dk, Dv, rep, N,
                                             lambda i: N - 1 - i)
    _, kernel, decay, c_shape, cspec = _by_decay(c, dims, rows,
                                                 lambda i: N - 1 - i)
    flat_qk = jax.ShapeDtypeStruct((B, T, Hk * Dk), q.dtype)
    row_shape = jax.ShapeDtypeStruct((B, Hv, N, L), _F32)
    dq, dk, dv, dc, db = _call(
        functools.partial(kernel, rep=rep, C=C, carry=carry),
        "gated_delta_rule_%sbwd" % decay, (B, Hk, N),
        [qk, qk, vspec, cspec, rows, state, inverse, vspec],
        [qk, qk, vspec, cspec, rows],
        [flat_qk, flat_qk, jax.ShapeDtypeStruct((B, T, Hv * Dv), v.dtype),
         jax.ShapeDtypeStruct(c_shape, _F32), row_shape],
        (rep, Dk, Dv), interpret)(
            q.reshape(B, T, Hk * Dk), k.reshape(B, T, Hk * Dk),
            v.reshape(B, T, Hv * Dv), c.reshape(c_shape),
            beta.reshape(B, Hv, N, L), states, inverses,
            do.reshape(B, T, Hv * Dv))
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dc.reshape(c.shape), db.reshape(beta.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def delta_rule(q, k, v, c, beta, carry_state=True):
    """The chunked rule through the kernels. q, k: (B, T, Hk, Dk),
    L2-normalised and scaled, in v's dtype; v: (B, T, Hv, Dv); c, beta:
    (B, Hv, N, C) float32, c the running sum of g inside each chunk, or
    with one decay a key channel c (B, N, C, Hv, Dk), which picks the
    kernels `gated_delta_rule_channels_fwd` / `_bwd`.
    Returns o (B, T, Hv, Dv) in v's dtype. The residuals of the backward
    are the inputs and every tile's entry state and inverses."""
    return _fwd_on(q, k, v, c, beta, carry_state, False, _interpret())[0]


def _vjp_fwd(q, k, v, c, beta, carry_state):
    o, kept = _fwd_on(q, k, v, c, beta, carry_state, True, _interpret())
    return o, (q, k, v, c, beta, *kept)


def _vjp_bwd(carry_state, res, do):
    return _bwd_on(*res, do, carry_state, _interpret())


delta_rule.defvjp(_vjp_fwd, _vjp_bwd)
