"""Qwen3-Next's expert layer on the CPU at tiny sizes: the held range
against a loop over the experts, and the shares against the uncut
layer."""
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu.ops.moe import moe_held_ffn, route_top_k, shared_expert_ffn
from qwen3_next_helpers import HI, _close, _randn, _value_and_grads


N, H, I, E_ALL, TOP = 48, 16, 8, 16, 3


def _expert_weights(seed=7):
    x, rw, wg, wu, wd = _randn(seed, (N, H), (E_ALL, H), (E_ALL, I, H),
                               (E_ALL, I, H), (E_ALL, H, I))
    return x, 0.5 * rw, 0.3 * wg, 0.3 * wu, 0.3 * wd


def _loop_over_experts(x, rw, wg, wu, wd, start, held):
    top_i, top_w, _ = route_top_k(x, rw, TOP)
    y = jnp.zeros_like(x)
    for e in range(start, start + held):
        w = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        y = y + w[:, None] * ((jax.nn.silu(x @ wg[e].T) * (x @ wu[e].T))
                              @ wd[e].T)
    return y


@pytest.mark.parametrize("start,held,tile", [(0, 4, 8), (8, 4, 4), (12, 4, 16),
                                             (0, 16, 8), (5, 2, 8)])
def test_held_expert_layer_matches_a_loop_over_experts(start, held, tile):
    x, rw, wg, wu, wd = _expert_weights()
    cut = slice(start, start + held)
    layer = lambda x, rw, a, b, c: moe_held_ffn(   # noqa: E731
        x, rw, a, b, c, TOP, start, tile)
    with HI:
        y, rows, load = jax.jit(layer)(x, rw, wg[cut], wu[cut], wd[cut])
        top_i, _, counts = jax.jit(lambda x, rw: route_top_k(x, rw, TOP))(x, rw)
        got = _value_and_grads(lambda *a: layer(*a)[0],
                               (x, rw, wg[cut], wu[cut], wd[cut]))[1]
        ref, want = _value_and_grads(lambda *a: _loop_over_experts(
            *a, start, held), (x, rw, wg, wu, wd))
    _close(y, ref, 2e-5)
    assert float(rows) == float(((top_i >= start)
                                 & (top_i < start + held)).sum())
    assert float(load) == pytest.approx(float(counts.max() / counts.mean()))
    for a, b in zip(got, want):
        _close(a, b[cut] if b.shape[0] == E_ALL and b.ndim == 3 else b, 5e-5)


def test_no_token_is_dropped_when_every_token_picks_one_expert():
    """The whole load on one held expert: a capacity layer would drop."""
    x, rw, wg, wu, wd = _expert_weights()
    rw = rw.at[2].set(0.0).at[2, 0].set(50.0)
    x = x.at[:, 0].set(jnp.abs(x[:, 0]) + 1.0)
    with HI:
        y, rows, load = jax.jit(lambda *a: moe_held_ffn(*a, TOP, 0, 8))(
            x, rw, wg[:4], wu[:4], wd[:4])
        top_i, _, _ = jax.jit(lambda x, rw: route_top_k(x, rw, TOP))(x, rw)
        ref = jax.jit(lambda *a: _loop_over_experts(*a, 0, 4))(
            x, rw, wg, wu, wd)
    assert bool(jnp.all(jnp.any(top_i == 2, -1))) and float(load) > 5
    _close(y, ref, 2e-5)


@pytest.mark.parametrize("shares", [4, 16])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """Each chip of `shares` holds E_ALL / shares experts and computes its
    own part; the parts, with the shared expert counted once, are the
    uncut layer."""
    x, rw, wg, wu, wd = _expert_weights(11)
    sg, su, sd, ss = _randn(12, (I, H), (I, H), (H, I), (1, H))
    held = E_ALL // shares
    @jax.jit
    def both(x, rw, wg, wu, wd):
        parts = [moe_held_ffn(x, rw, wg[s:s + held], wu[s:s + held],
                              wd[s:s + held], TOP, s, 8)
                 for s in range(0, E_ALL, held)]
        whole, rows, _ = moe_held_ffn(x, rw, wg, wu, wd, TOP, 0, 8)
        return (sum(p[0] for p in parts), sum(p[1] for p in parts), whole,
                rows, _loop_over_experts(x, rw, wg, wu, wd, 0, E_ALL),
                shared_expert_ffn(x, sg, su, sd, ss))

    with HI:
        summed, summed_rows, whole, rows, uncut, shared = both(
            x, rw, wg, wu, wd)
        _close(shared, jax.nn.sigmoid(x @ ss.T)
               * ((jax.nn.silu(x @ sg.T) * (x @ su.T)) @ sd.T), 1e-5)
    _close(summed + shared, uncut + shared, 2e-5)
    _close(whole, uncut, 2e-5)
    assert float(summed_rows) == float(rows) == N * TOP
