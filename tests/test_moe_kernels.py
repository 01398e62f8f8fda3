"""The held-range expert layer's Pallas kernels (`ops/moe_kernels.py`),
interpreted on the CPU, against the plain loop over tiles of `ops/moe.py`
at small shapes: the forward output and the gradients of x, the three
matrices and the routing weights, for loads that reach every branch of
the kernels' tiling; and which path the op's rule picks."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe
from mxnet_tpu.ops import moe_kernels as mk

N, H, I, E = 32, 128, 256, 4          # tokens, widths, experts held
E_ALL = 8
ROWS = 8                              # the kernels' row tile here


def _weights(seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf = jnp.bfloat16
    x = jax.random.normal(k[0], (N, H)).astype(bf)
    wg = (0.1 * jax.random.normal(k[1], (E, I, H))).astype(bf)
    wu = (0.1 * jax.random.normal(k[2], (E, I, H))).astype(bf)
    wd = (0.1 * jax.random.normal(k[3], (E, H, I))).astype(bf)
    return x, wg, wu, wd, jax.random.normal(k[4], (N, H), jnp.float32)


def _route(case, rng):
    """top_i (N, k) for a named load over E_ALL experts, and the held
    range's start."""
    k = 5 if case == "k_over_E" else 3
    start = 3 if case == "held_start" else 0
    top_i = np.stack([rng.permutation(E_ALL)[:k] for _ in range(N)])
    if case == "one_expert":            # every token's first choice: 1
        top_i = np.stack([np.r_[1, rng.permutation(np.arange(4, 8))[:k - 1]]
                          for _ in range(N)])
    elif case == "empty_expert":        # no token picks held expert 2
        top_i = np.stack([rng.permutation([0, 1, 3, 4, 5, 6, 7])[:k]
                          for _ in range(N)])
    elif case == "no_held_rows":
        top_i = np.stack([rng.permutation(np.arange(4, 8))[:k]
                          for _ in range(N)])
    return jnp.asarray(top_i, jnp.int32), start


def _counts(top_i, start):
    local = np.asarray(top_i) - start
    return jnp.asarray([(local == e).sum() for e in range(E)], jnp.float32)


@jax.jit
def _plain(x, wg, wu, wd, top_w, top_i, counts, c, start):
    return _value_and_grads(x, wg, wu, wd, top_w, top_i, counts, c, start,
                            False)


@jax.jit
def _kernels(x, wg, wu, wd, top_w, top_i, counts, c, start):
    return _value_and_grads(x, wg, wu, wd, top_w, top_i, counts, c, start,
                            True)


def _value_and_grads(x, wg, wu, wd, top_w, top_i, counts, c, start,
                     kernels):
    def loss(x, wg, wu, wd, top_w):
        # the held range's start enters as data: one trace a path
        y = moe._held(x, wg, wu, wd, top_i - start, top_w, counts, 0, ROWS,
                      kernels)
        return (y.astype(jnp.float32) * c).sum(), y
    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                       has_aux=True)(x, wg, wu, wd, top_w)
    return (y,) + grads


@pytest.mark.parametrize("case", [
    "balanced", "one_expert", "empty_expert", "no_held_rows", "held_start",
    "k_over_E", "chunks_of_I"])
def test_kernels_match_the_plain_loop(case, monkeypatch):
    """balanced: rows on every held expert; one_expert: four tiles of one
    expert, so its weight gradients are summed in VMEM over them;
    empty_expert: a held expert with no rows (its gradients zero);
    no_held_rows: every tile empty; held_start: the held range from
    expert 3; k_over_E: five choices over four held experts; chunks_of_I:
    I cut in two 128-wide chunks, each adding to y and dx, in tiles of
    16 rows."""
    monkeypatch.setattr(mk, "_ROWS", ROWS)
    passes = []
    if case == "chunks_of_I":         # tiles of 16 rows: a trace of its own
        monkeypatch.setattr(mk, "shape", lambda H, I, backward: (
            passes.append(backward) or (16, 128)))
    rng = np.random.default_rng(sum(map(ord, case)))
    x, wg, wu, wd, c = _weights()
    top_i, start = _route(case, rng)
    top_w = jax.nn.softmax(jnp.asarray(rng.standard_normal(top_i.shape),
                                       jnp.float32), axis=-1)
    counts = _counts(top_i, start)
    args = (x, wg, wu, wd, top_w, top_i, counts, c, start)
    want = _plain(*args)
    got = (_kernels if case != "chunks_of_I" else jax.jit(
        lambda *a: _value_and_grads(*a, True)))(*args)
    assert ({False, True} <= set(passes)) == (case == "chunks_of_I")
    for name, a, b in zip(("y", "dx", "dwg", "dwu", "dwd", "dtop_w"),
                          got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        # within a bfloat16 rounding of the largest value: the sums run in
        # float32 in another order
        scale = max(np.abs(b).max(), 1e-30)
        assert np.abs(a - b).max() <= 2 ** -8 * scale, (name, case)
    if case == "empty_expert":
        assert not np.asarray(got[2][2], np.float32).any()
    if case == "no_held_rows":
        assert not np.asarray(got[0], np.float32).any()
        assert not np.asarray(got[1], np.float32).any()


def test_the_path_follows_the_shape_and_the_backend(monkeypatch):
    """`moe.held.path` counts `kernel` where the layer tiles on a TPU
    (bfloat16, H and I multiples of 128), and `plain` for the tiny
    models' widths, float32 inputs, or off the chip."""
    path = moe.HELD_PATH

    def traced(h, i, dtype):
        kernel, plain = path.get(path="kernel"), path.get(path="plain")
        x = jax.ShapeDtypeStruct((N, h), dtype)
        jax.eval_shape(lambda x, rw, a, b, d: moe.moe_held_ffn(
            x, rw, a, b, d, 3, 0, ROWS), x,
            jax.ShapeDtypeStruct((E_ALL, h), jnp.float32),
            *(jax.ShapeDtypeStruct(s, dtype)
              for s in ((E, i, h), (E, i, h), (E, h, i))))
        return (path.get(path="kernel") - kernel,
                path.get(path="plain") - plain)

    assert traced(H, I, jnp.bfloat16) == (0, 1)       # off the chip
    monkeypatch.setattr(mk, "_interpret", lambda: False)
    assert traced(H, I, jnp.bfloat16) == (1, 0)
    assert traced(16, 8, jnp.bfloat16) == (0, 1)      # the tiny models
    assert traced(H, I, jnp.float32) == (0, 1)
    assert traced(H, 192, jnp.bfloat16) == (0, 1)


@pytest.mark.parametrize("mib, chunk", [(128, 896), (64, 256), (16, None)])
def test_the_chunk_of_I_follows_the_vmem(monkeypatch, mib, chunk):
    """The backward's chunk of LFM2's I (1792, H 2048) is the widest that
    fits 25/32 of the core's VMEM: two of 896 on a v5e's 128 MiB, seven
    of 256 on a 64 MiB core; on 16 MiB none fits, and the rule keeps the
    plain loop."""
    monkeypatch.setattr(mk, "_vmem_capacity", lambda: mib * 2 ** 20)
    got = mk.shape(2048, 1792, True)
    assert (got and got[1]) == chunk
    assert mk.tiles(2048, 1792, jnp.bfloat16) == (chunk is not None)
