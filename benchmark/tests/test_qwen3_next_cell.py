"""Qwen3-Next's benchmark files on the CPU: the FLOP count against XLA's
count of the plain reference, the planted chunk fault against the
reference, and a rehearsal of the cell at a tiny size, whole and with the
loop broken underneath. Run by hand with the other tests of this
directory; `tests/test_qwen3_next.py` holds the program's own."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
import tiny_qwen3_next as tq
import weights

ROOT = os.path.dirname(harness.HERE)
SEED = 3000000019


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def peak():
    return harness.load_json("peaks.json")["TPU v5 lite"]


def _shapes(kwargs):
    from mxnet_tpu.gluon.model_zoo import Qwen3NextDecoder
    net = Qwen3NextDecoder(**kwargs)
    return {k[len(net.prefix):]: tuple(p.shape)
            for k, p in net.collect_params().items()}


def test_the_cells_count_is_the_issues():
    config = harness.load_json("configs", "qwen3_next_80b_a3b.json")
    flops = harness.load_file("flops", "qwen3_next_80b_a3b")
    shapes = _shapes(config["model"]["kwargs"])
    assert sum(int(jnp.prod(jnp.array(s))) for k, s in shapes.items()
               if not k.endswith("_stats")) == config["parameters"] == 424340544
    macs = flops.forward_macs(config, 8192)
    assert macs["attention"] == pytest.approx(0.275e12, rel=0.01)
    outside = (sum(macs.values()) - macs["attention"]) / 8192
    assert outside == pytest.approx(192.8e6, rel=0.01)     # the issue: ~188M
    assert flops.train_flops_per_sample(config) == pytest.approx(11.1e12,
                                                                 rel=0.01)
    counts = flops.kernel_counts(config, 1)
    for kernel, (ops, least) in counts.items():
        assert ops > 0 and least > 0, kernel
    # the delta rule is bound by its bytes, attention and experts by FLOPs
    ms = {k: (1e3 * o / 197e12, 1e3 * b / 819e9) for k, (o, b) in counts.items()}
    assert ms["linear_attention"][1] > ms["linear_attention"][0]
    assert ms["attention"][0] > ms["attention"][1]
    assert ms["moe"][0] > ms["moe"][1]


def test_count_against_xla_at_a_small_size():
    """XLA's count of the plain reference's forward and backward, nothing
    recomputed. The reference multiplies the whole square of scores and
    every held expert by every token, so the count is asked for those."""
    flops = harness.load_file("flops", "qwen3_next_80b_a3b")
    ref = harness.load_file("reference", "qwen3_next_80b_a3b")
    kwargs = dict(tq.KWARGS, vocab_size=512, hidden_size=256,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                  linear_num_key_heads=2, linear_num_value_heads=4,
                  linear_key_head_dim=32, linear_value_head_dim=32,
                  moe_intermediate_size=128,
                  shared_expert_intermediate_size=128)
    t = 128
    shapes = _shapes(kwargs)
    w = weights.make_weights(shapes, tq.INITIALIZER, 1)
    p = {k: v for k, v in w.items() if ref.trainable(k)}
    x = jnp.zeros((1, t), jnp.int32)
    y = jnp.zeros((1, t), jnp.float32)
    kw = dict(tq.REFERENCE_KWARGS, rotary_dim=32)
    fn = jax.jit(jax.value_and_grad(
        lambda q: ref.loss(q, x, y, "float32", remat=False, **kw)))
    cost = fn.lower(p).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mine = flops.train_flops_per_sample(
        {"model": {"kwargs": kwargs}}, length=t, causal_share=1.0,
        held_per_token=kwargs["experts_held"])
    assert 0.95 * mine <= cost["flops"] <= 1.05 * mine, (mine, cost["flops"])


def test_reference_catches_a_state_not_carried_between_chunks():
    """The reference's token-by-token recurrence against the program's
    chunked op with the carry cut: the fault of the tests, at the
    reference's own interface."""
    from mxnet_tpu.ops.linear_attention import gated_delta_rule
    ref = harness.load_file("reference", "qwen3_next_80b_a3b")
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k = (jax.random.normal(keys[i], (1, 32, 2, 8)) for i in (0, 1))
    v = jax.random.normal(keys[2], (1, 32, 2, 8))
    g = -0.03 * jax.nn.softplus(jax.random.normal(keys[3], (1, 32, 2)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 32, 2)))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    with jax.default_matmul_precision("highest"):
        want = ref._recurrence(l2(q) * 8 ** -0.5, l2(k), v, g, beta, "float32")
        good = gated_delta_rule(q, k, v, g, beta, chunk=8)
        bad = gated_delta_rule(q, k, v, g, beta, chunk=8, carry_state=False)
    assert float(jnp.abs(good - want).max()) < 1e-5
    assert float(jnp.abs(bad - want).max()) > 0.05


def _run(bench, peak, **kw):
    cell = tiny.cell("sharded_trainer", 2)
    return harness.run_cell(cell, dict(tq.CONFIG), bench, SEED, 0.3, False,
                            jax.devices()[:1], peak, **kw)


def test_rehearsal_of_the_cell_agrees_with_the_reference(bench, peak):
    result = _run(bench, peak)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["unheld"]["grad_diff"] < 1e-4
    json.dumps(result)


@pytest.mark.parametrize("fault", ["state_unchanged", "chunk_state_dropped"])
def test_rehearsal_with_the_loop_broken_reads_not_correct(fault, bench, peak,
                                                          monkeypatch):
    """The timed path broken underneath: the optimizer's update thrown
    away, or the delta rule's state not carried between its chunks."""
    if fault == "state_unchanged":
        from mxnet_tpu.parallel import data_parallel

        def frozen(params, grads, state, **hp):
            _, new_state = data_parallel.adam_update(params, grads, state, **hp)
            return params, new_state

        monkeypatch.setitem(data_parallel._OPTIMIZERS, "adam",
                            (data_parallel.adam_init, frozen,
                             data_parallel._OPTIMIZERS["adam"][2]))
    else:
        from mxnet_tpu.ops import linear_attention as la
        whole = la.gated_delta_rule
        monkeypatch.setattr(la, "gated_delta_rule",
                            lambda *a, **k: whole(*a, **dict(k, carry_state=False)))
    result = _run(bench, peak)
    assert result["correct"] is False, result["compared"]
