"""The whole tiny LFM2-MoE (two periods) through the benchmark's own
`ShardedTrainer` loop and through a hybridized forward, against the
benchmark's plain reference; what its step program counts; and the four
shares of an expert layer against the uncut reference layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from qwen3_next_helpers import HI, _close
import tiny_lfm2 as tl  # noqa: E402  (benchmark/tests)

SEED = 77


@pytest.fixture(scope="module")
def _followed():
    """The benchmark's own loop and reference at the tiny size: what a run
    of the cell compares, in float32; and what the step's traces count."""
    import harness
    import tiny
    import traffic
    from mxnet_tpu.ops import short_conv
    cell, config = tiny.cell("sharded_trainer", 2), dict(tl.CONFIG)
    pool = traffic.make_pool(cell, config, SEED)
    devices = jax.devices()[:1]
    before = short_conv.LAYERS.total()
    loop = harness.load_file("loops", "sharded_trainer").Loop(
        cell, config, SEED, devices)
    cell["_shapes"] = {k: tuple(v.shape) for k, v in loop.weights.items()}
    prog = harness.first_steps(loop, iter(loop.feed(traffic.cycle(pool))))
    from mxnet_tpu.observability import device_counters
    counters = device_counters.drain()
    loop.close()
    prog["conv_layers_traced"] = short_conv.LAYERS.total() - before
    ref = harness.reference_readings(config, cell, SEED, pool, devices)
    prog["counters"] = counters
    return prog, ref, cell["_shapes"]


@pytest.mark.parametrize("what", ["losses", "gradient", "three_adam_steps"])
def test_model_against_the_plain_reference(what, _followed):
    """float32 on both sides, products at `highest`: what is left is the
    order of the sums (the experts' tiles, the attention's row blocks,
    the recomputed layers), 1e-6 of a loss and 1e-5 of a gradient."""
    prog, ref, shapes = _followed
    import check
    numbers, _ = check.readings(prog, ref, shapes)
    if what == "losses":
        assert max(numbers["loss_gap_%d" % i] for i in (1, 2, 3)) < 1e-5
    elif what == "gradient":
        assert numbers["grad_diff"] < 1e-4 and numbers["grad_norm_gap"] < 1e-4
        assert len(ref["grad"]) >= 60 and set(ref["grad"]) <= set(prog["grad"])
        # the expert bias is the trainer's leaf too, and no gradient
        # reaches it: the reference does not train it
        bias = [k for k in prog["grad"] if k.endswith("expert_bias")]
        assert len(bias) == 8 and not set(bias) & set(ref["grad"])
        assert all(not np.any(prog["grad"][k]) for k in bias)
        # the tied head: one leaf, its gradient the embedding's and the
        # head's together
        assert "embed_weight" in ref["grad"] and not any(
            k.endswith("head_weight") for k in prog["grad"])
    else:
        assert numbers["change_norm_gap"] < 1e-3
        assert numbers["change_norm_gap_median"] < 1e-5
        assert all(prog["change_norms"][k] == 0.0 for k in prog["change_norms"]
                   if k.endswith("expert_bias"))


def test_logits_against_the_plain_reference():
    """The hybridized block's logits for a batch of two against the
    reference's, float32 at `highest`: within 1e-5 of the largest."""
    import model
    from mxnet_tpu import nd
    from reference import lfm2_8b_a1b as ref
    net, weights = model.build(dict(tl.CONFIG), SEED, jax.devices()[0])
    net.hybridize()
    tokens = np.random.default_rng(5).integers(0, 61, (2, 64)).astype(np.int32)
    with HI:
        got = net(nd.array(tokens, dtype="int32")).asnumpy()
        want = jax.jit(lambda w, t: ref.logits(w, t, **dict(
            tl.REFERENCE_KWARGS)))(weights, tokens)
    assert got.shape == (2, 64, 61)
    _close(jnp.asarray(got), want, 1e-5)


def test_the_step_program_counts_its_layers(_followed):
    """Each trace of the step counts `short_conv.layers` once a
    short-convolution layer (7 of the tiny model's 9), and the step
    writes the device counters of the 8 expert layers."""
    prog = _followed[0]
    assert prog["conv_layers_traced"] >= 7
    assert prog["conv_layers_traced"] % 7 == 0
    counters = {name: {k: v for k, v in by_var.items()
                       if k.startswith("lfm2moedecoder")}
                for name, by_var in prog["counters"].items()}
    held = counters["moe.assignments.held"]
    assert len(held) == 8 and all(0 < v < 2 * 64 * 4 for v in held.values())
    assert all(v >= 1 for v in counters["moe.load.max_over_mean"].values())
    from mxnet_tpu.observability import registry
    text = registry.REGISTRY.to_prometheus()
    assert "short_conv_layers" in text.replace(".", "_")


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """32 experts over 4 chips, 8 held on each, at the tiny size (16 over
    4, 4 on each): a short-convolution layer with experts as each share
    computes it (the mixer, the norms and the router alike on every chip,
    counted once; each share's experts added) against the plain
    reference's layer with every expert held."""
    from mxnet_tpu.ops.linear_attention import rms_norm
    from mxnet_tpu.ops.moe import moe_held_ffn
    from mxnet_tpu.ops.short_conv import short_conv
    from qwen3_next_helpers import _randn
    from reference import lfm2_8b_a1b as ref
    H, E, I, held = 32, 16, 16, 4
    x, w_in, w_conv, w_out, rw, wg, wu, wd, b = _randn(
        9, (2, 24, H), (3 * H, H), (H, 3), (H, H), (E, H), (E, I, H),
        (E, I, H), (E, H, I), (E,))
    p = {"l1_in_norm_weight": jnp.ones(H), "l1_post_norm_weight": jnp.ones(H),
         "l1_conv_in_weight": 0.3 * w_in, "l1_conv_weight": w_conv,
         "l1_conv_out_weight": 0.3 * w_out, "l1_moe_router_weight": 0.5 * rw,
         "l1_moe_expert_bias": 0.3 * b, "l1_moe_gate_weight": 0.3 * wg,
         "l1_moe_up_weight": 0.3 * wu, "l1_moe_down_weight": 0.3 * wd}

    @jax.jit
    def both(x, p):
        h = x + short_conv(rms_norm(x, p["l1_in_norm_weight"], 1e-5),
                           p["l1_conv_in_weight"], p["l1_conv_weight"],
                           p["l1_conv_out_weight"])
        u = rms_norm(h, p["l1_post_norm_weight"], 1e-5).reshape(-1, H)
        parts = [moe_held_ffn(u, p["l1_moe_router_weight"],
                              p["l1_moe_gate_weight"][s:s + held],
                              p["l1_moe_up_weight"][s:s + held],
                              p["l1_moe_down_weight"][s:s + held], 4, s, 8,
                              "sigmoid", p["l1_moe_expert_bias"], 1.0, 1e-6)
                 for s in range(0, E, held)]
        summed = h + sum(part[0] for part in parts).reshape(h.shape)
        uncut = ref._layer(p, x, 1, "float32", dict(
            layer_types=["conv", "conv"], num_dense_layers=1, top_k=4,
            held_start=0, routed_scale=1.0, eps=1e-5, remat=False))
        return summed, sum(part[1] for part in parts), uncut

    with HI:
        summed, rows, uncut = both(x, p)
    assert float(rows) == 2 * 24 * 4               # no token dropped anywhere
    _close(summed, uncut, 2e-5)


def test_block_refuses_what_it_cannot_build_and_names_its_leaves():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon.model_zoo import Lfm2MoeDecoder, get_lfm2_moe
    with pytest.raises(MXNetError):
        Lfm2MoeDecoder(**dict(tl.KWARGS, layer_types=["conv", "mamba"]))
    with pytest.raises(MXNetError):
        Lfm2MoeDecoder(**dict(tl.KWARGS, held_start=14))
    with pytest.raises(MXNetError):
        Lfm2MoeDecoder(**dict(tl.KWARGS, num_key_value_heads=3))
    net = get_lfm2_moe(**tl.KWARGS)
    names = {k[len(net.prefix):] for k in net.collect_params()}
    assert "l0_mlp_gate_weight" in names
    assert "l0_moe_router_weight" not in names
    assert "l1_attn_q_norm_weight" in names
    assert "l1_conv_in_weight" not in names
    assert "l8_conv_weight" in names and "l8_moe_expert_bias" in names
    assert "head_weight" not in names
    untied = get_lfm2_moe(**dict(tl.KWARGS, tie_word_embeddings=False))
    assert any(k.endswith("head_weight") for k in untied.collect_params())
