"""The whole tiny Kimi Linear through the benchmark's own
`ShardedTrainer` loop, the cell's, against the benchmark's plain
reference, the counters the step publishes, and what that loop, which
moves the net's copy of the weights to the host, reads beside the
inherited loop's readings."""
import jax
import numpy as np
import pytest

import qwen3_next_helpers      # noqa: F401  (the benchmark's path)
import tiny_kimi_linear as tk  # noqa: E402  (benchmark/tests)


@pytest.mark.parametrize("what", ["losses", "gradient", "three_adam_steps"])
def test_model_against_the_plain_reference(what, _followed):
    prog, ref, shapes = _followed
    import check
    numbers, _ = check.readings(prog, ref, shapes)
    if what == "losses":
        assert max(numbers["loss_gap_%d" % i] for i in (1, 2, 3)) < 1e-5
    elif what == "gradient":
        assert numbers["grad_diff"] < 1e-4 and numbers["grad_norm_gap"] < 1e-4
        assert len(ref["grad"]) >= 70 and set(ref["grad"]) <= set(prog["grad"])
        # the router's bias is the trainer's leaf too, and no gradient
        # reaches it: the reference does not train it
        bias = [k for k in prog["grad"] if k.endswith("router_bias")]
        assert len(bias) == 4 and not set(bias) & set(ref["grad"])
        assert all(not np.any(prog["grad"][k]) for k in bias)
    else:
        assert numbers["change_norm_gap"] < 1e-3
        assert numbers["change_norm_gap_median"] < 1e-5
        assert all(prog["change_norms"][k] == 0.0 for k in prog["change_norms"]
                   if k.endswith("router_bias"))


@pytest.fixture(scope="module")
def _followed():
    """The benchmark's own loop and reference at the tiny size: what a run
    of the cell compares, in float32, through the cell's loop
    `sharded_trainer_net_on_host` and the follower its closing puts in
    `reference_train.follow`'s place (put back here); beside each reading
    that loop takes a leaf at a time, the inherited loop's reading of the
    same trainer at the same step."""
    import harness
    import reference_train
    import reference_train_on_host
    import tiny
    import traffic
    from mxnet_tpu.ops import attention, linear_attention
    mod = harness.load_file("loops", "sharded_trainer_net_on_host")

    class Beside(mod.Loop):
        def first_gradient(self):
            self.inherited = {"grad": mod._base.Loop.first_gradient(self)}
            return super().first_gradient()

        def change_norms(self):
            self.inherited["change_norms"] = mod._base.Loop.change_norms(self)
            return super().change_norms()

    cell, config, seed = (tiny.cell("sharded_trainer_net_on_host", 2),
                          dict(tk.CONFIG), 77)
    pool = traffic.make_pool(cell, config, seed)
    devices = jax.devices()[:1]
    path = linear_attention.DELTA_PATH
    before = (path.get(path="plain"), path.get(path="kernel"),
              attention.LATENT_LAYERS.total())
    loop = Beside(cell, config, seed, devices)
    on_host = (all(isinstance(v, np.ndarray) for v in loop.weights.values()),
               all(p.list_ctx()[0].device_type == "cpu"
                   for p in loop.net.collect_params().values()))
    cell["_shapes"] = {k: tuple(v.shape) for k, v in loop.weights.items()}
    prog = harness.first_steps(loop, iter(loop.feed(traffic.cycle(pool))))
    from mxnet_tpu.observability import device_counters
    counters = device_counters.drain()
    plain_follow = reference_train.follow
    loop.close()
    swapped = reference_train.follow is reference_train_on_host.follow
    try:
        ref = harness.reference_readings(config, cell, seed, pool, devices)
    finally:
        reference_train.follow = plain_follow
    prog["traced"] = tuple(now - was for now, was in zip(
        (path.get(path="plain"), path.get(path="kernel"),
         attention.LATENT_LAYERS.total()), before))
    prog["counters"] = counters
    prog["net_on_host"] = (on_host, swapped, loop.inherited)
    return prog, ref, cell["_shapes"]


def test_device_counters_are_published_without_a_sync(_followed):
    # the gauges keep other tests' trainers too: this loop's net alone
    counters = {name: {k: v for k, v in by_var.items()
                       if k.startswith("kimilineardecoder")}
                for name, by_var in _followed[0]["counters"].items()}
    chunks = counters["linear_attention.chunks"]
    assert len(chunks) == 4 and set(chunks.values()) == {2 * 64 / 32}
    held = counters["moe.assignments.held"]
    assert len(held) == 4 and all(0 < v < 2 * 64 * 3 for v in held.values())
    assert all(v >= 1 for v in counters["moe.load.max_over_mean"].values())


def test_the_step_program_counts_its_paths(_followed):
    """Each trace of the step counts `plain` once a delta-attention layer
    and never `kernel` (heads of 8 do not tile; at the published 128
    they do: `test_chip_compile.py`), and the latent layer once."""
    plain, kernel, latent = _followed[0]["traced"]
    assert kernel == 0 and plain >= 4 and plain % 4 == 0
    assert latent == plain // 4
    from mxnet_tpu.observability import registry
    text = registry.REGISTRY.to_prometheus()
    assert "attention_latent_layers" in text.replace(".", "_")


def test_net_on_host_loop_reads_what_the_plain_loop_reads(_followed):
    """`sharded_trainer_net_on_host`: the net's parameters and the seed's
    weights leave the device, every reading is the inherited loop's of the
    same trainer, and closing it hands the reference's steps to the
    follower that keeps its spare arrays on the host."""
    prog = _followed[0]
    (weights_on_host, net_on_host), swapped, inherited = prog["net_on_host"]
    assert weights_on_host and net_on_host and swapped
    assert prog["change_norms"] == inherited["change_norms"]
    assert sorted(prog["grad"]) == sorted(inherited["grad"])
    assert all(np.array_equal(prog["grad"][k], inherited["grad"][k])
               for k in inherited["grad"])


def test_block_refuses_lists_that_do_not_cover_the_layers():
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon.model_zoo import KimiLinearDecoder, get_kimi_linear
    with pytest.raises(MXNetError):
        KimiLinearDecoder(**dict(tk.KWARGS, kda_layers=[1, 2, 3]))
    with pytest.raises(MXNetError):
        KimiLinearDecoder(**dict(tk.KWARGS, full_attn_layers=[3, 4]))
    with pytest.raises(MXNetError):
        KimiLinearDecoder(**dict(tk.KWARGS, held_start=14))
    net = get_kimi_linear(**tk.KWARGS)
    names = {k[len(net.prefix):] for k in net.collect_params()}
    assert "l1_mlp_gate_weight" in names and "l1_moe_router_weight" not in names
    assert "l4_mla_kvb_weight" in names and "l4_kda_qkv_weight" not in names
    assert "l5_kda_A_log" in names and "l2_moe_router_bias" in names
