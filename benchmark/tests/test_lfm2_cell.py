"""LFM2-MoE's benchmark files on the CPU: the configuration's file against
the catalog's numbers and the model's kwargs, the parameter and FLOP
counts against the issue's and XLA's count of the plain reference, the
cell's files through the harness's own loader, the new readers, and a
rehearsal of the cell at a tiny size, whole, with the cell's layer order,
and with the short convolution broken underneath. Run by hand with the
other tests of this directory; `tests/test_lfm2_*.py` hold the program's
own."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
import tiny_lfm2 as tl
import weights

ROOT = os.path.dirname(harness.HERE)
SEED = 2654435761
CELL = "lfm2_moe_train_b2_t4096"
CONFIG = "lfm2_8b_a1b"
# the catalog's row (model-configs guide, `architectures.jsonl`), as read
# for this PR: every number of its `config`
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"]}


@pytest.fixture(autouse=True)
def _the_plain_follower_back(monkeypatch):
    """The cell's loop swaps `reference_train.follow` when it closes: a
    process runs one cell, this one runs other cells' tests afterwards."""
    import reference_train
    monkeypatch.setattr(reference_train, "follow", reference_train.follow)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def peak():
    return harness.load_json("peaks.json")["TPU v5 lite"]


def _shapes(kwargs):
    from mxnet_tpu.gluon.model_zoo import Lfm2MoeDecoder
    net = Lfm2MoeDecoder(**kwargs)
    return {k[len(net.prefix):]: tuple(p.shape)
            for k, p in net.collect_params().items()}


def test_the_configuration_is_the_catalogs_cut_as_stated(bench):
    """Every number of the catalog's config under its key, but the three
    in `reduced`; the kwargs say the same widths; the cut is published
    layers 1-5, 8 of 32 experts, a quarter of the vocabulary."""
    cell, config = harness.load_cell(bench, CELL)
    reduced = config["reduced"]
    assert reduced == ["num_hidden_layers", "num_experts", "vocab_size"]
    for k, v in CATALOG.items():
        if k in reduced:
            assert config["published"][k] == v, k
        else:
            assert config[k] == v, k
    kw = config["model"]["kwargs"]
    assert (kw["experts_held"], len(kw["layer_types"]), kw["vocab_size"]) == (
        config["num_experts"], config["num_hidden_layers"],
        config["vocab_size"]) == (8, 5, 16384)
    assert kw["layer_types"] == CATALOG["layer_types"][1:6]
    assert kw["num_dense_layers"] == CATALOG["num_dense_layers"] - 1
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_attention_heads", "num_attention_heads"),
                         ("num_key_value_heads", "num_key_value_heads"),
                         ("conv_L_cache", "conv_L_cache"),
                         ("intermediate_size", "intermediate_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("num_experts_per_tok", "num_experts_per_tok"),
                         ("routed_scaling_factor", "routed_scaling_factor"),
                         ("rope_theta", "rope_theta"),
                         ("norm_eps", "norm_eps")):
        assert kw[ours] == config[theirs], ours
    assert kw["num_experts"] == CATALOG["num_experts"]
    assert kw["tie_word_embeddings"] is True and "tie" in " ".join(
        config["assumed"])
    rk = config["reference_kwargs"]
    assert rk["layer_types"] == kw["layer_types"]
    assert (rk["heads"], rk["kv_heads"], rk["top_k"], rk["routed_scale"],
            rk["theta"], rk["eps"]) == (32, 8, 4, 1.0, 1e6, 1e-5)
    assert config["input"] == {"kind": "tokens", "length": 4096,
                               "vocab": 16384}


def test_the_cells_entries_are_there_by_name(bench):
    """The configuration, the cell and the two new readers are entries of
    their lists, found by name wherever later PRs leave them; the cell is
    listed by the thirteen metrics the issue names, by its two readers
    and by no other; the harness's loader finds every file by name."""
    cell, config = harness.load_cell(bench, CELL)
    entry, = [w for w in bench["workloads"] if w["name"] == CELL]
    conf, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (cell["name"], cell["config"], cell["traffic"],
                                cell["chips"]) == (
        CELL, CONFIG, "train_b2_t4096_bf16", 1)
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert (conf["name"], conf["file"], conf["reduced"], conf["source"]) == (
        CONFIG, "benchmark/configs/lfm2_8b_a1b.json", config["reduced"],
        config["source"])
    readers = {m["name"]: m for m in bench["per_layer"]}
    for name in ("short_conv_device_ms", "short_conv_roofline"):
        assert readers[name]["workloads"] == [CELL], name
    listed = {"fwd_device_ms", "bwd_device_ms", "optimizer_device_ms",
              "unscoped_device_pct", "idle_owned_pct", "step_prepare_ms",
              "step_launch_ms", "input_stage_ms", "attention_device_ms",
              "attention_roofline", "moe_device_ms", "moe_roofline",
              "moe_load_max_over_mean", "short_conv_device_ms",
              "short_conv_roofline"}
    for m in bench["per_layer"]:
        if "workloads" in m:
            assert (CELL in m["workloads"]) == (m["name"] in listed), m
    assert (cell["batch"], cell["pool"], cell["warmup_steps"],
            cell["trace_steps"], cell["control"], cell["compute_dtype"],
            cell["loop"]) == (2, 8, 5, 10, "fp8", "bfloat16",
                              "sharded_trainer_net_on_host")
    assert set(cell["limits_why"]) >= set(cell["limits"])
    for kind, name in (("loops", cell["loop"]), ("flops", config["flops"]),
                       ("reference", config["reference"]),
                       ("metrics", "short_conv_device_ms"),
                       ("metrics", "short_conv_roofline")):
        harness.load_file(kind, name)


def test_the_counts_are_the_issues():
    config = harness.load_json("configs", "lfm2_8b_a1b.json")
    flops = harness.load_file("flops", CONFIG)
    ref = harness.load_file("reference", CONFIG)
    shapes = _shapes(config["model"]["kwargs"])
    size = lambda names: sum(int(jnp.prod(jnp.array(shapes[k])))   # noqa: E731
                             for k in names)
    trained = [k for k in shapes if ref.trainable(k)]
    assert size(trained) == config["parameters"]
    assert config["parameters"] == pytest.approx(507.8e6, rel=1e-3)
    assert size(["embed_weight"]) == 33554432
    assert size(k for k in trained if k.startswith("l0_conv")) == \
        pytest.approx(16.8e6, rel=2e-3)
    assert size(k for k in trained if k.startswith("l0_mlp")) == \
        pytest.approx(44.0e6, rel=2e-3)
    assert size(k for k in trained if k.startswith("l1_attn")) == \
        pytest.approx(10.5e6, rel=2e-3)
    assert size(k for k in trained if k.startswith("l2_moe_gate")) * 3 == \
        pytest.approx(88.1e6, rel=2e-3)
    macs = flops.forward_macs(config, 4096)
    assert sum(macs.values()) / 4096 == pytest.approx(207.9e6, rel=2e-3)
    step = 2 * flops.train_flops_per_sample(config)
    assert step == pytest.approx(10.2e12, rel=5e-3)
    share = {k: v / sum(macs.values()) for k, v in macs.items()}
    assert share["short_conv"] == pytest.approx(0.32, abs=0.01)
    assert share["moe"] == pytest.approx(0.21, abs=0.01)
    assert share["dense"] == pytest.approx(0.21, abs=0.01)
    assert share["head"] == pytest.approx(0.16, abs=0.01)
    assert share["attention"] == pytest.approx(0.04, abs=0.01)
    counts = flops.kernel_counts(config, 2)
    assert sorted(counts) == ["attention", "moe", "short_conv"]
    # every kernel is bound by its FLOPs at these shapes
    for k, (ops, least_bytes) in counts.items():
        assert ops / 197e12 > least_bytes / 819e9 > 0, k


def test_count_against_xla_at_a_small_size():
    """XLA's count of the plain reference's forward and backward, nothing
    recomputed. The reference multiplies the whole square of scores and
    every held expert by every token, so the count is asked for those."""
    flops = harness.load_file("flops", CONFIG)
    ref = harness.load_file("reference", CONFIG)
    kwargs = dict(tl.KWARGS, vocab_size=512, hidden_size=256,
                  num_attention_heads=4, intermediate_size=512,
                  moe_intermediate_size=128)
    t = 128
    shapes = _shapes(kwargs)
    w = weights.make_weights(shapes, tl.INITIALIZER, 1)
    p = {k: v for k, v in w.items() if ref.trainable(k)}
    frozen = {k: v for k, v in w.items() if not ref.trainable(k)}
    x = jnp.zeros((1, t), jnp.int32)
    y = jnp.zeros((1, t), jnp.float32)
    fn = jax.jit(jax.value_and_grad(
        lambda q: ref.loss({**frozen, **q}, x, y, "float32", remat=False,
                           **dict(tl.REFERENCE_KWARGS))))
    cost = fn.lower(p).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mine = flops.train_flops_per_sample(
        {"model": {"kwargs": kwargs}}, length=t, causal_share=1.0,
        held_per_token=kwargs["experts_held"])
    assert 0.95 * mine <= cost["flops"] <= 1.05 * mine, (mine, cost["flops"])


def _run(bench, peak, config=None, **kw):
    cell = tiny.cell("sharded_trainer_net_on_host", 2)
    return harness.run_cell(cell, config or dict(tl.CONFIG), bench, SEED, 0.3,
                            False, jax.devices()[:1], peak, **kw)


def test_rehearsal_of_the_cell_agrees_with_the_reference(bench, peak):
    result = _run(bench, peak)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["unheld"]["grad_diff"] < 1e-4
    json.dumps(result)


def test_the_cells_layer_order_counts_four_convolutions_and_four_expert_layers(
        bench, peak):
    """The cell's five layers at a tiny width: each trace of the step
    counts `short_conv.layers` four times, and the graph calls the held
    expert layer in the four sparse layers."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import Lfm2MoeDecoder
    from mxnet_tpu.ops import short_conv
    layers = harness.load_json("configs", "lfm2_8b_a1b.json")[
        "model"]["kwargs"]["layer_types"]
    kwargs = dict(tl.KWARGS, layer_types=layers)
    config = dict(tl.CONFIG, model=dict(tl.CONFIG["model"], kwargs=kwargs),
                  reference_kwargs=dict(tl.REFERENCE_KWARGS,
                                        layer_types=layers))
    before = short_conv.LAYERS.total()
    result = _run(bench, peak, config)
    traced = short_conv.LAYERS.total() - before
    assert result["correct"] is True, result["compared"]
    assert traced >= 4 and traced % 4 == 0
    graph = json.loads(Lfm2MoeDecoder(**kwargs)(mx.sym.var("data")).tojson())
    ops = [n["op"] for n in graph["nodes"]]
    assert ops.count("_contrib_short_conv") == 4
    assert ops.count("_contrib_moe_held_ffn") == 4
    assert ops.count("_contrib_causal_gqa_attention") == 1


@pytest.mark.parametrize("fault", ["leaks_across_sequences", "taps_reversed"])
def test_rehearsal_with_the_short_convolution_broken_reads_not_correct(
        fault, bench, peak, monkeypatch):
    """The timed path broken underneath: the taps run over the batch laid
    end to end (the second sequence sees the first's last tokens), or in
    the wrong order in time (a convolution where PyTorch's Conv1d
    correlates)."""
    from mxnet_tpu.ops import short_conv as sc
    whole = sc.short_conv
    if fault == "leaks_across_sequences":
        def broken(x, w_in, w_conv, w_out):
            B, T, H = x.shape
            return whole(x.reshape(1, B * T, H), w_in, w_conv,
                         w_out).reshape(B, T, H)
    else:
        def broken(x, w_in, w_conv, w_out):
            return whole(x, w_in, w_conv[:, ::-1], w_out)
    monkeypatch.setattr(sc, "short_conv", broken)
    result = _run(bench, peak)
    assert result["correct"] is False, result["compared"]


def test_the_readers_read_nothing_where_there_is_nothing(bench):
    """Untraced, or a configuration with no short convolution: None,
    not an error."""
    cell, config = harness.load_cell(bench, CELL)
    run = {"traced_steps": 0, "steps": [], "config": config, "batch": 2,
           "chips": 1}
    for name in ("short_conv_device_ms", "short_conv_roofline"):
        assert harness.load_file("metrics", name).read(run) is None
