"""Kimi Linear's benchmark files on the CPU: the parameter and FLOP
counts against the configuration's file, the issue's numbers and XLA's
count of the plain reference, the cell's files through the harness's own
loader, and a rehearsal of the cell at a tiny size, whole and with the
delta rule broken underneath. Run by hand with the other tests of this
directory; `tests/test_kimi_linear_*.py` hold the program's own."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
import tiny_kimi_linear as tk
import weights

ROOT = os.path.dirname(harness.HERE)
SEED = 3000000019
CELL = "kimi_linear_train_b1_t4096"


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
    from mxnet_tpu.gluon.model_zoo import KimiLinearDecoder
    net = KimiLinearDecoder(**kwargs)
    return {k[len(net.prefix):]: tuple(p.shape)
            for k, p in net.collect_params().items()}


def test_the_cells_files_load_and_say_what_the_issue_says(bench):
    """The configuration and the cell are the last entries of their lists,
    and the harness's own loader finds every file by name."""
    cell, config = harness.load_cell(bench, CELL)
    entry = bench["workloads"][-1]
    assert (entry["name"], entry["config"], entry["traffic"],
            entry["chips"]) == (cell["name"], cell["config"], cell["traffic"],
                                cell["chips"]) == (
        CELL, "kimi_linear_48b_a3b", "train_b1_t4096_bf16", 1)
    assert len(entry["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    assert bench["configs"][-1]["name"] == cell["config"]
    assert bench["configs"][-1]["file"] == \
        "benchmark/configs/kimi_linear_48b_a3b.json"
    assert bench["configs"][-1]["reduced"] == config["reduced"]
    assert bench["configs"][-1]["source"] == config["source"]
    assert cell["batch"] == 1 and cell["compute_dtype"] == "bfloat16"
    assert config["input"] == {"kind": "tokens", "length": 4096,
                               "vocab": 20480}
    for kind, name in (("loops", cell["loop"]), ("flops", config["flops"]),
                       ("reference", config["reference"])):
        harness.load_file(kind, name)
    # the per-layer metrics that list their cells and that Qwen3-Next's
    # cell reports: the same op kinds own this model's mixers and experts
    listed = [m["name"] for m in bench["per_layer"] if "workloads" in m
              and "qwen3next_train_b1_t8192" in m["workloads"]]
    assert len(listed) == 15
    for m in bench["per_layer"]:
        if "workloads" in m:
            assert (m["workloads"][-1] == CELL) == (m["name"] in listed), m
    assert cell["loop"] == "sharded_trainer_net_on_host"
    assert set(cell["limits"]) == {"grad_diff", "grad_norm_gap_median",
                                   "change_norm_gap"}
    assert set(cell["limits_why"]) >= set(cell["limits"])
    # every width as published; the cut is depth, experts held, vocabulary
    kw = config["model"]["kwargs"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (kw["num_layers"], kw["experts_held"], kw["vocab_size"]) == (
        config["num_hidden_layers"], config["num_experts"],
        config["vocab_size"]) == (5, 8, 20480)
    assert config["published"]["num_experts"] == kw["num_experts"] == 256
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("intermediate_size", "intermediate_size"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("kv_lora_rank", "kv_lora_rank"),
                         ("qk_nope_head_dim", "qk_nope_head_dim"),
                         ("qk_rope_head_dim", "qk_rope_head_dim"),
                         ("v_head_dim", "v_head_dim"),
                         ("num_experts_per_token", "num_experts_per_token"),
                         ("routed_scaling_factor", "routed_scaling_factor"),
                         ("num_attention_heads", "num_attention_heads"),
                         ("rms_norm_eps", "rms_norm_eps")):
        assert kw[ours] == config[theirs], ours
    linear = config["linear_attn_config"]
    assert (kw["kda_num_heads"], kw["kda_head_dim"],
            kw["short_conv_kernel_size"]) == (
                linear["num_heads"], linear["head_dim"],
                linear["short_conv_kernel_size"])
    assert kw["kda_layers"] == [i for i in linear["kda_layers"] if i <= 5]
    assert kw["full_attn_layers"] == [i for i in linear["full_attn_layers"]
                                      if i <= 5]


def test_the_cells_count_is_the_issues():
    config = harness.load_json("configs", "kimi_linear_48b_a3b.json")
    flops = harness.load_file("flops", "kimi_linear_48b_a3b")
    ref = harness.load_file("reference", "kimi_linear_48b_a3b")
    shapes = _shapes(config["model"]["kwargs"])
    size = lambda names: sum(int(jnp.prod(jnp.array(shapes[k])))   # noqa: E731
                             for k in names)
    trained = [k for k in shapes if ref.trainable(k)]
    assert size(trained) == config["parameters"] == 602433408
    # the issue's terms: a KDA mixer 39.51M, the latent one 29.11M
    assert size(k for k in trained if k.startswith("l2_kda_")) == 39514272
    assert size(k for k in trained if k.startswith("l4_mla_")) == 29114880
    assert size(k for k in trained if k.startswith("l1_")) == 103219872
    macs = flops.forward_macs(config, 4096)
    assert macs["attention"] == pytest.approx(0.0859e12, rel=0.01)
    outside = (sum(macs.values()) - macs["attention"]) / 4096
    assert outside == pytest.approx(335.8e6, rel=0.02)       # the issue's
    assert flops.train_flops_per_sample(config) == pytest.approx(8.9e12,
                                                                 rel=0.01)
    kda = (macs["linear_projections"] + macs["linear_attention"]) \
        / sum(macs.values())
    assert kda == pytest.approx(0.45, abs=0.01)
    counts = flops.kernel_counts(config, 1)
    assert sorted(counts) == ["attention", "linear_attention", "moe"]
    # the delta rule is bound by its bytes, attention and experts by FLOPs
    ms = {k: (1e3 * o / 197e12, 1e3 * b / 819e9)
          for k, (o, b) in counts.items()}
    assert ms["linear_attention"][1] > ms["linear_attention"][0] > 0
    assert ms["attention"][0] > ms["attention"][1] > 0
    assert ms["moe"][0] > ms["moe"][1] > 0


def test_count_against_xla_at_a_small_size():
    """XLA's count of the plain reference's forward and backward, nothing
    recomputed. The reference multiplies the whole square of scores and
    every held expert by every token, so the count is asked for those."""
    flops = harness.load_file("flops", "kimi_linear_48b_a3b")
    ref = harness.load_file("reference", "kimi_linear_48b_a3b")
    kwargs = dict(tk.KWARGS, vocab_size=512, hidden_size=256,
                  kda_num_heads=4, kda_head_dim=32, kda_low_rank_dim=32,
                  num_attention_heads=4, qk_nope_head_dim=32,
                  qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
                  intermediate_size=512, moe_intermediate_size=128)
    t = 128
    shapes = _shapes(kwargs)
    w = weights.make_weights(shapes, tk.INITIALIZER, 1)
    p = {k: v for k, v in w.items() if ref.trainable(k)}
    frozen = {k: v for k, v in w.items() if not ref.trainable(k)}
    x = jnp.zeros((1, t), jnp.int32)
    y = jnp.zeros((1, t), jnp.float32)
    kw = dict(tk.REFERENCE_KWARGS, heads=4, linear_heads=4)
    fn = jax.jit(jax.value_and_grad(
        lambda q: ref.loss({**frozen, **q}, x, y, "float32", remat=False,
                           **kw)))
    cost = fn.lower(p).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mine = flops.train_flops_per_sample(
        {"model": {"kwargs": kwargs}}, length=t, causal_share=1.0,
        held_per_token=kwargs["experts_held"])
    assert 0.95 * mine <= cost["flops"] <= 1.05 * mine, (mine, cost["flops"])


def test_reference_catches_the_two_faults_of_the_delta_rule():
    """The reference's token-by-token recurrence against the program's
    chunked op whole, with the carry cut, and with the decay applied per
    head instead of per channel."""
    from mxnet_tpu.ops.linear_attention import gated_delta_rule
    ref = harness.load_file("reference", "kimi_linear_48b_a3b")
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k = (jax.random.normal(keys[i], (1, 64, 2, 8)) for i in (0, 1))
    v = jax.random.normal(keys[2], (1, 64, 2, 8))
    g = -0.1 * jax.nn.softplus(2 * jax.random.normal(keys[3], (1, 64, 2, 8)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 64, 2)))

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    with jax.default_matmul_precision("highest"):
        want = ref._recurrence(l2(q) * 8 ** -0.5, l2(k), v, g, beta, "float32")
        good = gated_delta_rule(q, k, v, g, beta, chunk=32)
        cut = gated_delta_rule(q, k, v, g, beta, chunk=32, carry_state=False)
        mean = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        per_head = gated_delta_rule(q, k, v, mean, beta, chunk=32)
        planted = ref._recurrence(l2(q) * 8 ** -0.5, l2(k), v, g, beta,
                                  "float32", per_channel=False)
    assert float(jnp.abs(good - want).max()) < 1e-5
    assert float(jnp.abs(cut - want).max()) > 0.05
    assert float(jnp.abs(per_head - want).max()) > 0.05
    assert float(jnp.abs(per_head - planted).max()) < 1e-5


def _run(bench, peak, **kw):
    cell = tiny.cell("sharded_trainer_net_on_host", 2)
    return harness.run_cell(cell, dict(tk.CONFIG), bench, SEED, 0.3, False,
                            jax.devices()[:1], peak, **kw)


def test_rehearsal_of_the_cell_agrees_with_the_reference(bench, peak):
    result = _run(bench, peak)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["compared"]["unheld"]["grad_diff"] < 1e-4
    json.dumps(result)


@pytest.mark.parametrize("fault", ["chunk_state_dropped", "decay_per_head"])
def test_rehearsal_with_the_delta_rule_broken_reads_not_correct(
        fault, bench, peak, monkeypatch):
    """The timed path broken underneath: the state not carried between
    the chunks, or one decay a head (the channels' mean) in place of one a
    channel."""
    from mxnet_tpu.ops import linear_attention as la
    whole = la.gated_delta_rule
    if fault == "chunk_state_dropped":
        broken = lambda *a, **k: whole(*a, **dict(k, carry_state=False))  # noqa: E731
    else:
        def broken(q, k, v, g, beta, **kw):
            return whole(q, k, v, jnp.broadcast_to(
                g.mean(-1, keepdims=True), g.shape), beta, **kw)
    monkeypatch.setattr(la, "gated_delta_rule", broken)
    result = _run(bench, peak)
    assert result["correct"] is False, result["compared"]


def _follow_both(config, cell, unchanged=False):
    """`reference_train.follow` and `reference_train_on_host.follow` on
    the same seed and batches."""
    import reference_train
    import reference_train_on_host
    import traffic
    ref = harness.load_file("reference", config["reference"])
    pool = traffic.make_pool(cell, config, SEED)[:harness.FIRST_STEPS]
    loop = harness.load_file("loops", "sharded_trainer").Loop(
        cell, config, SEED, jax.devices()[:1])
    shapes = {k: tuple(v.shape) for k, v in loop.weights.items()}
    loop.close()
    got = []
    for follow in (reference_train.follow, reference_train_on_host.follow):
        w = weights.make_weights(shapes, config["initializer"], SEED,
                                 jax.devices()[0])
        got.append(follow(ref, w, pool, config["optimizer"], "float32",
                          config.get("reference_kwargs"), None, unchanged))
    return got


@pytest.mark.parametrize("which,unchanged", [
    ("kimi", False), ("kimi", True), ("resnet", False)])
def test_the_follower_on_the_host_is_follow_bit_for_bit(which, unchanged):
    """Adam on the tiny Kimi Linear, with the state left unchanged (the
    planted fault), and momentum with weight decay and batch norms'
    variances on the tiny ResNet."""
    import numpy as np
    config, cell = ((dict(tk.CONFIG), tiny.cell("sharded_trainer", 2))
                    if which == "kimi" else
                    (dict(tiny.RESNET), tiny.cell("sharded_trainer", 4)))
    plain, lean = _follow_both(config, cell, unchanged)
    assert plain["losses"] == lean["losses"]
    assert plain["grad_norms"] == lean["grad_norms"]
    assert plain["change_norms"] == lean["change_norms"]
    assert sorted(plain["grad"]) == sorted(lean["grad"])
    for k, v in plain["grad"].items():
        assert isinstance(lean["grad"][k], np.ndarray)
        assert np.array_equal(np.asarray(v), lean["grad"][k]), k
    assert sorted(plain["variances"]) == sorted(lean["variances"])
    for k, v in plain["variances"].items():
        assert np.array_equal(np.asarray(v), np.asarray(lean["variances"][k]))
    if unchanged:
        assert max(lean["change_norms"].values()) == 0.0


@pytest.mark.parametrize("which", ["kimi", "resnet"])
def test_the_loops_readings_are_the_inherited_ones(which):
    """The first gradient and the change's norms a leaf at a time against
    the inherited loop's, from the same trainer after one step; and the
    net's copy of the weights has left the device the trainer runs on."""
    import numpy as np
    import traffic
    config, cell = ((dict(tk.CONFIG), tiny.cell("sharded_trainer", 2))
                    if which == "kimi" else
                    (dict(tiny.RESNET), tiny.cell("sharded_trainer", 4)))
    mod = harness.load_file("loops", "sharded_trainer_net_on_host")
    loop = mod.Loop(cell, config, SEED, jax.devices()[:1])
    assert all(isinstance(v, np.ndarray) for v in loop.weights.values())
    feed = iter(loop.feed(traffic.cycle(traffic.make_pool(cell, config, SEED))))
    loop.fetch(loop.step(next(feed)))
    base = mod._base.Loop
    mine, theirs = loop.first_gradient(), base.first_gradient(loop)
    assert sorted(mine) == sorted(theirs)
    for k in theirs:
        if which == "kimi":       # no weight decay: one product, exact
            assert np.array_equal(mine[k], theirs[k]), k
        else:                     # XLA contracts `a*m - wd*p` into an fma
            np.testing.assert_allclose(mine[k], theirs[k], rtol=1e-6,
                                       atol=1e-9, err_msg=k)
    mine, theirs = loop.change_norms(), base.change_norms(loop)
    assert mine == theirs
    import reference_train
    import reference_train_on_host
    loop.close()
    assert reference_train.follow is reference_train_on_host.follow
