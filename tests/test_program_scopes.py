"""Owners for device time and for host time on the training path
(docs/observability.md "Step spans", "Program table"): the scopes that
reach the compiled step's text, the program table that keeps them, and
the step spans in the trace ring — a tiny conv + batch-norm + dense net
through `ShardedTrainer.step` and through `gluon.Trainer`."""
import contextlib

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.compile import programs
from mxnet_tpu.observability import trace
from mxnet_tpu.parallel import ShardedTrainer, make_mesh

BATCH = 8
_RNG = np.random.RandomState(11)
X = _RNG.rand(BATCH, 8, 8, 3).astype("float32")
Y = _RNG.randint(0, 5, (BATCH,)).astype("float32")

# what each trainer's iteration must leave in the ring, by span name
VOCABULARY = {
    "sharded": {"step", "input.wait", "step.prepare", "step.launch",
                "step.finish", "fence"},
    "gluon": {"step", "input.stage", "frontend.forward",
              "frontend.backward", "step.prepare", "step.launch",
              "step.finish", "fence"},
}
# (program, what its owners must name) for each trainer's step
PROGRAMS = {
    "sharded": [("jit_sharded_step", "jvp(mx.Convolution."),
                ("jit_sharded_step", "transpose(jvp(mx.Convolution."),
                ("jit_sharded_step", "jvp(mx.BatchNorm."),
                ("jit_sharded_step", "transpose(jvp(mx.FullyConnected."),
                ("jit_sharded_step", "/mx.optimizer/")],
    # the recorded forward runs the graph under `jax.checkpoint`, whose
    # backward names its operations `transpose(jvp(jvp()))/checkpoint/...`
    "gluon": [("jit_cachedop_fwd_", "jvp(mx.Convolution."),
              ("jit_cachedop_bwd_",
               "transpose(jvp(jvp()))/checkpoint/mx.Convolution."),
              ("jit_cachedop_bwd_",
               "transpose(jvp(jvp()))/checkpoint/mx.FullyConnected."),
              ("jit_fused_step_sgd", "/mx.optimizer/")],
}


def _net():
    mx.random.seed(5)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, layout="NHWC", in_channels=3),
            gluon.nn.BatchNorm(axis=3, in_channels=4),
            gluon.nn.Activation("relu"), gluon.nn.Flatten(),
            gluon.nn.Dense(5, in_units=144))
    net.initialize(mx.init.Xavier())
    return net


def _train(kind, steps=3, net=None):
    """`steps` iterations; returns the losses as float32 bits."""
    net = net or _net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    hp = {"learning_rate": 0.1, "momentum": 0.9}
    losses = []
    if kind == "sharded":
        trainer = ShardedTrainer(net, loss_fn, "sgd", hp,
                                 mesh=make_mesh({"dp": 1},
                                                jax.devices()[:1]))
        feed = trainer.prefetched(((X, Y) for _ in range(steps)), depth=2)
        for staged in feed:
            losses.append(trainer.step(*staged).asnumpy())
        feed.close()
    else:
        net.hybridize()
        trainer = gluon.Trainer(net.collect_params(), "sgd", hp)
        for _ in range(steps):
            x, y = mx.nd.array(X), mx.nd.array(Y)
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(BATCH)
            losses.append(loss.mean().asnumpy())
    return [np.asarray(v, np.float32).tobytes() for v in losses]


@pytest.fixture(scope="module")
def runs():
    """Each trainer driven once with tracing in its default state: its
    losses, the ring's spans and the program table before and after."""
    out = {}
    for kind in ("sharded", "gluon"):
        trace.reset_ring()
        before = programs.snapshot()
        losses = _train(kind)
        out[kind] = {"losses": losses, "spans": trace.ring_spans(),
                     "before": before, "after": programs.snapshot()}
        trace.detach()
    trace.reset_ring()
    return out


def _owners(prefix):
    found = {}
    for name in programs.snapshot():
        if name.startswith(prefix):
            found.update({k: v for k, v in programs.owners(name).items()
                          if v})
    return found


@pytest.mark.parametrize("kind,program,scope", [
    (k, p, s) for k, rows in PROGRAMS.items() for p, s in rows])
def test_compiled_step_text_carries_the_scopes(runs, kind, program, scope):
    owners = _owners(program)
    assert owners, "no program named %s* in the table" % program
    assert any(scope in op for op in owners.values()), (
        scope, sorted(set(owners.values()))[:20])


@pytest.mark.parametrize("kind", ["sharded", "gluon"])
def test_losses_bit_equal_with_and_without_scopes(runs, kind, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert _train(kind) == runs[kind]["losses"]


@pytest.fixture()
def own_cache(tmp_path):
    """A persistent cache of this test's own: whatever directory earlier
    tests of the process left jax with, a first build here misses and a
    second one hits."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    cc.reset_cache()


@pytest.mark.parametrize("kind,program", [
    ("sharded", "jit_sharded_step"), ("gluon", "jit_fused_step_sgd")])
def test_table_lists_the_step_program_and_counts_a_cache_hit(
        runs, kind, program, own_cache):
    first = runs[kind]["after"][program]
    was = runs[kind]["before"].get(program, {"builds": 0})
    assert first["builds"] > was["builds"]
    assert first["instructions"] > 0 and first["scoped"] is True
    assert first["seconds"] > 0
    net = _net()      # a program's key holds its parameters' names
    _train(kind, 1, net)             # built into the empty cache: a miss
    missed = programs.snapshot()[program]
    assert missed["cache_misses"] > first["cache_misses"]
    _train(kind, 1, net)             # the same program, built once more
    again = programs.snapshot()[program]
    assert again["builds"] == missed["builds"] + 1
    assert again["cache_hits"] == missed["cache_hits"] + 1
    assert mx.observability.REGISTRY.get("compile.programs").get(
        name=program, outcome="hit") >= 1


@pytest.mark.parametrize("kind,program", [
    ("sharded", "jit_sharded_step"), ("gluon", "jit_fused_step_sgd")])
def test_compile_span_lands_under_the_step_that_built_it(runs, kind,
                                                         program):
    spans = runs[kind]["spans"]
    built = [s for s in spans if s["name"] == "compile"
             and s.get("program") == program]
    assert built, sorted({s.get("program") for s in spans
                          if s["name"] == "compile"})
    roots = {s["trace_id"]: s for s in spans
             if s["name"] == "step" and s["parent_id"] is None}
    root = roots[built[0]["trace_id"]]
    assert root["step"] == 0
    launch = next(s for s in spans if s["span_id"] == built[0]["parent_id"])
    assert launch["name"] == "step.launch"


@pytest.mark.parametrize("kind", ["sharded", "gluon"])
def test_one_iteration_leaves_the_vocabulary_under_one_trace_id(runs, kind):
    spans = runs[kind]["spans"]
    roots = [s for s in spans
             if s["name"] == "step" and s["parent_id"] is None]
    assert [r["step"] for r in roots] == [0, 1, 2]
    root = roots[1]                     # a whole iteration, not the first
    mine = [s for s in spans if s["trace_id"] == root["trace_id"]]
    assert {s["name"] for s in mine} >= VOCABULARY[kind]
    by_id = {s["span_id"]: s for s in mine}
    for s in mine:
        assert isinstance(s["t0"], float)
        assert s["ts"] == pytest.approx(root["ts"] + s["t0"] - root["t0"])
        if s is root:
            continue
        assert s["parent_id"] in by_id
        assert root["t0"] <= s["t0"]
        assert s["t0"] + s["step_time"] <= root["t0"] + root["step_time"]
    # the three step spans follow one another inside the root
    order = [next(s for s in mine if s["name"] == n)
             for n in ("step.prepare", "step.launch", "step.finish")]
    assert all(s["parent_id"] == root["span_id"] for s in order)
    assert order[0]["t0"] < order[1]["t0"] < order[2]["t0"]
    if kind == "sharded":
        # the staging thread's spans have a context of their own
        staged = [s for s in spans if s["name"] == "input.stage"]
        assert [s["batch"] for s in staged] == [0, 1, 2]
        assert all(s["tid"] != root["tid"] and s["parent_id"] is None
                   for s in staged)
        assert sum(1 for s in mine if s["tid"] == root["tid"]) <= 8


@pytest.mark.parametrize("kind", ["sharded", "gluon"])
def test_trace_off_leaves_the_ring_empty(kind, monkeypatch):
    monkeypatch.setenv("MXTPU_TRACE", "0")
    trace.reset_ring()
    _train(kind, steps=2)
    assert trace.ring_spans() == []
    assert trace.current() is None


def test_missing_hook_gives_an_empty_table_and_no_exception(
        monkeypatch, capsys):
    from jax._src import compiler
    monkeypatch.setitem(programs._state, "installed", False)
    monkeypatch.setitem(programs._state, "warned", False)
    monkeypatch.setattr(programs, "_table", {})
    monkeypatch.delattr(compiler, "compile_or_get_cached")
    assert programs.install() is False
    assert programs.snapshot() == {}
    assert programs.owners("jit_sharded_step") is None
    assert "program table" in capsys.readouterr().err


def test_a_program_traced_with_no_scope_is_counted_and_not_read():
    def plain_program_of_this_test(x):
        return x * 3 + 1

    jax.jit(plain_program_of_this_test)(np.ones(3, np.float32))
    row = programs.snapshot()["jit_plain_program_of_this_test"]
    assert row["builds"] == 1 and row["seconds"] > 0
    assert row["instructions"] == 0 and row["scoped"] is False
    assert programs.owners("jit_plain_program_of_this_test") == {}


def test_parse_owners_keeps_top_level_instructions_only():
    text = '''HloModule jit_f

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.0 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/mx.optimizer/mul"}
}

ENTRY %main.1 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy.2 = f32[4]{0} copy(%x.1)
  ROOT %multiply_fusion = f32[4]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/mx.optimizer/mul" stack_frame_id=2}
}
'''
    assert programs.parse_owners(text) == {
        "x.1": "x", "multiply_fusion": "jit(f)/mx.optimizer/mul"}
    # the same text printed without the sigils
    assert programs.parse_owners(text.replace("%", "")) == \
        programs.parse_owners(text)


def test_debugz_has_a_programs_section(runs):
    from mxnet_tpu.observability import httpz
    section = httpz.debug_snapshot()["programs"]
    assert section["jit_sharded_step"]["scoped"] is True
    assert set(section["jit_sharded_step"]) >= {
        "builds", "cache_hits", "cache_misses", "seconds", "instructions"}


@pytest.mark.parametrize("kind", ["sharded", "gluon"])
def test_roots_carry_the_os_account_and_the_launch_its_leaves(runs, kind):
    spans = runs[kind]["spans"]
    roots = [s for s in spans
             if s["name"] == "step" and s["parent_id"] is None]
    for root in roots:
        assert {"cpu_ms", "nvcsw", "nivcsw", "majflt", "minflt", "gc0",
                "gc0_ms"} <= set(root)
    launches = [s for s in spans if s["name"] == "step.launch"]
    if kind == "sharded":
        # conv, batch norm and dense: 6 parameters, 2 running stats and
        # 6 momenta in and out; in, the batch and its labels; out, the
        # loss and the guard's flag
        assert [(s["leaves_in"], s["leaves_out"]) for s in launches] \
            == [(16, 16)] * 3
    else:
        assert not any("leaves_in" in s for s in launches)


def test_the_guards_read_is_a_fence_and_the_loss_fetch_the_last(runs):
    """The harness lays the spans on a device trace where the last fence
    ends: that has to stay the fetch of the loss, after the guard's."""
    spans = runs["gluon"]["spans"]
    guard = {s["span_id"] for s in spans if s["name"] == "numerics"}
    fences = sorted((s for s in spans if s["name"] == "fence"),
                    key=lambda s: s["t0"] + s["step_time"])
    under_guard = [s for s in fences if s["parent_id"] in guard]
    assert len(under_guard) == len(guard) == 3
    assert fences[-1]["parent_id"] not in guard
    assert fences[-1]["t0"] > under_guard[-1]["t0"]
