"""Fused, donated optimizer step (parallel/fused_update.py).

The contract under test (docs/performance.md "Fused weight update"):

1. bit-parity: the fused path produces byte-identical weights AND
   optimizer states vs the per-parameter path, for SGD/momentum, Adam,
   RMSProp (both modes), AdaGrad, across mixed dtypes, lr_mult/wd_mult
   per-param scaling, and multi-precision (fp32 master for fp16);
2. dispatch count: O(n_groups) fused update dispatches per step, not
   O(n_params) — asserted via the optimizer.update.dispatches counter;
3. donation: the fused jits alias inputs to outputs (no new
   weight/state buffers), asserted via compiled-HLO introspection and
   live-array accounting on CPU;
4. ignore_stale_grad, save/load_states round-trips through fused
   steps, and the kvstore updater path all stay exact.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu.observability import registry as obs
from mxnet_tpu.parallel import fused_update as fu


@pytest.fixture
def fused_env(tmp_path):
    """The updater under test or its reference, `updater_for(o,
    fused)`: `optimizer.get_updater(o)` (grouped) against the base
    class `optimizer.Updater(o)` (per-key), + a COLD per-test XLA
    compilation cache. The session conftest latches the shared
    ``$TMPDIR/mxtpu_xla_cache_<uid>`` dir for the whole process; a
    rerun against that warm cache serves executables from disk instead
    of compiling, so compile-count/donation/dispatch expectations that
    held on the first (cold) run could nondeterministically flip on
    the second. Pointing ``jax_compilation_cache_dir`` at a fresh
    tmp_path makes every parity test compile from scratch regardless
    of what earlier sessions left in the shared cache."""
    import jax
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))

    def updater_for(o, fused):
        return opt.get_updater(o) if fused else opt.Updater(o)
    yield updater_for
    jax.config.update("jax_compilation_cache_dir", prev)


SHAPES = [(5, 3), (7,), (4, 4), (2, 2, 2), (11,)]


def _make_params(dtype="float32", seed=0):
    rng = np.random.RandomState(seed)
    return [mx.nd.array(rng.randn(*s).astype(dtype)) for s in SHAPES]


def _make_grads(step, dtype="float32"):
    rng = np.random.RandomState(100 + step)
    return [mx.nd.array((rng.randn(*s) * 0.1).astype(dtype))
            for s in SHAPES]


def _run(optname, optkw, fused, updater_for, steps=4, dtype="float32",
         mp=False, lr_mult=None, wd_mult=None):
    ws = _make_params(dtype)
    o = opt.create(optname, **optkw)
    if mp:
        o.multi_precision = True
    if lr_mult:
        o.lr_mult = dict(lr_mult)
    if wd_mult:
        o.wd_mult = dict(wd_mult)
    upd = updater_for(o, fused)
    for step in range(steps):
        gs = _make_grads(step, dtype)
        upd.update_all(list(range(len(ws))), gs, ws)
    return ws, upd


def _state_arrays(state):
    if state is None:
        return []
    if isinstance(state, mx.nd.NDArray):
        return [state.asnumpy()]
    out = []
    for s in state:
        out.extend(_state_arrays(s))
    return out


def _assert_bitwise(ws_a, upd_a, ws_b, upd_b, ulps=0):
    """Equal bit for bit; `ulps` > 0 allows that many ulps of each
    array's largest element instead."""
    def same(x, y):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        if not ulps:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(
                x, y, rtol=0,
                atol=ulps * np.finfo(np.float32).eps * np.abs(y).max())

    for a, b in zip(ws_a, ws_b):
        assert a.dtype == b.dtype
        same(a.asnumpy(), b.asnumpy())
    for i in upd_a.states:
        sa = _state_arrays(upd_a.states[i])
        sb = _state_arrays(upd_b.states[i])
        assert len(sa) == len(sb)
        for x, y in zip(sa, sb):
            same(x, y)


_STEPS = 4


@pytest.mark.parametrize("name,kw", [
    ("sgd", dict(learning_rate=0.1)),
    ("sgd", dict(learning_rate=0.1, momentum=0.9)),
    ("sgd", dict(learning_rate=0.1, momentum=0.9, wd=0.01,
                 clip_gradient=0.5)),
    ("adam", dict(learning_rate=0.01)),
    ("adam", dict(learning_rate=0.01, wd=0.001, clip_gradient=1.0)),
    ("rmsprop", dict(learning_rate=0.01)),
    ("rmsprop", dict(learning_rate=0.01, centered=True,
                     clip_weights=2.0)),
    ("adagrad", dict(learning_rate=0.1, wd=0.01)),
])
def test_fused_bit_parity(name, kw, fused_env):
    a_w, a_u = _run(name, kw, True, fused_env, steps=_STEPS)
    b_w, b_u = _run(name, kw, False, fused_env, steps=_STEPS)
    # centered RMSProp alone is held to an ulp a step, not to the bit:
    # its `lr * g / sqrt(n - gm**2 + eps)` reaches XLA:CPU as a product
    # with an APPROXIMATE rsqrt whose rounding follows the shape of the
    # loop (optimizer.py, above `_adagrad_math`): the per-key kernel's
    # (4, 4) nest reads one element of `delta` an ulp off the same
    # element in the group's flat buffer. The fused kernel IS
    # optimizer._rmsprop_math; the other seven cases agree to the bit
    _assert_bitwise(a_w, a_u, b_w, b_u,
                    ulps=_STEPS if kw.get("centered") else 0)


@pytest.mark.parametrize("name,kw", [
    ("sgd", dict(learning_rate=0.1, momentum=0.9)),
    ("adam", dict(learning_rate=0.01)),
])
def test_fused_bit_parity_float16(name, kw, fused_env):
    a_w, a_u = _run(name, kw, True, fused_env, dtype="float16")
    b_w, b_u = _run(name, kw, False, fused_env, dtype="float16")
    _assert_bitwise(a_w, a_u, b_w, b_u)


def test_fused_lr_wd_mult_lanes(fused_env):
    """Per-param lr_mult/wd_mult values split groups but stay exact."""
    mults = dict(lr_mult={1: 0.5, 3: 2.0}, wd_mult={2: 0.0})
    a_w, a_u = _run("sgd", dict(learning_rate=0.1, momentum=0.9, wd=0.01),
                    True, fused_env, **mults)
    b_w, b_u = _run("sgd", dict(learning_rate=0.1, momentum=0.9, wd=0.01),
                    False, fused_env, **mults)
    _assert_bitwise(a_w, a_u, b_w, b_u)


def test_fused_multi_precision_master_stays_fp32(fused_env):
    """fp16 params under multi_precision: the fused pack/unpack must
    keep the fp32 master weights and fp32 states (the regression the
    Updater.sync_state_context satellite guards)."""
    a_w, a_u = _run("sgd", dict(learning_rate=0.1, momentum=0.9), True,
                    fused_env, dtype="float16", mp=True)
    b_w, b_u = _run("sgd", dict(learning_rate=0.1, momentum=0.9), False,
                    fused_env, dtype="float16", mp=True)
    _assert_bitwise(a_w, a_u, b_w, b_u)
    for i, state in a_u.states.items():
        master, mom = state
        assert master._data.dtype == np.float32
        assert mom._data.dtype == np.float32
        assert a_w[i].dtype == np.float16


def test_mixed_dtypes_group_separately_and_match(fused_env):
    """One update_all over fp32 + fp16 params: two groups, exact."""
    def run(fused):
        rng = np.random.RandomState(3)
        ws = [mx.nd.array(rng.randn(4, 4).astype("float32")),
              mx.nd.array(rng.randn(6,).astype("float32")),
              mx.nd.array(rng.randn(3, 3).astype("float16")),
              mx.nd.array(rng.randn(5,).astype("float16"))]
        upd = fused_env(opt.create("sgd", learning_rate=0.1,
                                   momentum=0.9), fused)
        for step in range(3):
            g = np.random.RandomState(50 + step)
            gs = [mx.nd.array((g.randn(*w.shape) * 0.1).astype(
                str(w.dtype.name if hasattr(w.dtype, "name") else w.dtype)))
                for w in ws]
            upd.update_all(list(range(len(ws))), gs, ws)
        return ws, upd

    a_w, a_u = run(True)
    b_w, b_u = run(False)
    _assert_bitwise(a_w, a_u, b_w, b_u)


def test_dispatch_count_drops_to_group_count(fused_env):
    """The telemetry counter shows O(n_groups), not O(n_params)."""
    disp = obs.REGISTRY.get("optimizer.update.dispatches")
    groups = obs.REGISTRY.get("optimizer.fused.groups")

    ws = _make_params()
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    gs = _make_grads(0)
    d0, g0 = disp.total(), groups.total()
    fused_env(o, True).update_all(list(range(len(ws))), gs, ws)
    assert disp.total() - d0 == 1          # one group: one dispatch
    assert groups.total() - g0 == 1

    d0 = disp.total()
    fused_env(o, False).update_all(list(range(len(ws))), _make_grads(1),
                                   ws)
    assert disp.total() - d0 == len(ws)    # per-key: one per param
    assert groups.total() - g0 == 1


def test_unsupported_optimizer_falls_back_per_key(fused_env):
    disp = obs.REGISTRY.get("optimizer.update.dispatches")
    ws = _make_params()
    upd = opt.get_updater(opt.create("nag", learning_rate=0.05,
                                     momentum=0.9))
    d0 = disp.total()
    upd.update_all(list(range(len(ws))), _make_grads(0), ws)
    assert disp.total() - d0 == len(ws)


def test_fused_jit_donates_buffers(fused_env):
    """Compiled-HLO introspection: the fused update aliases its weight
    and state inputs to outputs — no new buffers per step."""
    import jax.numpy as jnp
    spec = fu._SUPPORTED[opt.SGD]
    o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
    jfn = fu._jit_for(spec)
    w = jnp.ones((32,)); g = jnp.ones((32,)); m = jnp.zeros((32,))
    lowered = jfn.lower(w, g, (m,), 0.1, 1, 0.0, spec.hyper(o))
    assert "input_output_alias" in lowered.compile().as_text()


def test_donation_consumes_packed_inputs(fused_env):
    """Live-array accounting on CPU: fused steps donate their packed
    weight and state flats, so steady-state steps add no buffers — the
    update ran in place."""
    import jax
    rng = np.random.RandomState(0)
    # two 1-D params in one group: pack concatenates, so originals
    # survive; run enough steps that steady state is reached, then
    # check live-array count stability (no per-step buffer growth)
    ws = [mx.nd.array(rng.randn(64).astype("float32")),
          mx.nd.array(rng.randn(32).astype("float32"))]
    upd = opt.get_updater(opt.create("sgd", learning_rate=0.1,
                                     momentum=0.9))
    gs = [mx.nd.array(rng.randn(64).astype("float32")),
          mx.nd.array(rng.randn(32).astype("float32"))]
    from mxnet_tpu.resilience import numerics
    upd.update_all([0, 1], gs, ws)
    numerics.drain_flags()   # resolve the guard's ok flag, as a real
    # training loop's step boundary does — otherwise the pending 0-d
    # verdicts count as live arrays here
    jax.block_until_ready([w._data for w in ws])
    n0 = len(jax.live_arrays())
    for _ in range(3):
        upd.update_all([0, 1], gs, ws)
        numerics.drain_flags()
        jax.block_until_ready([w._data for w in ws])
    assert len(jax.live_arrays()) <= n0 + 2  # no unbounded buffer growth


def _stale_test_params(seed=7):
    from mxnet_tpu.gluon import Parameter
    rng = np.random.RandomState(seed)
    params = []
    for i, s in enumerate([(4, 3), (5,)]):
        p = Parameter("p%d_weight" % i, shape=s)
        p.initialize(init="zeros")
        p.set_data(mx.nd.array(rng.randn(*s).astype("float32")))
        params.append(p)
    return params


def _backward_through(params):
    """A real backward over exactly these params (sets _fresh_grad)."""
    from mxnet_tpu import autograd
    with autograd.record():
        loss = sum((p.data() * p.data()).sum() for p in params)
    loss.backward()


def test_ignore_stale_grad_parity(fused_env):
    """An update with ignore_stale_grad=True skips params whose grad
    was not refreshed by a backward since the last update — identically
    through `Trainer.step` (the one-program step) and through
    `allreduce_grads()` + `update()` (the staged grouped update)."""
    def run(fused):
        params = _stale_test_params()
        tr = mx.gluon.Trainer(params, "sgd",
                              {"learning_rate": 0.1, "momentum": 0.9})

        def update():
            if fused:
                tr.step(1, ignore_stale_grad=True)
            else:
                tr.allreduce_grads()
                tr.update(1, ignore_stale_grad=True)

        _backward_through(params)
        update()
        snap1 = [p.data().asnumpy().copy() for p in params]
        # no new backward: a second stale step must be a no-op
        update()
        snap2 = [p.data().asnumpy() for p in params]
        for a, b in zip(snap1, snap2):
            np.testing.assert_array_equal(a, b)
        # refresh ONE param's grad: only that one moves
        _backward_through(params[:1])
        update()
        return [p.data().asnumpy() for p in params]

    a = run(True)
    b = run(False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_ignore_stale_grad_skips_never_backwarded(fused_env):
    """A param no backward ever touched must not move (wd/momentum on a
    zero grad would silently drift it), and zero_grad() must NOT count
    as a refresh — the reference's _fresh_grad contract."""
    params = _stale_test_params()
    tr = mx.gluon.Trainer(params, "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9,
                           "wd": 0.1})
    before = [p.data().asnumpy().copy() for p in params]
    tr.step(1, ignore_stale_grad=True)   # no backward at all: no-op
    for p, b in zip(params, before):
        np.testing.assert_array_equal(p.data().asnumpy(), b)
    _backward_through(params[:1])        # p0 fresh, p1 still never
    tr.step(1, ignore_stale_grad=True)
    assert not np.array_equal(params[0].data().asnumpy(), before[0])
    np.testing.assert_array_equal(params[1].data().asnumpy(), before[1])
    moved = params[0].data().asnumpy().copy()
    params[0].zero_grad()                # zeroing is not a refresh
    tr.step(1, ignore_stale_grad=True)
    np.testing.assert_array_equal(params[0].data().asnumpy(), moved)


def test_multi_precision_flag_on_fp32_weights_consistent(fused_env):
    """multi_precision=True on fp32 weights (no master pair exists):
    BOTH paths must take the plain update branch and agree bitwise —
    the per-key path used to misread Adam's (mean, var) as
    (master, base) and crash."""
    results = []
    for fused in (True, False):
        ws = _make_params()
        o = opt.create("adam", learning_rate=0.01)
        o.multi_precision = True
        upd = fused_env(o, fused)
        for step in range(3):
            upd.update_all(list(range(len(ws))), _make_grads(step), ws)
        results.append((ws, upd))
    _assert_bitwise(*results[0], *results[1])


def test_save_load_states_roundtrip_through_fused_step(fused_env):
    """get_states/set_states mid-run: the resumed updater continues
    bit-identically to the uninterrupted one."""
    ws_a = _make_params()
    ws_b = _make_params()
    u_a = opt.get_updater(opt.create("adam", learning_rate=0.01))
    for step in range(2):
        u_a.update_all(list(range(len(ws_a))), _make_grads(step), ws_a)
    blob = u_a.get_states(dump_optimizer=True)

    u_b = opt.get_updater(opt.create("adam", learning_rate=0.01))
    for step in range(2):
        u_b.update_all(list(range(len(ws_b))), _make_grads(step), ws_b)
    u_b.set_states(blob)
    # weights continue from the same values (states came from u_a;
    # both weight sets saw identical updates so they are equal here)
    for step in range(2, 4):
        u_a.update_all(list(range(len(ws_a))), _make_grads(step), ws_a)
        u_b.update_all(list(range(len(ws_b))), _make_grads(step), ws_b)
    _assert_bitwise(ws_a, u_a, ws_b, u_b)


def test_kvstore_updater_path_fused_parity(fused_env):
    """update-on-kvstore: push_all lands the whole batch through ONE
    fused update, bit-identical to the per-key store."""
    disp = obs.REGISTRY.get("optimizer.update.dispatches")

    def run(fused):
        rng = np.random.RandomState(11)
        kv = mx.kv.create("device")
        o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
        if fused:
            kv.set_optimizer(o)
        else:
            kv.set_updater(opt.Updater(o))
        keys = list(range(len(SHAPES)))
        for k, s in zip(keys, SHAPES):
            kv.init(k, mx.nd.array(rng.randn(*s).astype("float32")))
        d0 = disp.total()
        for step in range(3):
            kv.push_all(keys, _make_grads(step),
                        priorities=[-k for k in keys])
        return [kv._data[k].asnumpy() for k in keys], disp.total() - d0

    a, da = run(True)
    b, db = run(False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert da == 3          # one fused group per push
    assert db == 3 * len(SHAPES)


def test_kvstore_push_duplicate_keys_updates_twice(fused_env):
    """Repeated keys in one push keep per-key semantics (two sequential
    optimizer steps) — the batched-update scope must not collapse them."""
    kv = mx.kv.create("device")
    kv.set_optimizer(opt.create("sgd", learning_rate=0.1, momentum=0.9))
    kv.init("w", mx.nd.array(np.ones(4, np.float32)))
    g = mx.nd.array(np.full(4, 1.0, np.float32))
    kv.push(["w", "w"], [g, g])
    # two momentum steps: m=-0.1, w=0.9; m=0.9*-0.1-0.1=-0.19, w=0.71
    np.testing.assert_allclose(kv._data["w"].asnumpy(),
                               np.full(4, 0.71), rtol=1e-6)


def test_scheduler_skewed_counts_parity(fused_env):
    """lr_scheduler + skewed update counts: two same-t params can
    resolve different lr mid-collection (the scheduler reads the global
    num_update a higher-count param just bumped); the fused cohorts
    must honor each resolved lr exactly like the per-key path."""
    def run(fused):
        ws = _make_params()
        o = opt.create("sgd", learning_rate=0.5, momentum=0.9,
                       lr_scheduler=mx.lr_scheduler.FactorScheduler(
                           step=2, factor=0.5, base_lr=0.5))
        upd = fused_env(o, fused)
        # skew: param 1 advances three steps alone (per-key: len<2)
        for step in range(3):
            upd.update_all([1], [_make_grads(step)[1]], [ws[1]])
        # now a full update_all: params 0 and 2 share t but straddle
        # param 1's num_update bump in caller order
        for step in range(3, 6):
            upd.update_all(list(range(len(ws))), _make_grads(step), ws)
        return ws, upd

    a_w, a_u = run(True)
    b_w, b_u = run(False)
    _assert_bitwise(a_w, a_u, b_w, b_u)


def test_steptimer_records_fused_fields(fused_env):
    from mxnet_tpu.observability.telemetry import StepTimer
    timer = StepTimer("test.fused")
    timer.begin_step()
    ws = _make_params()
    upd = opt.get_updater(opt.create("sgd", learning_rate=0.1,
                                     momentum=0.9))
    upd.update_all(list(range(len(ws))), _make_grads(0), ws)
    rec = timer.end_step(batch_size=4)
    assert rec["update_dispatches"] == 1
    assert rec["fused_groups"] == 1
    assert rec.get("fused_pack_seconds", 0) > 0


def test_telemetry_report_optimizer_section(tmp_path):
    from tools import telemetry_report as tr
    records = [{"step_time": 0.1, "optimizer_time": 0.02,
                "update_dispatches": 2, "fused_groups": 2,
                "fused_pack_seconds": 0.001,
                "fused_update_seconds": 0.004, "batch_size": 8}
               for _ in range(4)]
    s = tr.summarize(records)
    assert s["update_dispatches"] == 8
    assert s["update_dispatches_per_step"] == 2.0
    assert s["fused_groups"] == 8
    assert s["optimizer_p50_s"] == pytest.approx(0.02)
    text = tr.format_summary(s)
    assert "optimizer" in text and "dispatches" in text
    # CI gate behavior unchanged: malformed input still exits non-zero
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"step_time": 0.1}\nnot json\n')
    assert tr.main([str(bad)]) == 1
    good = tmp_path / "good.jsonl"
    good.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    assert tr.main(["--json", str(good)]) == 0


def test_update_cost_accounting():
    """MFU accounting helper: fused update FLOPs/bytes per optimizer."""
    from mxnet_tpu.parallel import update_cost
    n = 1000
    sgd = update_cost(opt.create("sgd", momentum=0.9), n, 4)
    plain = update_cost(opt.create("sgd"), n, 4)
    adam = update_cost(opt.create("adam"), n, 4)
    assert sgd["bytes"] == 5 * n * 4 and sgd["flops"] == 5 * n
    assert plain["bytes"] < sgd["bytes"] < adam["bytes"]
    assert adam["flops"] > sgd["flops"]
    assert update_cost(opt.create("nag"), n, 4) is None


def test_fused_layout_plans_are_reused(fused_env):
    """Steady-state steps reuse the memoized layout plan (the PR-3
    GradBucketer invariant carried over to the update path)."""
    ws = _make_params()
    upd = opt.get_updater(opt.create("sgd", learning_rate=0.1,
                                     momentum=0.9))
    for step in range(3):
        upd.update_all(list(range(len(ws))), _make_grads(step), ws)
    assert len(upd._layout._plans) == 1
