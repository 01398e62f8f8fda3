"""Every loop, driven through `harness.run_cell` on the CPU backend at a
tiny size (four virtual devices for the dp=4 loop): the rest of a run
once `run.py` has found its chip. Checks the result's keys, that the
program agrees with the plain reference in float32, and that the timed
path broken underneath comes out as not correct."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import harness
import tiny
import trace_reduce

ROOT = os.path.dirname(harness.HERE)
SEED = 3000000019
FIXTURE = os.path.join(os.path.dirname(__file__), "data", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def peak():
    return harness.load_json("peaks.json")["TPU v5 lite"]


CASES = {
    "resnet_sharded": (tiny.RESNET, dict(loop="sharded_trainer", batch=16)),
    "gpt_sharded": (tiny.GPT, dict(loop="sharded_trainer", batch=4)),
    "resnet_gluon": (tiny.RESNET, dict(loop="gluon_trainer", batch=16)),
    "resnet_dp4": (tiny.RESNET, dict(loop="sharded_trainer", batch=16,
                                     chips=4)),
}


def _run(case, bench, peak, trace=False, seconds=0.3, **kw):
    config, cell_kw = CASES[case]
    cell = tiny.cell(**cell_kw)
    devices = jax.devices()[:cell["chips"]]
    return harness.run_cell(cell, dict(config), bench, SEED, seconds, trace,
                            devices, peak, **kw)


@pytest.mark.parametrize("case", sorted(CASES))
def test_loop_runs_and_agrees_with_the_reference(case, bench, peak):
    result = _run(case, bench, peak)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    wanted = {m["name"] for m in bench["end_to_end"]
              if "workloads" not in m}
    assert wanted <= set(result["metrics"])
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["count"] == CASES[case][1].get("chips", 1)
    assert set(result["compared"]) >= set(tiny.LIMITS)
    unheld = result["compared"]["unheld"]
    assert unheld["grad_diff"] < 1e-3
    assert ("var_diff" in unheld) == (CASES[case][0] is tiny.RESNET)
    assert unheld.get("var_diff", 0) < 1e-4
    json.dumps(result)


def test_traced_run_reports_per_layer_metrics(bench, peak):
    result = _run("resnet_sharded", bench, peak, trace=True,
                  load_trace=lambda _dir: trace_reduce.load(FIXTURE))
    names = set(result["metrics"])
    assert {"host_dispatch_ms", "input_wait_ms", "compiles_in_window",
            "programs_per_step", "device_idle_pct", "step_mfu",
            "step_program_roofline", "hbm_peak_gb"} >= names
    assert "collective_exposed_ms" not in names        # one chip: nothing
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert result["device"]["busy_s"] > 0
    assert result["device"]["window_s"] > result["device"]["busy_s"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert len(result["breakdown"]["idle_gaps"]) <= 5


def test_cpu_trace_with_no_device_plane_is_refused(bench, peak):
    with pytest.raises(RuntimeError, match="no device plane"):
        _run("gpt_sharded", bench, peak, trace=True)


# -- the timed path, broken underneath -----------------------------------
_load_file = harness.load_file


def _broken_loop(name, fault):
    base = _load_file("loops", name).Loop

    class Unchanged(base):
        """A step that returns its state unchanged."""

        def step(self, staged):
            if name == "sharded_trainer":
                t = self.trainer
                keep = jax.tree.map(lambda v: jnp.array(v, copy=True),
                                    (t._params, t._aux, t._opt_state))
                loss = super().step(staged)
                t._params, t._aux, t._opt_state = keep
                return loss
            params = list(self.net.collect_params().values())
            keep = [jnp.array(p.data(self.ctx)._data, copy=True)
                    for p in params]
            loss = super().step(staged)
            for p, old in zip(params, keep):
                p.data(self.ctx)._set(old)
            return loss

    class PartOfTheBatch(base):
        """Rows left out, the mean taken over the rest: half of them, or on
        four chips all but one chip's (the exchange left out)."""

        def __init__(self, cell, config, seed, devices):
            self._keep = (int(cell["batch"]) // len(devices)
                          if fault == "no_exchange"
                          else int(cell["batch"]) // 2)
            super().__init__(cell, config, seed, devices)
            if hasattr(self, "batch"):
                self.batch = self._keep

        def feed(self, batches):
            k = self._keep
            return super().feed((x[:k], y[:k]) for x, y in batches)

    import types
    cls = Unchanged if fault == "unchanged" else PartOfTheBatch
    return types.SimpleNamespace(Loop=cls)


@pytest.mark.parametrize("case,fault", [
    ("resnet_sharded", "unchanged"), ("resnet_sharded", "half_batch"),
    ("gpt_sharded", "unchanged"), ("gpt_sharded", "half_batch"),
    ("resnet_gluon", "unchanged"), ("resnet_gluon", "half_batch"),
    ("resnet_dp4", "unchanged"), ("resnet_dp4", "half_batch"),
    ("resnet_dp4", "no_exchange"),
])
def test_broken_timed_path_is_not_correct(case, fault, bench, peak,
                                          monkeypatch):
    def load_file(kind, name):
        if kind == "loops":
            return _broken_loop(name, fault)
        return _load_file(kind, name)

    monkeypatch.setattr(harness, "load_file", load_file)
    result = _run(case, bench, peak)
    assert result["correct"] is False
    failed = [k for k, v in result["compared"].items()
              if isinstance(v, dict) and "limit" in v
              and not v["value"] <= v["limit"]]
    assert failed, result["compared"]
    if fault == "unchanged":
        # by the training bullet's measure an unmoved state reads 1
        assert result["compared"]["change_norm_gap"]["value"] == pytest.approx(1.0)
