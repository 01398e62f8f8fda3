"""A tiny cell with `--trace` on the CPU backend through the readers of
PR 28, by way of `load_trace`: the host metrics read the ring that the
rehearsed loop filled; the device metrics read the recorded v5e trace of
a scoped program with the map that goes with it (the CPU backend's own
programs have other instruction names)."""
import json
import os

import jax
import pytest

import harness
import program_trace
import tiny
import trace_reduce

ROOT = os.path.dirname(harness.HERE)
DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 3000000019
NEW = {"fwd_device_ms", "bwd_device_ms", "optimizer_device_ms",
       "unscoped_device_pct", "idle_owned_pct", "step_prepare_ms",
       "step_launch_ms", "frontend_host_ms", "input_stage_ms"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture()
def recorded_table(monkeypatch):
    with open(os.path.join(DATA, "scoped_v5e.json")) as f:
        fx = json.load(f)
    monkeypatch.setattr(program_trace, "collect_programs",
                        lambda: (fx["owners"], fx["snapshot"]))


@pytest.mark.parametrize("loop,cell_name", [
    ("sharded_trainer", "resnet50_train_b128"),
    ("gluon_trainer", "resnet50_gluon_b64")])
def test_traced_rehearsal_reports_the_program_metrics(
        loop, cell_name, bench, recorded_table):
    cell = tiny.cell(loop=loop, batch=16)
    cell["name"] = cell_name          # the new metrics list their cells
    peak = harness.load_json("peaks.json")["TPU v5 lite"]
    result = harness.run_cell(
        cell, dict(tiny.RESNET), bench, SEED, 0.3, True, jax.devices()[:1],
        peak, load_trace=lambda _dir: trace_reduce.load(
            os.path.join(DATA, "scoped_v5e.xplane.pb")))
    assert result["correct"] is True, result["compared"]
    got = {k: v["value"] for k, v in result["metrics"].items() if k in NEW}
    wanted = {m["name"] for m in bench["per_layer"]
              if m["name"] in NEW and cell_name in m["workloads"]}
    assert set(got) == wanted
    assert ("frontend_host_ms" in got) == (loop == "gluon_trainer")
    assert all(v > 0 for k, v in got.items() if k != "unscoped_device_pct")
    busy = 1e3 * result["device"]["busy_s"] / cell["trace_steps"]
    assert (got["fwd_device_ms"] + got["bwd_device_ms"]
            + got["optimizer_device_ms"]) < busy
    assert 0 < got["unscoped_device_pct"] < 100
    assert 0 < got["idle_owned_pct"] <= 100
    json.dumps(result)


def test_new_entries_are_appended_with_their_cells(bench):
    names = [m["name"] for m in bench["per_layer"]]
    assert set(names[-len(NEW):]) == NEW
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"][-len(NEW):]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert set(m["workloads"]) <= cells and m["workloads"]
        assert os.path.isfile(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))
