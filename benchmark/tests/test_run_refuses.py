"""`run.py` measures nothing without the chip: a CPU backend, too few
chips or a device kind that `peaks.json` does not know end the process
with a code other than 0 and no result line."""
import json
import os
import subprocess
import sys
import types

import pytest

import harness
import run as bench_run

ROOT = os.path.dirname(harness.HERE)


def test_cpu_backend_is_refused_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         cell, "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "no accelerator" in done.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in done.stdout.splitlines())


def _fake_devices(monkeypatch, n, kind="TPU v5 lite", platform="tpu"):
    import jax
    devs = [types.SimpleNamespace(platform=platform, device_kind=kind, id=i)
            for i in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)
    return devs


def test_unknown_device_kind_is_an_error(monkeypatch):
    peaks = harness.load_json("peaks.json")
    _fake_devices(monkeypatch, 1, kind="TPU v9 imaginary")
    with pytest.raises(SystemExit) as e:
        bench_run.find_chips(1, peaks)
    assert "peaks.json" in str(e.value)


def test_too_few_chips_is_an_error(monkeypatch):
    peaks = harness.load_json("peaks.json")
    _fake_devices(monkeypatch, 1)
    with pytest.raises(SystemExit) as e:
        bench_run.find_chips(4, peaks)
    assert "4 chips" in str(e.value)


def test_known_chip_is_taken(monkeypatch):
    peaks = harness.load_json("peaks.json")
    devs = _fake_devices(monkeypatch, 4)
    got, peak = bench_run.find_chips(1, peaks)
    assert got == devs[:1] and peak["bf16_flops_per_s"] == 1.97e14
    assert peak["hbm_bytes_per_s"] == 8.19e11
    assert "cpu" not in peaks and not os.environ.get("MXTPU_PEAK_FLOPS")
