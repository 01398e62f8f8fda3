"""bench.py must stay runnable: the driver executes it on real hardware
at round end, so a CPU smoke run with tiny shapes gates bitrot."""
import json
import os
import subprocess
import sys

import pytest


def _bench(timeout, **overrides):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(MXTPU_BENCH_PLATFORM="cpu", MXTPU_BENCH_BATCH="8",
               MXTPU_BENCH_IMG="32", MXTPU_BENCH_STEPS="2",
               MXTPU_BENCH_SCORE_BATCH="4", MXTPU_BENCH_UNROLL="1",
               MXTPU_BENCH_EXTRA_STEPS="2",
               MXTPU_BENCH_INCEPTION_BATCH="8",
               MXTPU_BENCH_ALEX_BATCH="8",
               # never let the in-bench budget skip extras: this test
               # asserts their presence, so skipping must be a failure
               MXTPU_BENCH_BUDGET_S="100000")
    env.update(overrides)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, os.path.join(root, "bench.py")],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    out = json.loads(line)
    assert out["metric"].startswith("resnet50_v1_train_throughput")
    assert out["value"] > 0 and out["unit"] == "img/s"
    # what the number was measured on, as jax reports it
    assert (out["platform"], out["device_kind"]) == ("cpu", "cpu")
    assert out["device_count"] >= 1
    return out


def test_bench_smoke_cpu_single_measurement():
    """One in-process measurement, no ladder, no secondary rows: the
    cheap gate that stays in the tier-1 run."""
    _bench(600, MXTPU_BENCH_LADDER="0", MXTPU_BENCH_SCORE="0",
           MXTPU_BENCH_EXTRAS="0")


@pytest.mark.slow
def test_bench_smoke_cpu():
    """Ladder mode (the default path) runs the measurement in FOUR
    fresh-interpreter rungs (secure/score/mid/full), each a ResNet-50
    compile on the CPU, plus the secondary rows: ten minutes."""
    out = _bench(3000)
    assert "score_b4_img_s" in out["extra"]
    # the BASELINE.md secondary rows ride along (errors would be
    # reported under *_error keys — fail loudly here instead)
    for key in ("inception_v3_train_b8_img_s", "alexnet_train_b8_img_s",
                "int8_resnet50_score_b4_img_s"):
        assert key in out["extra"], out["extra"]
