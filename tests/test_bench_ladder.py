"""Unit tests for bench.py's ladder construction, compile-cache guard,
and child-reaping fence — the pure-Python pieces the CPU smoke
exercises only end-to-end. No jax import; the two fence tests spawn
short-lived -S subprocesses and sync on a readiness line, so the whole
file stays in low single-digit seconds."""
import importlib.util
import os
import signal
import subprocess
import sys

import pytest


@pytest.fixture()
def bench(monkeypatch, tmp_path):
    # a fresh module per test so env-derived module constants reset
    monkeypatch.setenv("MXTPU_XLA_CACHE", str(tmp_path / "cache"))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(root, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_default_ladder_order_and_shape(bench, monkeypatch):
    monkeypatch.delenv("MXTPU_BENCH_DEADLINES", raising=False)
    monkeypatch.delenv("MXTPU_BENCH_SCORE", raising=False)
    rungs = bench._rungs()
    assert [r[0] for r in rungs] == ["secure", "score", "mid", "full"]
    # secure and score measure the identical small train config; the
    # score rung exists only to isolate the inference compile
    assert rungs[0][1:3] == rungs[1][1:3]
    # escalation is monotone in work: steps then unroll
    assert rungs[2][1] >= rungs[0][1] and rungs[3][2] >= rungs[2][2]


def test_legacy_three_value_deadlines_keep_meaning(bench, monkeypatch):
    monkeypatch.setenv("MXTPU_BENCH_DEADLINES", "111,222,333")
    by_name = {r[0]: r[5] for r in bench._rungs()}
    # pre-round-5 spelling was (secure, mid, full): mid/full must NOT
    # silently inherit looser fences; score borrows secure's
    assert by_name == {"secure": 111.0, "score": 111.0,
                       "mid": 222.0, "full": 333.0}


def test_single_deadline_bounds_every_rung(bench, monkeypatch):
    monkeypatch.setenv("MXTPU_BENCH_DEADLINES", "77")
    assert [r[5] for r in bench._rungs()] == [77.0] * 4


def test_score_rung_dropped_when_scoring_masked(bench, monkeypatch):
    monkeypatch.setenv("MXTPU_BENCH_SCORE", "0")
    monkeypatch.setenv("MXTPU_BENCH_DEADLINES", "1,2,3,4")
    rungs = bench._rungs()
    assert [r[0] for r in rungs] == ["secure", "mid", "full"]
    # deadlines are zipped before the drop so the others keep slots
    assert [r[5] for r in rungs] == [1.0, 3.0, 4.0]


def _spawn_wedged(setup, payload):
    """Start a -S python child that runs `setup` (e.g. signal handler
    installs), prints `payload` to stdout, signals readiness on STDERR,
    then sleeps forever. Readiness rides stderr so the parent's
    buffered readline can't swallow the stdout payload fence_child's
    communicate must see; blocking on it replaces any fixed sleep."""
    emit = ("\nprint(%r, flush=True)\n"
            "print('ready', file=sys.stderr, flush=True)\n"
            "time.sleep(600)\n") % (payload,)
    code = "import sys, time\n" + setup + emit  # setup never %-parsed
    p = subprocess.Popen([sys.executable, "-S", "-c", code],
                         stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                         text=True)
    assert p.stderr.readline().strip() == "ready"
    return p


def test_fence_child_keeps_pre_wedge_stdout(bench):
    # child emits its result, then wedges ignoring SIGINT/SIGTERM —
    # the fence must escalate to SIGKILL AND return what was printed
    p = _spawn_wedged(
        "import signal\n"
        "signal.signal(signal.SIGINT, signal.SIG_IGN)\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)",
        '{"value": 42}')
    try:
        out, status = bench.fence_child(
            p, graces=((signal.SIGINT, 1), (signal.SIGTERM, 1),
                       (signal.SIGKILL, 5)))
        assert status == "SIGKILL"
        assert out is not None and '"value": 42' in out
    finally:
        p.kill()
        p.wait()


def test_fence_child_clean_sigint_unwind(bench):
    # a child that honors SIGINT exits within the first grace window
    p = _spawn_wedged("", "partial")
    try:
        out, status = bench.fence_child(
            p, graces=((signal.SIGINT, 10), (signal.SIGTERM, 5),
                       (signal.SIGKILL, 5)))
        assert status == "SIGINT"
        assert out is not None and "partial" in out
    finally:
        p.kill()
        p.wait()


def _guard_cache_env(monkeypatch):
    """_enable_compile_cache writes JAX_COMPILATION_CACHE_DIR straight
    into os.environ; register the var with monkeypatch first so the
    mutation is rolled back after the test instead of leaking into
    later jax-importing tests."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "sentinel")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")


def test_compile_cache_env_respects_explicit_dir(bench, monkeypatch,
                                                 tmp_path):
    target = tmp_path / "explicit"
    monkeypatch.setenv("MXTPU_XLA_CACHE", str(target))
    _guard_cache_env(monkeypatch)
    bench._enable_compile_cache()
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(target)


def test_compile_cache_disabled_by_zero(bench, monkeypatch):
    monkeypatch.setenv("MXTPU_XLA_CACHE", "0")
    _guard_cache_env(monkeypatch)
    bench._enable_compile_cache()
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


def test_compile_cache_default_is_the_frameworks(bench, monkeypatch):
    # one spelling of the default: bench asks the framework's resolver,
    # which answers the fixed directory inside the checkout
    from mxnet_tpu.compile.cache import default_cache_dir
    monkeypatch.delenv("MXTPU_XLA_CACHE", raising=False)
    monkeypatch.delenv("MXTPU_COMPILE_CACHE", raising=False)
    _guard_cache_env(monkeypatch)
    bench._enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] \
        == default_cache_dir() == os.path.join(root, ".jax_cache")


def test_no_cpu_fallback_left(bench):
    # with no chip bench.py exits non-zero; the CPU is measured only
    # under the explicit MXTPU_BENCH_PLATFORM=cpu pin
    assert not hasattr(bench, "_fallback_to_cpu")
    src = open(bench.__file__).read()
    assert "MXTPU_BENCH_CPU_FALLBACK" not in src
