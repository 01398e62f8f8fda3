"""The whole tiny Qwen3-Next through the benchmark's own
`ShardedTrainer` loop against the benchmark's plain reference, and the
device counters that loop publishes."""
import jax
import pytest

import qwen3_next_helpers      # noqa: F401  (the benchmark's path)
import tiny_qwen3_next as tq   # noqa: E402  (benchmark/tests)


@pytest.mark.parametrize("what", ["losses", "gradient", "three_adam_steps"])
def test_model_against_the_plain_reference(what, _followed):
    prog, ref, shapes = _followed
    import check
    numbers, _ = check.readings(prog, ref, shapes)
    if what == "losses":
        assert max(numbers["loss_gap_%d" % i] for i in (1, 2, 3)) < 1e-5
    elif what == "gradient":
        assert numbers["grad_diff"] < 1e-4 and numbers["grad_norm_gap"] < 1e-4
        assert len(prog["grad"]) == len(ref["grad"]) >= 60
    else:
        assert numbers["change_norm_gap"] < 1e-3
        assert numbers["change_norm_gap_median"] < 1e-5


@pytest.fixture(scope="module")
def _followed():
    """The benchmark's own loop and reference at the tiny size: what a run
    of the cell compares, in float32."""
    import harness
    import tiny
    import traffic
    cell, config, seed = tiny.cell("sharded_trainer", 2), dict(tq.CONFIG), 77
    pool = traffic.make_pool(cell, config, seed)
    devices = jax.devices()[:1]
    loop = harness.load_file("loops", "sharded_trainer").Loop(
        cell, config, seed, devices)
    cell["_shapes"] = {k: tuple(v.shape) for k, v in loop.weights.items()}
    prog = harness.first_steps(loop, iter(loop.feed(traffic.cycle(pool))))
    from mxnet_tpu.observability import device_counters
    counters = device_counters.drain()
    loop.close()
    ref = harness.reference_readings(config, cell, seed, pool, devices)
    prog["counters"] = counters
    return prog, ref, cell["_shapes"]


def test_device_counters_are_published_without_a_sync(_followed):
    # the gauges keep other tests' trainers too: this loop's net alone
    counters = {name: {k: v for k, v in by_var.items()
                       if k.startswith("qwen3nextdecoder")}
                for name, by_var in _followed[0]["counters"].items()}
    chunks = counters["linear_attention.chunks"]
    assert len(chunks) == 3 and set(chunks.values()) == {2 * 24 / 8}
    held = counters["moe.assignments.held"]
    assert len(held) == 4 and all(0 < v < 2 * 24 * 3 for v in held.values())
    assert all(v >= 1 for v in counters["moe.load.max_over_mean"].values())
    from mxnet_tpu.observability import registry
    gauge = registry.REGISTRY.get("moe.assignments.held")
    assert sorted(gauge.labelsets()) and gauge.get(
        var=next(iter(held))) == next(iter(held.values()))
