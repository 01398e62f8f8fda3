"""The control of "How `correct` is decided", at a size a test can hold:
the plain reference put in the program's place in the precision below the
cell's own comes out as NOT correct under the limits the cell's file
holds, and so does each fault planted in it; the reference in float32 held
against itself reads nought. The readings at the cells' own sizes, on the
chip, are in PERF.md."""
import glob
import json
import os

import jax
import pytest

import check
import harness
import tiny
import traffic

CELLS = sorted(glob.glob(os.path.join(harness.HERE, "workloads", "*.json")))
SEEDS = (11, 2147483659, 3000000019)


def _tiny_for(cell):
    config = tiny.GPT if cell["config"].startswith("gpt") else tiny.RESNET
    small = dict(cell, batch=8 if config is tiny.GPT else 32, pool=3)
    return dict(config), small


@pytest.mark.parametrize("path", CELLS, ids=[os.path.basename(p)[:-5]
                                             for p in CELLS])
def test_control_and_faults_are_not_correct(path):
    with open(path) as f:
        cell = json.load(f)
    config, small = _tiny_for(cell)
    devices = jax.devices()[:1]
    import model
    _, w = model.build(config, 1, devices[0])
    small["_shapes"] = {k: tuple(v.shape) for k, v in w.items()}
    for seed in SEEDS:
        pool = traffic.make_pool(small, config, seed)
        ref = harness.reference_readings(config, small, seed, pool, devices)
        same, _ = check.readings(ref, ref, small["_shapes"])
        assert check.verdict(same, cell["limits"])[0]
        planted = {"control": dict(mode=cell["control"]),
                   "half_batch": dict(rows=small["batch"] // 2),
                   "unchanged": dict(unchanged=True)}
        if cell["chips"] > 1:
            planted["no_exchange"] = dict(rows=small["batch"] // cell["chips"])
        for name, kw in planted.items():
            other = harness.reference_readings(config, small, seed, pool,
                                               devices, **kw)
            numbers, _ = check.readings(other, ref, small["_shapes"])
            ok, rows = check.verdict(numbers, cell["limits"])
            assert not ok, (name, seed, rows)
