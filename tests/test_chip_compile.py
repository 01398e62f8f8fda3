"""Compiles against a DESCRIBED chip (v5e:2x2), without the chip.

The TPU's compiler is installed beside the CPU backend and compiles for
a topology that is described and not attached: it raises here what it
would raise on the chip (tiling, fast-memory limits, a program that does
not fit the device's memory). Nothing runs, so nothing here says a result
or a time is right — `chip_smoke.py` on the chip does that.

This is the ONE file for such compiles: only one process may load the
TPU's library, so the topology is described inside a module-scoped
fixture (never at import, in a `skipif` or in `parametrize`), and the
compiles happen in the test's own process.

`pallas_kernels._interpret()` reads the default backend, which is the
CPU here; each test patches it to False for the compile.
"""
import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import pallas_kernels as pk

HBM_BYTES = 16 * 2 ** 30        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no compiler for it here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def for_the_chip(monkeypatch):
    """Real (not interpreted) kernels, and no persistent cache around the
    compile: an entry written for a described device cannot be read back
    without the chip and only makes the next compile warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("causal,dtype", [
    (True, jnp.bfloat16), (False, jnp.bfloat16), (True, jnp.float32)],
    ids=["causal", "full", "causal-float32"])
def test_flash_attention_gpt2_small(one_chip, for_the_chip, causal, dtype):
    # one GPT-2-small attention call, B=8, H=12, T=1024, D=64, at the
    # blocks the op chooses: the forward kernel and both kernels of the
    # backward (tiling and VMEM limits of all three). bfloat16, and
    # float32 as `ShardedTrainer`'s bf16 step hands q, k and v over
    # (LayerNorm's float32 gamma promotes them): at the default precision
    # the kernels then read them rounded once to bfloat16
    qkv = ((8, 12, 1024, 64), dtype)
    compiled = _compile(jax.value_and_grad(
        lambda q, k, v: pk.flash_attention(q, k, v, causal)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        one_chip, qkv, qkv, qkv)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert "1024,1024]" not in text             # no T x T array a head
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert all("constraints={bf16[96,1024,64]" in line for line in calls)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_gated_delta_rule_qwen3_next(one_chip, for_the_chip, monkeypatch,
                                     dtype):
    # one Gated DeltaNet layer of `qwen3next_train_b1_t8192`: B 1, T 8192,
    # 16 key and 32 value heads of 128, chunks of 64 (two a grid step):
    # the forward kernel, the forward that writes what the backward is
    # handed, and the backward kernel
    from mxnet_tpu.ops import delta_rule_kernels as dk
    from mxnet_tpu.ops import linear_attention as la
    monkeypatch.setattr(dk, "_interpret", lambda: False)
    qk, v = ((1, 8192, 16, 128), dtype), ((1, 8192, 32, 128), dtype)
    rows = ((1, 8192, 32), jnp.float32)
    compiled = _compile(jax.value_and_grad(
        lambda *a: la._through_kernels(*a, 64, True).astype(jnp.float32)
        .sum(), argnums=(0, 1, 2, 3, 4)), one_chip, qk, qk, v, rows, rows)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # no XLA array of C x C a chunk: what the backward is handed is
    # 128 x 128 a grid step, from one kernel to the other
    assert "64,64]" not in text
    _compile(lambda *a: la._through_kernels(*a, 64, True), one_chip,
             qk, qk, v, rows, rows)


def test_per_channel_delta_rule_kimi_linear(one_chip, for_the_chip,
                                            monkeypatch):
    # one Kimi Delta Attention layer at its published widths: B 1, T 4096,
    # 32 heads of 128, one decay a key channel, chunks of 64, bfloat16:
    # the two kernels `gated_delta_rule_channels_fwd` / `_bwd` are in the
    # program compiled for the chip (tiling and VMEM limits of both), no
    # `while` and no `triangular_solve` is left of the plain path, and
    # the temporaries of a layer's backward (what the backward is handed:
    # the tiles' states and inverses) stay under the 1.5 GB a
    # 602M-parameter step has room for
    from mxnet_tpu.ops import delta_rule_kernels as dk
    from mxnet_tpu.ops import linear_attention as la
    monkeypatch.setattr(dk, "_interpret", lambda: False)
    qkv = ((1, 4096, 32, 128), jnp.bfloat16)
    g, beta = ((1, 4096, 32, 128), jnp.float32), ((1, 4096, 32), jnp.float32)
    path = la.DELTA_PATH
    kernel0, plain0 = path.get(path="kernel"), path.get(path="plain")
    compiled = _compile(jax.value_and_grad(
        lambda *a: la.gated_delta_rule(*a, chunk=64).astype(jnp.float32)
        .sum(), argnums=(0, 1, 2, 3, 4)), one_chip, qkv, qkv, qkv, g, beta)
    assert (path.get(path="kernel"), path.get(path="plain")) \
        == (kernel0 + 1, plain0)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("gated_delta_rule_channels_fwd",
                   "gated_delta_rule_channels_bwd"):
        assert kernel in text, kernel
    assert " while(" not in text and "triangular" not in text.lower()
    assert "64,64]" not in text                 # no C x C array a chunk
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 1.5e9, temp
    print("per-channel delta rule, forward and backward: %.2f GB of "
          "temporaries" % (temp / 1e9))


@pytest.mark.parametrize("cell", ["lfm2", "qwen3next", "lfm2_64mib"])
def test_held_expert_layer(one_chip, for_the_chip, monkeypatch, cell):
    # one sparse layer of `lfm2_moe_train_b2_t4096` (N 8192, H 2048,
    # I 1792, 8 held of 32, top-4) or of `qwen3next_train_b1_t8192`
    # (I 512, 16 held of 512, top-10), bfloat16: the op's rule takes the
    # kernels (`moe.held.path`), and `moe_held_fwd`, `moe_held_bwd` and
    # the row-layout copies `moe_held_rows` compile for the chip (tiling,
    # the row DMAs, VMEM limits) with no `while` of the plain loop left;
    # lfm2_64mib: LFM2's layer in the chunks of I and the VMEM limit of a
    # core of 64 MiB
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops import moe_kernels as mk
    monkeypatch.setattr(mk, "_interpret", lambda: False)
    if cell == "lfm2_64mib":
        monkeypatch.setattr(mk, "_vmem_capacity", lambda: 64 * 2 ** 20)
    I, E_all, E, k, score = {"qwen3next": (512, 512, 16, 10, "softmax")}.get(
        cell, (1792, 32, 8, 4, "sigmoid"))
    bf = jnp.bfloat16
    path = moe.HELD_PATH
    kernel0, plain0 = path.get(path="kernel"), path.get(path="plain")
    compiled = _compile(jax.value_and_grad(
        lambda x, *w: moe.moe_held_ffn(x, *w, k, 0, 256, score)[0]
        .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)), one_chip,
        ((8192, 2048), bf), ((E_all, 2048), jnp.float32),
        ((E, I, 2048), bf), ((E, I, 2048), bf), ((E, 2048, I), bf))
    assert (path.get(path="kernel"), path.get(path="plain")) \
        == (kernel0 + 1, plain0)
    text = compiled.as_text()
    for kernel in ("moe_held_fwd", "moe_held_bwd", "moe_held_rows"):
        assert kernel in text, kernel
    assert " while(" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < 0.5e9, temp
    print("held experts, %s: %.2f GB of temporaries" % (cell, temp / 1e9))


def test_layer_norm_8192x768(one_chip, for_the_chip):
    _compile(pk.pallas_layer_norm, one_chip,
             ((8192, 768), jnp.bfloat16), ((768,), jnp.float32),
             ((768,), jnp.float32))


def test_fused_sgd_momentum_mfu_probe_shape(one_chip, for_the_chip):
    # tools/mfu_probe.py's update: 199680x128 fp32 (25.6M weights)
    wgm = ((199680, 128), jnp.float32)
    _compile(lambda w, g, m: pk.fused_sgd_momentum(w, g, m, lr=0.1),
             one_chip, wgm, wgm, wgm)


def test_conv1x1_bn_stats_resnet_stage1(one_chip, for_the_chip):
    # tools/mfu_probe.py's 1x1 conv: 128*56*56 rows, 64 -> 256 channels
    _compile(pk.conv1x1_bn_stats, one_chip,
             ((128 * 56 * 56, 64), jnp.bfloat16),
             ((64, 256), jnp.bfloat16))


@pytest.fixture(scope="module")
def resnet50_trainer():
    """The trainer `chip_smoke.py` builds, made on the CPU at a tiny image
    size (the parameters do not depend on it); only its pure step body
    goes to the described devices."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh

    net = vision.resnet50_v1(classes=1000, layout="NHWC")
    net.initialize()
    # shapes from inference, not from an eager forward: that would be a
    # program for every layer
    net.infer_shape(mx.nd.zeros((1, 32, 32, 3)))
    for p in net.collect_params().values():
        p._finish_deferred_init()
    return ShardedTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                          {"learning_rate": 0.01, "momentum": 0.9},
                          mesh=make_mesh({"dp": 1}, jax.devices()[:1]),
                          compute_dtype="bfloat16")


@pytest.mark.parametrize("n_chips", [1, 4], ids=["dp1", "dp4"])
def test_resnet50_b128_train_step_fits(topo, for_the_chip,
                                       resnet50_trainer, n_chips):
    """The steps `chip_smoke.py` runs — ResNet-50 v1 NHWC 224x224, global
    batch 128, bf16 compute, fp32 masters, SGD momentum, on a dp mesh of
    one chip or of all four — compiled for the described chips from
    shapes alone, must fit 16 GB a chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    st = resnet50_trainer
    mesh = Mesh(np.array(topo.devices[:n_chips]), ("dp",))
    replicated = NamedSharding(mesh, PartitionSpec())
    by_dp = NamedSharding(mesh, PartitionSpec("dp"))

    def described(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=replicated), tree)

    inputs = {"data": jax.ShapeDtypeStruct((128, 224, 224, 3),
                                           jnp.float32, sharding=by_dp),
              "label": jax.ShapeDtypeStruct((128,), jnp.float32,
                                            sharding=by_dp)}
    step = jax.jit(st._make_step_body(), donate_argnums=(0, 1, 2))
    compiled = step.lower(described(st._params), described(st._aux),
                          described(st._opt_state), inputs,
                          None).compile()
    ma = compiled.memory_analysis()      # bytes on each device
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert 0 < need < HBM_BYTES, (need, ma)
    n_params = sum(int(np.prod(v.shape)) for v in st._params.values())
    assert 25.0e6 < n_params < 26.0e6, n_params     # the full width
    # the gradients of a batch split over four chips are summed by a
    # collective the compiler put in; one chip needs none
    assert ("all-reduce" in compiled.as_text()) == (n_chips == 4)
    print("resnet50 b128 step on %d described chip(s): %.2f GiB a chip"
          % (n_chips, need / 2 ** 30))
