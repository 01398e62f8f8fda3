"""Tiny stand-ins for the CPU rehearsals and the comparisons with the
plain references: the program's own blocks at a size a test can hold."""


def tiny_resnet(classes=10):
    """Bottleneck ResNet, one block a stage, an eighth of the widths."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1
    return ResNetV1(BottleneckV1, [1, 1, 1, 1], [8, 32, 64, 128, 256],
                    classes=classes, layout="NHWC")


RESNET = {
    "name": "tiny_resnet",
    "model": {"factory": "tiny:tiny_resnet", "kwargs": {"classes": 10},
              "infer_shape": [1, 32, 32, 3]},
    "input": {"kind": "image", "shape": [32, 32, 3], "classes": 10},
    "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "sgd", "params": {"learning_rate": 0.1,
                                            "momentum": 0.9, "wd": 1e-4}},
    "initializer": [
        {"match": "running_var$", "fill": 1.0},
        {"match": "gamma$", "fill": 1.0},
        {"match": "(beta|bias|running_mean)$", "fill": 0.0},
        {"match": "weight$", "normal": "xavier_in", "magnitude": 2.0}],
    "batch_norm": {"momentum": 0.9},
    "reference": "resnet50_v1",
    "reference_kwargs": {"blocks": [1, 1, 1, 1]},
    "flops": "resnet50_v1",
}

GPT = {
    "name": "tiny_gpt",
    "model": {"factory": "mxnet_tpu.gluon.model_zoo.gpt:GPTDecoder",
              "kwargs": {"vocab_size": 97, "max_seq_len": 16,
                         "num_layers": 2, "num_heads": 4, "embed_dim": 64}},
    "input": {"kind": "tokens", "length": 16, "vocab": 97},
    "loss": "SoftmaxCrossEntropyLoss",
    "optimizer": {"name": "adam", "params": {
        "learning_rate": 6e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8}},
    "initializer": [
        {"match": "gamma$", "fill": 1.0},
        {"match": "(beta|bias)$", "fill": 0.0},
        {"match": "weight$", "normal": "sigma", "sigma": 0.02}],
    "reference": "gpt2_small",
    "reference_kwargs": {"heads": 4},
    "flops": "gpt2_small",
}

LIMITS = {"loss_gap_1": 1e-4, "loss_gap_2": 1e-4, "loss_gap_3": 1e-4,
          "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3}


def cell(loop, batch, chips=1, compute_dtype=None, **more):
    out = {"name": "tiny_" + loop, "loop": loop, "batch": batch, "pool": 4,
           "chips": chips, "warmup_steps": 1, "trace_steps": 2,
           "limits": dict(LIMITS)}
    if compute_dtype:
        out["compute_dtype"] = compute_dtype
    out.update(more)
    return out
