"""Registry-wide numeric-gradient gate.

Reference discipline: `check_numeric_gradient` (python/mxnet/test_utils.py:792)
applied across tests/python/unittest/test_operator.py (6,785 LoC). The
TPU-native equivalent is generated rather than hand-written: every op in
`registry.list_ops()` must either

  (a) have a GRAD_CASES entry here — executed as jax.grad vs central
      finite differences on a small input drawn from a smooth domain, or
  (b) appear in exactly one EXEMPT_* list with a standing justification
      (non-float outputs, a.e.-zero derivatives, stochastic samplers,
      optimizer update rules, host-callback bridges, ...).

Aliases share the underlying fn, so covering one name covers them all.
`test_gate_registry_fully_cataloged` fails the moment a new op lands
without a grad case or exemption — that is the gate.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import registry as R

RNG = np.random.RandomState(11)


def U(shape, lo=-2.0, hi=2.0):
    """float32 uniform in a smooth domain"""
    return RNG.uniform(lo, hi, shape).astype("float32")


def P(shape, lo=0.5, hi=2.0):
    """strictly positive"""
    return U(shape, lo, hi)


def spd(n):
    """symmetric positive definite (for linalg)"""
    a = RNG.randn(n, n).astype("float32")
    return a @ a.T + n * np.eye(n, dtype="float32")


# ---------------------------------------------------------------------------
# case table: name -> dict(arrays=[np arrays], params={}, wrt=[arg indices])
# wrt defaults to [0]; params default {}
# ---------------------------------------------------------------------------

GRAD_CASES = {}


def case(name, arrays, params=None, wrt=(0,), atol=1e-2, rtol=5e-2,
         eps=1e-2):
    assert name not in GRAD_CASES, name
    GRAD_CASES[name] = dict(arrays=arrays, params=params or {},
                            wrt=tuple(wrt), atol=atol, rtol=rtol, eps=eps)


# --- elementwise unary (smooth domains chosen per-op) ----------------------
for name, dom in [
    ("abs", (0.5, 2)), ("negative", (-2, 2)), ("exp", (-1, 1)),
    ("expm1", (-1, 1)), ("log", (0.5, 3)), ("log2", (0.5, 3)),
    ("log10", (0.5, 3)), ("log1p", (-0.4, 2)), ("sqrt", (0.5, 3)),
    ("rsqrt", (0.5, 3)), ("cbrt", (0.5, 3)), ("rcbrt", (0.5, 3)),
    ("square", (-2, 2)), ("reciprocal", (0.5, 3)), ("sin", (-2, 2)),
    ("cos", (-2, 2)), ("tan", (-0.5, 0.5)), ("arcsin", (-0.8, 0.8)),
    ("arccos", (-0.8, 0.8)), ("arctan", (-2, 2)), ("sinh", (-1.5, 1.5)),
    ("cosh", (-1.5, 1.5)), ("arcsinh", (-2, 2)), ("arccosh", (1.5, 3)),
    ("arctanh", (-0.7, 0.7)), ("erf", (-1.5, 1.5)), ("erfinv", (-0.7, 0.7)),
    ("gamma", (1.5, 3)), ("gammaln", (1.5, 3)), ("sigmoid", (-2, 2)),
    ("tanh", (-2, 2)), ("relu", (0.25, 2)), ("softsign", (-2, 2)),
    ("hard_sigmoid", (-0.4, 0.4)), ("degrees", (-2, 2)),
    ("radians", (-2, 2)), ("smooth_l1", (0.2, 0.8)),
    ("_copy", (-2, 2)),
]:
    case(name, [U((3, 4), *dom)])

# --- elementwise binary ----------------------------------------------------
for name, (la, lb) in [
    ("_add", ((-2, 2), (-2, 2))), ("_sub", ((-2, 2), (-2, 2))),
    ("_mul", ((-2, 2), (-2, 2))), ("_div", ((-2, 2), (0.5, 2))),
    ("_grad_add", ((-2, 2), (-2, 2))),
    ("_Power", ((0.5, 2), (0.5, 2))), ("_hypot", ((0.5, 2), (0.5, 2))),
    ("_Maximum", ((0.3, 0.9), (1.1, 2))), ("_Minimum", ((0.3, 0.9), (1.1, 2))),
    ("_mod", ((2.2, 2.8), (1.0, 1.0))),
]:
    case(name, [U((3, 4), *la), U((3, 4), *lb)], wrt=(0, 1))

# --- scalar variants -------------------------------------------------------
for name, dom, pr in [
    ("_PlusScalar", (-2, 2), {"scalar": 1.5}),
    ("_MinusScalar", (-2, 2), {"scalar": 1.5}),
    ("_rminus_scalar", (-2, 2), {"scalar": 1.5}),
    ("_MulScalar", (-2, 2), {"scalar": 1.5}),
    ("_DivScalar", (-2, 2), {"scalar": 1.5}),
    ("_rdiv_scalar", (0.5, 2), {"scalar": 1.5}),
    ("_power_scalar", (0.5, 2), {"scalar": 1.5}),
    ("_rpower_scalar", (-1, 1), {"scalar": 1.5}),
    ("_maximum_scalar", (1.2, 2), {"scalar": 1.0}),
    ("_minimum_scalar", (0.2, 0.8), {"scalar": 1.0}),
    ("_mod_scalar", (2.2, 2.8), {"scalar": 1.0}),
    ("_rmod_scalar", (1.0, 1.0), {"scalar": 2.5}),
    ("_hypot_scalar", (0.5, 2), {"scalar": 1.5}),
]:
    case(name, [U((3, 4), *dom)], params=pr)

# --- reductions / cumulative ----------------------------------------------
case("sum", [U((3, 4))], params={"axis": 1})
case("mean", [U((3, 4))], params={"axis": 1})
case("prod", [P((3, 4))], params={"axis": 1})
case("nansum", [U((3, 4))], params={"axis": 1})
case("nanprod", [P((3, 4))], params={"axis": 1})
case("max", [U((3, 4))])   # unique max a.e.: differentiable at sample
case("min", [U((3, 4))])
case("norm", [P((3, 4))])
case("logsumexp", [U((3, 4))], params={"axis": 1})
case("cumsum", [U((3, 4))], params={"axis": 1})
case("_square_sum", [U((3, 4))], params={"axis": 1})

# --- broadcast binary family (only fns not already covered via the
# elemwise names that share the implementation) -----------------------------
_BCAST = [
    ("broadcast_add", (-2, 2)), ("broadcast_sub", (-2, 2)),
    ("broadcast_mul", (-2, 2)), ("broadcast_div", (0.5, 2)),
    ("broadcast_power", (0.5, 2)), ("broadcast_hypot", (0.5, 2)),
    ("broadcast_maximum", (0.2, 0.9)), ("broadcast_minimum", (0.2, 0.9)),
    ("broadcast_mod", (2.2, 2.8)),
]
for _name, _dom in _BCAST:
    try:
        _op = R.get(_name)
    except Exception:
        continue
    if any(R.get(n).fn is _op.fn for n in GRAD_CASES):
        continue
    case(_name, [U((3, 4), *_dom), U((1, 4), max(_dom[0], 1.0),
                                     max(_dom[1], 1.5))], wrt=(0, 1))

# --- shape/structural (differentiable pass-throughs) -----------------------
case("Reshape", [U((3, 4))], params={"shape": (4, 3)})
case("Flatten", [U((2, 3, 4))])
case("transpose", [U((3, 4))], params={"axes": (1, 0)})
case("expand_dims", [U((3, 4))], params={"axis": 1})
case("squeeze", [U((3, 1, 4))], params={"axis": 1})
case("Concat", [U((2, 3)), U((2, 3))], params={"num_args": 2, "dim": 1},
     wrt=(0, 1))
case("stack", [U((2, 3)), U((2, 3))], params={"num_args": 2, "axis": 1},
     wrt=(0, 1))
case("split", [U((2, 4))], params={"num_outputs": 2, "axis": 1})
case("slice_axis", [U((3, 4))], params={"axis": 1, "begin": 1, "end": 3})
case("crop", [U((3, 4))], params={"begin": (0, 1), "end": (2, 3)})
case("slice_like", [U((3, 4)), U((2, 3))], params={},
     wrt=(0,))
case("tile", [U((2, 3))], params={"reps": (2, 2)})
case("repeat", [U((2, 3))], params={"repeats": 2, "axis": 1})
case("flip", [U((2, 3))], params={"axis": 1})
case("SwapAxis", [U((2, 3, 4))], params={"dim1": 0, "dim2": 2})
case("diag", [U((4, 4))])
case("Pad", [U((1, 2, 3, 4))],
     params={"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)})
case("broadcast_to", [U((1, 3))], params={"shape": (4, 3)})
case("broadcast_axes", [U((1, 3))], params={"axis": 0, "size": 4})
case("broadcast_like", [U((1, 3)), U((4, 3))], wrt=(0,))
case("reshape_like", [U((3, 4)), U((4, 3))], wrt=(0,))
case("depth_to_space", [U((1, 4, 2, 2))], params={"block_size": 2})
case("space_to_depth", [U((1, 1, 4, 4))], params={"block_size": 2})
case("where", [np.array([[1.0, 0.0], [0.0, 1.0]], "float32"),
               U((2, 2)), U((2, 2))], wrt=(1, 2))
case("clip", [U((3, 4), -0.8, 0.8)], params={"a_min": -1.0, "a_max": 1.0})
case("Crop", [U((1, 2, 5, 5)), U((1, 2, 3, 3))],
     params={"num_args": 2, "offset": (1, 1)}, wrt=(0,))

# --- indexing (differentiable w.r.t. data) ---------------------------------
case("take", [U((5, 3)), np.array([1, 3], "int32")], wrt=(0,))
case("Embedding", [np.array([1, 2], "int32"), U((5, 3))],
     params={"input_dim": 5, "output_dim": 3}, wrt=(1,))
case("pick", [U((3, 4)), np.array([0, 2, 1], "int32")],
     params={"axis": 1}, wrt=(0,))
case("gather_nd", [U((4, 3)), np.array([[0, 2], [1, 0]], "int32")],
     wrt=(0,))
case("scatter_nd", [U((2,)), np.array([[0, 2]], "int32")],
     params={"shape": (4,)}, wrt=(0,))
case("one_hot", [np.array([0, 2], "int32")], params={"depth": 4}, wrt=())
case("SequenceLast", [U((3, 2, 4)), np.array([2, 3], "float32")],
     params={"use_sequence_length": True}, wrt=(0,))
case("SequenceMask", [U((3, 2, 4)), np.array([2, 3], "float32")],
     params={"use_sequence_length": True}, wrt=(0,))
case("SequenceReverse", [U((3, 2, 4))], wrt=(0,))
case("_sparse_retain", [U((4, 3)), np.array([0, 2], "int64")], wrt=(0,))

# --- matmul / linalg -------------------------------------------------------
case("dot", [U((3, 4)), U((4, 2))], wrt=(0, 1))
case("batch_dot", [U((2, 3, 4)), U((2, 4, 2))], wrt=(0, 1))
case("khatri_rao", [U((2, 3)), U((4, 3))], params={"num_args": 2},
     wrt=(0, 1))
case("linalg_gemm", [U((3, 4)), U((4, 2)), U((3, 2))], wrt=(0, 1, 2))
case("linalg_gemm2", [U((3, 4)), U((4, 2))], wrt=(0, 1))
case("linalg_potrf", [spd(3)], atol=5e-2)
case("linalg_potri", [spd(3)], atol=8e-2, rtol=0.1)
case("linalg_sumlogdiag", [spd(3)])
case("linalg_syrk", [U((3, 4))])
case("linalg_trmm", [np.tril(P((3, 3))).astype("float32"), U((3, 4))],
     wrt=(0, 1))
case("linalg_trsm", [(np.tril(U((3, 3), 0.8, 1.5)) +
                      2 * np.eye(3, dtype="float32")).astype("float32"),
                     U((3, 4))], wrt=(0, 1), atol=5e-2)
case("linalg_gelqf", [U((2, 4))], atol=8e-2, rtol=0.1)
case("linalg_syevd", [spd(3)], atol=8e-2, rtol=0.1)

# --- nn core ---------------------------------------------------------------
case("FullyConnected", [U((2, 5)), U((3, 5)), U((3,))],
     params={"num_hidden": 3}, wrt=(0, 1, 2))
case("Convolution", [U((1, 4, 4, 2)), U((2, 3, 3, 2)), U((2,))],
     params={"kernel": (3, 3), "num_filter": 2, "layout": "NHWC"},
     wrt=(0, 1, 2))
case("Deconvolution", [U((1, 3, 3, 3)), U((3, 2, 2, 2)), U((2,))],
     params={"kernel": (2, 2), "num_filter": 2, "no_bias": False},
     wrt=(0, 1, 2), atol=5e-2)
case("Pooling", [U((1, 4, 4, 2))],
     params={"kernel": (2, 2), "pool_type": "avg", "stride": (2, 2),
             "layout": "NHWC"})
case("Activation", [U((3, 4), 0.25, 2)], params={"act_type": "relu"})
case("LeakyReLU", [U((3, 4), 0.25, 2)], params={"act_type": "leaky"})
case("softmax", [U((3, 4))], params={"axis": -1})
case("softmin", [U((3, 4))], params={"axis": -1})
case("log_softmax", [U((3, 4))], params={"axis": -1})
case("SoftmaxActivation", [U((3, 4))])
case("LayerNorm", [U((3, 4)), P((4,)), U((4,))], wrt=(0, 1, 2))
case("InstanceNorm", [U((2, 3, 4)), P((3,)), U((3,))], wrt=(0, 1, 2))
case("L2Normalization", [P((3, 4))])
case("LRN", [P((1, 4, 3, 3))], params={"nsize": 3}, atol=5e-2)
case("BatchNorm",
     [U((2, 3, 4, 2)), P((2,)), U((2,)), np.zeros(2, "float32"),
      np.ones(2, "float32")],
     params={"axis": 3}, wrt=(0, 1, 2), atol=5e-2)
case("Dropout", [U((3, 4))], params={"p": 0.0})  # deterministic at p=0
case("Cast", [U((3, 4))], params={"dtype": "float32"})
case("UpSampling", [U((1, 2, 3, 3))],
     params={"scale": 2, "sample_type": "nearest", "num_args": 1})
case("BilinearSampler", [U((1, 2, 4, 4)),
                         np.clip(U((1, 2, 3, 3)), -0.9, 0.9)],
     wrt=(0,), atol=5e-2)
case("GridGenerator", [U((1, 6), -0.5, 0.5)],
     params={"transform_type": "affine", "target_shape": (4, 4)})
case("SpatialTransformer",
     [U((1, 2, 4, 4)), np.array([[1, 0, 0, 0, 1, 0]], "float32")],
     params={"transform_type": "affine", "sampler_type": "bilinear",
             "target_shape": (4, 4)}, wrt=(0,), atol=5e-2)
case("ROIPooling", [P((1, 2, 6, 6)), np.array([[0, 0, 0, 3, 3]], "float32")],
     params={"pooled_size": (2, 2), "spatial_scale": 1.0}, wrt=(0,))
case("Correlation", [P((1, 2, 4, 4)), P((1, 2, 4, 4))],
     params={"kernel_size": 1, "max_displacement": 1, "stride1": 1,
             "stride2": 1, "pad_size": 1}, wrt=(0, 1), atol=5e-2)
case("RNN", [U((3, 2, 4), -0.5, 0.5),
             U((sum([4 * 3 + 3 * 3 + 3 + 3]),), -0.3, 0.3),
             np.zeros((1, 2, 3), "float32")],
     params={"state_size": 3, "num_layers": 1, "mode": "rnn_tanh"},
     wrt=(0,), atol=5e-2)

# --- losses / outputs ------------------------------------------------------
# (loss HEADS — SoftmaxOutput, SVMOutput, *RegressionOutput — have custom
# vjps that return the loss gradient, not d(forward); they are checked
# against independent analytic formulas in ANALYTIC_GRAD_CASES below)
case("MakeLoss", [P((3, 4))])
case("softmax_cross_entropy", [U((3, 4)), np.array([0, 2, 1], "float32")],
     wrt=(0,))
case("IdentityAttachKLSparseReg", [P((3, 4), 0.05, 0.9)])
case("_contrib_CTCLoss",
     [U((4, 2, 5), -1, 1), np.array([[1, 2], [2, 1]], "float32")],
     wrt=(0,), atol=5e-2)

# --- contrib (differentiable) ----------------------------------------------
case("_contrib_quadratic", [U((3, 4))],
     params={"a": 1.0, "b": 2.0, "c": 3.0})
case("_contrib_div_sqrt_dim", [U((3, 4))])
case("_contrib_AdaptiveAvgPooling2D", [U((1, 2, 4, 4))],
     params={"output_size": 2})
case("_contrib_BilinearResize2D", [U((1, 2, 3, 3))],
     params={"height": 5, "width": 5}, atol=5e-2)
case("_contrib_ROIAlign",
     [P((1, 2, 6, 6)), np.array([[0, 0.5, 0.5, 3.5, 3.5]], "float32")],
     params={"pooled_size": (2, 2), "spatial_scale": 1.0}, wrt=(0,),
     atol=5e-2)
case("_contrib_PSROIPooling",
     [U((1, 8, 6, 6)), np.array([[0, 0, 0, 3, 3], [0, 1, 1, 4, 4]],
                                "float32")],
     params={"spatial_scale": 1.0, "output_dim": 2, "pooled_size": 2,
             "group_size": 2}, wrt=(0,), atol=5e-2)
# trans values are kept small (|dx| <= 0.1 px) so no bilinear sample
# crosses an integer grid line within the finite-difference eps
case("_contrib_DeformablePSROIPooling",
     [U((1, 8, 8, 8)), np.array([[0, 1, 1, 5, 5]], "float32"),
      U((1, 2, 2, 2), -0.2, 0.2)],
     params={"spatial_scale": 1.0, "output_dim": 2, "pooled_size": 2,
             "group_size": 2, "trans_std": 0.1, "no_trans": False},
     wrt=(0, 2), atol=5e-2)
case("_contrib_count_sketch", [U((2, 8)), np.array([0, 1, 0, 1, 1, 0, 1, 0],
                                                   "float32"),
                               np.array([1, 3, 0, 2, 4, 1, 0, 3], "float32")],
     params={"out_dim": 5}, wrt=(0,))
case("_contrib_fft", [U((2, 4))], params={}, atol=5e-2)
case("_contrib_ifft", [U((2, 8))], params={}, atol=5e-2)
case("_contrib_SparseEmbedding", [np.array([1, 2], "int32"), U((5, 3))],
     params={"input_dim": 5, "output_dim": 3}, wrt=(1,))
case("_image_normalize", [P((2, 3, 3))],
     params={"mean": (0.5,), "std": (0.3,)})
case("_npi_to_tensor", [U((4, 4, 3), 0, 255)])
case("_contrib_flash_attention",
     [U((1, 2, 4, 8), -0.5, 0.5), U((1, 2, 4, 8), -0.5, 0.5),
      U((1, 2, 4, 8), -0.5, 0.5)], wrt=(0, 1, 2), atol=5e-2)
case("_contrib_RingAttention",
     [U((1, 2, 4, 8), -0.5, 0.5), U((1, 2, 4, 8), -0.5, 0.5),
      U((1, 2, 4, 8), -0.5, 0.5)], wrt=(0, 1, 2), atol=5e-2)
case("_contrib_MoEFFN",
     [U((6, 8), -0.5, 0.5), U((8, 4), -0.3, 0.3),
      U((4, 8, 16), -0.3, 0.3), np.zeros((4, 16), "float32"),
      U((4, 16, 8), -0.3, 0.3), np.zeros((4, 8), "float32")],
     params={"capacity_factor": 4.0},  # nothing dropped: smooth at sample
     wrt=(0, 2, 4), atol=5e-2)
case("_contrib_SyncBatchNorm",
     [U((2, 3, 4, 2)), P((2,)), U((2,)), np.zeros(2, "float32"),
      np.ones(2, "float32")],
     params={"axis": 3}, wrt=(0, 1, 2), atol=5e-2)
case("_contrib_DeformableConvolution",
     [U((1, 2, 4, 4)), np.zeros((1, 18, 2, 2), "float32") + 0.01,
      U((2, 2, 3, 3))],
     params={"kernel": (3, 3), "num_filter": 2},
     wrt=(0, 2), atol=5e-2)

# arithmetic/assign-style ops
case("_scatter_elemwise_div", [U((3, 4)), P((3, 4))], wrt=(0, 1))
case("_scatter_plus_scalar", [U((3, 4))], params={"scalar": 1.5})
case("_scatter_minus_scalar", [U((3, 4))], params={"scalar": 1.5})
case("_crop_assign", [U((3, 4)), U((2, 2))],
     params={"begin": (0, 1), "end": (2, 3)}, wrt=(0, 1))
case("_crop_assign_scalar", [U((3, 4))],
     params={"scalar": 1.0, "begin": (0, 1), "end": (2, 3)})
case("_identity_with_attr_like_rhs", [U((3, 4)), U((3, 4))], wrt=(0,))
case("add_n", [U((3, 4)), U((3, 4))], params={"num_args": 2}, wrt=(0, 1))
case("BlockGrad", [U((3, 4))], wrt=())       # zero-grad by contract
case("_CrossDeviceCopy", [U((3, 4))])

# ---------------------------------------------------------------------------
# exemptions, each list = one standing justification
# ---------------------------------------------------------------------------

# outputs are indices / ints / bools / shapes: no gradient exists
# --- the linear-attention decoder's ops (PR 32); their own generator, so
# that the cases above keep the inputs they had --------------------------
_Q = np.random.RandomState(32)


def _q(shape, lo=-1.0, hi=1.0):
    return _Q.uniform(lo, hi, shape).astype("float32")


case("_contrib_gated_delta_rule",
     [_q((1, 8, 1, 4)), _q((1, 8, 1, 4)), _q((1, 8, 2, 4)), _q((1, 8, 2)),
      _q((1, 8, 2)), _q((2,), -3, -2), _q((2,), 0.5, 1.5),
      np.zeros(1, "float32")],
     params={"chunk": 4}, wrt=(0, 1, 2, 3, 4, 5, 6), atol=2e-2)
case("_contrib_causal_conv1d", [_q((1, 6, 3)), _q((3, 4))],
     params={"activation": "silu"}, wrt=(0, 1))
case("_contrib_rms_norm", [_q((2, 5)), _q((5,))], params={"offset": 1.0},
     wrt=(0, 1))
case("_contrib_gated_rms_norm", [_q((2, 5)), _q((2, 5)), _q((5,), 0.5, 1.5)],
     wrt=(0, 1, 2))
case("_contrib_causal_gqa_attention",
     [_q((1, 8, 4, 4)), _q((1, 8, 2, 4)), _q((1, 8, 2, 4))],
     params={"block_q": 4}, wrt=(0, 1, 2), atol=2e-2)
case("_contrib_rotary_embedding", [_q((1, 4, 2, 8))],
     params={"rotary_dim": 4, "theta": 100.0})
# the held experts' matrices: smooth at the sample (the router's choice
# is piecewise constant in x and in its own weights)
case("_contrib_moe_held_ffn",
     [_q((6, 8)), _q((8, 8)), _q((2, 4, 8)), _q((2, 4, 8)), _q((2, 8, 4)),
      np.zeros(2, "float32")],
     params={"top_k": 3, "held_start": 2, "tile": 4}, wrt=(2, 3, 4),
     atol=2e-2)
case("_contrib_shared_expert_ffn",
     [_q((3, 8)), _q((4, 8)), _q((4, 8)), _q((8, 4)), _q((1, 8))],
     wrt=(0, 1, 2, 3, 4), atol=2e-2)
# LFM2's gated short convolution (PR 39): a generator of its own, so that
# the cases above keep their inputs; batch 2, three taps
_S = np.random.RandomState(39)
case("_contrib_short_conv",
     [_S.uniform(-1, 1, shape).astype("float32")
      for shape in ((2, 5, 4), (12, 4), (4, 3), (4, 4))],
     wrt=(0, 1, 2, 3), atol=2e-2)

EXEMPT_NONFLOAT_OUTPUT = {
    "argmax", "argmin", "argsort", "topk", "sort",  # sort: permutation —
    # value-grads exist but are just scatter of ones; covered via topk in
    # test_autograd.test_multi_output_partial_use
    "shape_array", "size_array", "_histogram", "histogram",
    "_ravel_multi_index", "ravel_multi_index", "_unravel_index",
    "unravel_index", "_contrib_bipartite_matching",
    "_equal", "_not_equal", "_greater", "_greater_equal", "_lesser",
    "_lesser_equal", "_equal_scalar", "_not_equal_scalar",
    "_greater_scalar", "_greater_equal_scalar", "_lesser_scalar",
    "_lesser_equal_scalar", "_logical_and", "_logical_or", "_logical_xor",
    "_logical_and_scalar", "_logical_or_scalar", "_logical_xor_scalar",
    "logical_not", "broadcast_logical_and", "broadcast_logical_or",
    "broadcast_logical_xor", "argmax_channel",
}

# derivative is zero almost everywhere: finite differences are vacuous
EXEMPT_PIECEWISE_CONSTANT = {
    "round", "rint", "fix", "floor", "ceil", "trunc", "sign",
}

# stochastic output: no meaningful numeric gradient (reparameterized
# sampling is not part of the reference API either)
EXEMPT_RANDOM = {
    "uniform", "normal", "randint", "bernoulli", "random_exponential",
    "random_gamma", "random_negative_binomial", "random_poisson",
    "random_generalized_negative_binomial", "sample_uniform",
    "sample_normal", "sample_multinomial", "_sample_exponential",
    "_sample_gamma", "_sample_negative_binomial", "_sample_poisson",
    "_sample_generalized_negative_binomial", "shuffle",
}

# optimizer update rules: applied under stop-gradient by contract
# (reference registers them without FGradient)
EXEMPT_OPTIMIZER_UPDATE = {
    "sgd_update", "sgd_mom_update", "mp_sgd_update", "mp_sgd_mom_update",
    "adam_update", "ftml_update", "ftrl_update", "rmsprop_update",
    "rmspropalex_update", "signsgd_update", "signum_update",
    "_sparse_adagrad_update", "_scatter_set_nd",
}

# constant constructors: no float inputs to differentiate
EXEMPT_CONSTANT = {
    "_zeros", "_ones", "_arange", "_full", "zeros_like", "ones_like",
    "eye", "_eye",
}

# int8/quantized kernels: integer tensors end-to-end
EXEMPT_QUANTIZED = {
    "_contrib_quantize", "_contrib_dequantize", "_contrib_requantize",
    "_contrib_qdq", "_contrib_int8_conv", "_contrib_int8_fc",
    "_contrib_quantized_act", "_contrib_quantized_conv",
    "_contrib_quantized_flatten", "_contrib_quantized_fully_connected",
    "_contrib_quantized_pooling", "cast_storage",
}

# host-callback / subgraph bridges: gradient correctness is covered by
# dedicated suites (test_custom_op.py, test_control_flow.py) because the
# op takes closures, not arrays
EXEMPT_BRIDGE = {
    "Custom", "_foreach", "_while_loop", "_cond",
}

# detection/proposal heads: outputs are box coordinates + scores whose
# reference implementations are likewise non-differentiable C++ kernels
# (no FGradient registered: multibox_*.cc, proposal.cc, bounding_box.cc).
# PSROIPooling / DeformablePSROIPooling do NOT belong here — the
# reference trains through both (psroi_pooling.cc PSROIPoolBackwardAcc,
# deformable_psroi_pooling.cc) — so they carry GRAD_CASES above.
EXEMPT_DETECTION = {
    "_contrib_MultiBoxPrior", "_contrib_MultiBoxTarget",
    "_contrib_MultiBoxDetection", "_contrib_box_nms", "_contrib_box_iou",
    "_contrib_Proposal", "_contrib_MultiProposal",
}

EXEMPT = (EXEMPT_NONFLOAT_OUTPUT | EXEMPT_PIECEWISE_CONSTANT
          | EXEMPT_RANDOM | EXEMPT_OPTIMIZER_UPDATE | EXEMPT_CONSTANT
          | EXEMPT_QUANTIZED | EXEMPT_BRIDGE | EXEMPT_DETECTION)


# ---------------------------------------------------------------------------
# loss heads: backward returns the LOSS gradient by contract (the incoming
# cotangent is ignored — reference regression_output-inl.h:206,
# softmax_output-inl.h, svm_output.cc), so finite differences of the
# forward are invalid by design. Each gets an independent numpy formula
# the custom vjp must reproduce.
# ---------------------------------------------------------------------------


def _np_softmax(x):
    e = np.exp(x - x.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _np_onehot(lbl, n):
    return np.eye(n, dtype="float32")[lbl.astype("int64")]


def _exp_linear_regression(data, label):
    return (data - label) / data.shape[1]       # /num_output, ref :200-206


def _exp_mae_regression(data, label):
    return np.sign(data - label) / data.shape[1]


def _exp_logistic_regression(data, label):
    return (1 / (1 + np.exp(-data)) - label) / data.shape[1]


def _exp_softmax_output(data, label):
    return _np_softmax(data) - _np_onehot(label, data.shape[-1])


def _exp_svm_output(data, label):
    # L1-SVM (use_linear=True): g_j = coef·1{margin > s_t − s_j}, j ≠ t;
    # g_t = −Σ g_j  (reference svm_output.cc forward-identity hinge head)
    n = data.shape[-1]
    oh = _np_onehot(label, n)
    s_true = (data * oh).sum(-1, keepdims=True)
    viol = (1.0 - (s_true - data)) > 0
    g = np.where(oh > 0, 0.0, viol.astype("float32"))
    g_t = -g.sum(-1, keepdims=True)
    return g + oh * g_t


ANALYTIC_GRAD_CASES = {
    "LinearRegressionOutput": ([U((3, 4)), U((3, 4))], {},
                               _exp_linear_regression),
    "MAERegressionOutput": ([U((3, 4), 0.5, 2), U((3, 4), -0.4, 0.4)], {},
                            _exp_mae_regression),
    "LogisticRegressionOutput": ([U((3, 4)), P((3, 4), 0.1, 0.9)], {},
                                 _exp_logistic_regression),
    "SoftmaxOutput": ([U((3, 4)), np.array([0, 2, 1], "float32")], {},
                      _exp_softmax_output),
    "SVMOutput": ([U((3, 4)), np.array([0, 2, 1], "float32")],
                  {"use_linear": True}, _exp_svm_output),
}


@pytest.mark.parametrize("name", sorted(ANALYTIC_GRAD_CASES),
                         ids=sorted(ANALYTIC_GRAD_CASES))
def test_loss_head_analytic_vjp(name):
    arrays, params, expect = ANALYTIC_GRAD_CASES[name]
    op = R.get(name)
    full = R.apply_defaults(op, dict(params))

    def f(x):
        return jnp.sum(op.fn(x, jnp.asarray(arrays[1]), **full))

    g = np.asarray(jax.grad(f)(jnp.asarray(arrays[0])))
    exp = expect(np.asarray(arrays[0], "float64"),
                 np.asarray(arrays[1], "float64"))
    assert np.allclose(g, exp, atol=1e-4, rtol=1e-4), name


# ---------------------------------------------------------------------------
# the gate
# ---------------------------------------------------------------------------


def _covered_fns():
    ids = set()
    for name in GRAD_CASES:
        ids.add(id(R.get(name).fn))
    for name in ANALYTIC_GRAD_CASES:
        ids.add(id(R.get(name).fn))
    for name in EXEMPT:
        try:
            ids.add(id(R.get(name).fn))
        except Exception:
            pass
    return ids


def test_gate_registry_fully_cataloged():
    covered = _covered_fns()
    missing = sorted(
        n for n in R.list_ops()
        if id(R.get(n).fn) not in covered)
    assert not missing, (
        "ops with neither a numeric-gradient case nor a justified "
        "exemption in test_operator_grad_gate.py: %s" % missing)


def test_gate_exemptions_exist():
    """Exempt names must stay real registry entries (catch typos/renames)."""
    all_ops = set(R.list_ops())
    stale = sorted(n for n in EXEMPT if n not in all_ops)
    assert not stale, "stale exemptions: %s" % stale


def test_gate_no_double_booking():
    both = sorted(set(GRAD_CASES) & EXEMPT)
    assert not both, "ops both cased and exempted: %s" % both


# ---------------------------------------------------------------------------
# the generated check
# ---------------------------------------------------------------------------


def _run_case(name, spec):
    op = R.get(name)
    arrays = [jnp.asarray(a) for a in spec["arrays"]]
    # mimic the frontend: drop codegen-only params the fn doesn't take,
    # then validate + fill defaults exactly as invoke() does
    params = {k: v for k, v in spec["params"].items()
              if k in op.params or op.allow_extra_params}
    params = R.apply_defaults(op, params)
    if op.takes_mode:
        params["_mode"] = "predict"
    wrt = spec["wrt"]
    # rng ops: fix the key — deterministic given the key, so autodiff and
    # finite differences see the same function (Dropout is cased at p=0,
    # LeakyReLU at act_type=leaky, RNN in predict mode: all key-invariant)
    key = jax.random.PRNGKey(0) if op.needs_rng else None

    vis = op.visible_outputs
    n_vis = vis(params) if callable(vis) else (vis or None)

    def f(*diffs):
        ins = list(arrays)
        for k, j in enumerate(wrt):
            ins[j] = diffs[k]
        if key is not None:
            ins = [key] + ins
        out = op.fn(*ins, **params)
        outs = out if isinstance(out, tuple) else (out,)
        if n_vis is not None:
            outs = outs[:n_vis]
        tot = 0.0
        for o in outs:
            if jnp.issubdtype(o.dtype, jnp.floating):
                tot = tot + jnp.sum(o.astype(jnp.float32))
        return tot

    if not wrt:
        f()          # smoke only: no differentiable inputs by contract
        return

    # one program for f and one for its gradient instead of one tiny
    # program per primitive: the compiles are what this gate costs
    grad = jax.jit(jax.grad(f, argnums=tuple(range(len(wrt)))))
    f = jax.jit(f)
    diffs = [arrays[j] for j in wrt]
    grads = grad(*diffs)
    eps = spec["eps"]
    for k, j in enumerate(wrt):
        base = np.asarray(arrays[j], "float64")
        g = np.asarray(grads[k], "float64")
        flat = base.reshape(-1)
        # sample a handful of coordinates — enough to catch a wrong vjp,
        # cheap enough to run registry-wide
        import zlib
        rng = np.random.RandomState(
            (zlib.crc32(name.encode()) ^ (j << 16)) & 0x7fffffff)
        idxs = rng.choice(flat.size, size=min(4, flat.size), replace=False)
        for idx in idxs:
            fp = flat.copy(); fp[idx] += eps
            fm = flat.copy(); fm[idx] -= eps
            vp = float(f(*[jnp.asarray(fp.reshape(base.shape), "float32")
                           if kk == k else diffs[kk]
                           for kk in range(len(wrt))]))
            vm = float(f(*[jnp.asarray(fm.reshape(base.shape), "float32")
                           if kk == k else diffs[kk]
                           for kk in range(len(wrt))]))
            num = (vp - vm) / (2 * eps)
            got = g.reshape(-1)[idx]
            assert np.isclose(got, num, rtol=spec["rtol"],
                              atol=spec["atol"]), (
                "%s: d/d(input %d)[%d]: autodiff %g vs numeric %g"
                % (name, j, idx, got, num))


@pytest.mark.parametrize("name", sorted(GRAD_CASES), ids=sorted(GRAD_CASES))
def test_numeric_gradient(name, ):
    _run_case(name, GRAD_CASES[name])


# ---------------------------------------------------------------------------
# second-order spot checks: jax.grad(jax.grad(...)) vs central differences
# of the analytic first derivative, on representative smooth ops (the
# breadth backing autograd.grad(create_graph=True) beyond the tape tests)
# ---------------------------------------------------------------------------

SECOND_ORDER_CASES = {
    "tanh": ([U((3, 4), -1.5, 1.5)], {}),
    "sigmoid": ([U((3, 4), -2, 2)], {}),
    "exp": ([U((3, 4), -1, 1)], {}),
    "log": ([P((3, 4), 0.5, 3)], {}),
    "square": ([U((3, 4))], {}),
    "softmax": ([U((3, 4))], {"axis": -1}),
    "FullyConnected": ([U((2, 5)), U((3, 5)), U((3,))],
                       {"num_hidden": 3}),
    "Convolution": ([U((1, 4, 4, 2)), U((2, 3, 3, 2)), U((2,))],
                    {"kernel": (3, 3), "num_filter": 2,
                     "layout": "NHWC"}),
    "LayerNorm": ([U((3, 4)), P((4,)), U((4,))], {}),
}


@pytest.mark.parametrize("name", sorted(SECOND_ORDER_CASES),
                         ids=sorted(SECOND_ORDER_CASES))
def test_second_order_gradient(name):
    arrays, params = SECOND_ORDER_CASES[name]
    op = R.get(name)
    full = R.apply_defaults(op, dict(params))
    if op.takes_mode:
        full["_mode"] = "predict"
    xs = [jnp.asarray(a) for a in arrays]

    def f(x0):
        out = op.fn(x0, *xs[1:], **full)
        out = out[0] if isinstance(out, tuple) else out
        # nonlinear functional so the 2nd derivative is nontrivial
        # even for linear ops (FC/conv)
        return jnp.sum(jnp.tanh(out.astype(jnp.float32)))

    g = jax.grad(f)
    gg = np.asarray(jax.grad(lambda x: jnp.sum(g(x)))(xs[0]), "float64")
    base = np.asarray(arrays[0], "float64")
    eps = 1e-3
    import zlib
    rng = np.random.RandomState(zlib.crc32(name.encode()) & 0x7fffffff)
    flat = base.reshape(-1)
    for idx in rng.choice(flat.size, size=min(3, flat.size),
                          replace=False):
        xp = flat.copy(); xp[idx] += eps
        xm = flat.copy(); xm[idx] -= eps
        gp = float(np.sum(np.asarray(
            g(jnp.asarray(xp.reshape(base.shape), "float32")))))
        gm = float(np.sum(np.asarray(
            g(jnp.asarray(xm.reshape(base.shape), "float32")))))
        num = (gp - gm) / (2 * eps)
        got = gg.reshape(-1)[idx]
        assert np.isclose(got, num, rtol=0.05, atol=5e-2), (
            "%s: d2[%d] %g vs numeric %g" % (name, idx, got, num))
