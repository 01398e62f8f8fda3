"""Core shared definitions: dtypes, errors, small utilities.

TPU-native re-imagination of the reference's dmlc-core plumbing
(reference: include/mxnet/base.h, python/mxnet/base.py). Instead of a C ABI
with string-encoded params, ops take real Python values and arrays are backed
by jax.Array; XLA subsumes the mshadow kernel layer.
"""
from __future__ import annotations

import os
import numpy as np

__version__ = "0.3.0"


class MXNetError(RuntimeError):
    """Framework error (name kept for API parity with the reference's
    python/mxnet/base.py:MXNetError)."""


# dtype registry: mxnet dtype-name <-> numpy dtype (reference:
# python/mxnet/base.py _DTYPE_NP_TO_MX / _DTYPE_MX_TO_NP). bfloat16 is the
# TPU-native addition: it is the MXU's preferred input dtype.
import ml_dtypes  # ships with jax

_DTYPE_NAMES = {
    "float32": np.dtype("float32"),
    "float64": np.dtype("float64"),
    "float16": np.dtype("float16"),
    "bfloat16": np.dtype(ml_dtypes.bfloat16),
    "uint8": np.dtype("uint8"),
    "int32": np.dtype("int32"),
    "int8": np.dtype("int8"),
    "int64": np.dtype("int64"),
    "bool": np.dtype("bool"),
}
_NAME_BY_DTYPE = {v: k for k, v in _DTYPE_NAMES.items()}


def dtype_from_name(name):
    if isinstance(name, str):
        if name not in _DTYPE_NAMES:
            raise MXNetError("unknown dtype name %r" % (name,))
        return _DTYPE_NAMES[name]
    return np.dtype(name)


def dtype_name(dtype) -> str:
    dtype = np.dtype(dtype)
    if dtype in _NAME_BY_DTYPE:
        return _NAME_BY_DTYPE[dtype]
    return dtype.name


def probe_devices(timeout_s=60):
    """Probe jax.devices() with a deadline from a daemon thread.

    Backend init can hang indefinitely when the device does not answer;
    callers that must not hang (bench, diagnose) use this. Returns
    (devices, None) on success, (None, error_message) on timeout or
    failure."""
    import threading
    result = {}

    def probe():
        try:
            import jax
            result["devs"] = jax.devices()
        except Exception as e:  # noqa: BLE001 — reported to caller
            result["err"] = str(e)

    th = threading.Thread(target=probe, daemon=True)
    th.start()
    th.join(timeout=timeout_s)
    if "devs" in result:
        return result["devs"], None
    return None, result.get("err",
                            "init timed out after %ds" % timeout_s)


def getenv(name, default):
    """Env-var config plane (reference: dmlc::GetEnv, docs/faq/env_var.md).

    All knobs are spelled MXTPU_* ; the reference's MXNET_* names are
    accepted as a fallback for familiarity.
    """
    val = os.environ.get(name)
    if val is None and name.startswith("MXTPU_"):
        val = os.environ.get("MXNET_" + name[len("MXTPU_"):])
    if val is None:
        return default
    if isinstance(default, bool):
        return val not in ("0", "false", "False", "")
    if isinstance(default, int):
        return int(val)
    if isinstance(default, float):
        return float(val)
    return val


def tuple_param(value, length=None, name="param"):
    """Normalize an int-or-tuple op parameter (kernel, stride, pad...)."""
    if value is None:
        return None
    if isinstance(value, (int, np.integer)):
        value = (int(value),) * (length or 1)
    value = tuple(int(v) for v in value)
    if length is not None and len(value) == 1:
        value = value * length
    if length is not None and len(value) != length:
        raise MXNetError("%s must have length %d, got %r" % (name, length, value))
    return value


_counter = [0]


def fresh_name(prefix: str) -> str:
    _counter[0] += 1
    return "%s%d" % (prefix, _counter[0])
