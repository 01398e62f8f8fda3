"""NDArray: the eager tensor type.

Reference: include/mxnet/ndarray.h:82, src/ndarray/ndarray.cc,
python/mxnet/ndarray/ndarray.py:169.

TPU-native design: an NDArray wraps a jax.Array. The reference's async
semantics (engine var per chunk, WaitToRead/WaitToWrite) are inherited for
free from JAX's async dispatch — every op returns immediately with a future
-backed buffer and `wait_to_read()` fences via `_fence` (block_until_ready
plus, under MXTPU_STRICT_FENCE=1, a device_get of a dependent slice —
see _fence's docstring). The dependency engine, storage pool and kernel
library are all subsumed by XLA/PJRT.

Eager op dispatch (the analog of Imperative::Invoke,
src/imperative/imperative.cc:87) goes through `invoke()`: per-(op, params)
jit-cached XLA executables, plus autograd tape recording via jax.vjp.
"""
from __future__ import annotations

import contextlib
import functools
import time
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from ..base import MXNetError, dtype_from_name, dtype_name
from ..context import Context, current_context
from ..observability.trace import trace_span
from ..ops import registry as _reg

__all__ = ["NDArray", "invoke", "array", "zeros", "ones", "full", "empty",
           "arange", "concatenate", "moveaxis", "waitall", "imdecode",
           "load", "save"]


# every live NDArray, so waitall() can fence on all in-flight results
# (reference: Engine::WaitForAll orders against every dispatched op).
# WeakSet is not thread-safe and input-pipeline worker threads create
# NDArrays concurrently with a main-thread waitall(): all access goes
# through _live_lock.
_live_arrays = weakref.WeakSet()
_live_lock = __import__("threading").Lock()


def _fence(data):
    """The ONE completion fence for a jax.Array (reference WaitToRead,
    include/mxnet/ndarray.h:315-323: returns only after all pending
    writes completed). Shared by NDArray.wait_to_read/wait_to_write and
    waitall() (which batches via _fence_many).

    block_until_ready() is that fence. When asked for by
    MXTPU_STRICT_FENCE=1 we additionally device_get a tiny dependent
    slice: the fetched bytes can only exist after the producer ran, at
    O(1) transfer cost. Non-addressable (multi-process sharded) buffers
    can't be fetched from one host and keep the plain fence.
    """
    _fence_many([data])


def _fence_many(datas):
    """Fence a batch of jax.Arrays with ONE host round trip for the
    strict leg (device_get takes a pytree), so a waitall() over
    hundreds of live arrays doesn't pay a transfer per array."""
    from ..base import getenv
    strict = getenv("MXTPU_STRICT_FENCE", False)
    slices = []
    for data in datas:
        if not isinstance(data, jax.Array):
            continue
        if isinstance(data, jax.core.Tracer):
            continue  # inside a trace there is nothing to fence (and
            # device_get on a tracer would raise ConcretizationTypeError)
        if getattr(data, "is_deleted", lambda: False)():
            continue  # donated buffer: its producer has completed
        data.block_until_ready()
        if (strict and data.size
                and getattr(data, "is_fully_addressable", True)):
            # one-ELEMENT slice (O(1) device work — not ravel, which
            # would materialize a full reshaped copy per fence)
            slices.append(data[(0,) * data.ndim])
    if slices:
        jax.device_get(slices)


class NDArray:
    """A device array with eager, asynchronous semantics."""

    __slots__ = ("_data", "_ctx", "_grad", "_grad_req", "_tape_node",
                 "_tape_index", "_stype", "_fresh_grad", "__weakref__")

    def __init__(self, data, ctx=None, _stype="default"):
        if isinstance(data, NDArray):
            data = data._data
        if not isinstance(data, jax.Array):
            data = jnp.asarray(data)
        self._data = data
        self._ctx = ctx
        self._grad = None
        self._grad_req = None
        self._tape_node = None
        self._tape_index = 0
        self._stype = _stype
        # set True on the GRAD array by autograd's writeback, cleared
        # by Trainer after consuming it (the reference's _fresh_grad;
        # backs step(ignore_stale_grad=True))
        self._fresh_grad = False
        with _live_lock:
            _live_arrays.add(self)

    # ------------------------------------------------------------------
    # properties
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def size(self):
        return int(self._data.size)

    @property
    def stype(self):
        return self._stype

    @property
    def context(self):
        if self._ctx is not None:
            return self._ctx
        dev = next(iter(self._data.devices()))
        plat = dev.platform
        return Context("cpu" if plat == "cpu" else "tpu", dev.id)

    ctx = context

    @property
    def T(self):
        return invoke(_reg.get("transpose"), [self], {})[0]

    @property
    def grad(self):
        return self._grad

    # ------------------------------------------------------------------
    # sync / conversion (reference: ndarray.py:1951 asnumpy sync point)
    # ------------------------------------------------------------------
    def asnumpy(self):
        # `fence`: where a training thread blocks on the device; the
        # span that lays the step spans on a device trace's clock
        with trace_span("fence"):
            return np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise MXNetError("The current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def tolist(self):
        return self.asnumpy().tolist()

    def wait_to_read(self):
        with trace_span("fence"):
            _fence(self._data)

    wait_to_write = wait_to_read

    def astype(self, dtype, copy=True):
        return invoke(_reg.get("Cast"), [self],
                      {"dtype": dtype_name(dtype_from_name(dtype))})[0]

    def copy(self):
        return NDArray(self._data, self._ctx)

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data,
                                         other.context.jax_device)
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self._data, other.jax_device),
                           other)
        raise MXNetError("copyto: bad target %r" % (other,))

    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return self.copyto(ctx)

    def asjax(self):
        """TPU-native accessor: the underlying jax.Array (zero-copy)."""
        return self._data

    def astuple(self):
        return tuple(self.asnumpy())

    # ------------------------------------------------------------------
    # autograd
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        """Allocate gradient buffer (reference: autograd.mark_variables /
        gluon Parameter.attach_grad)."""
        self._grad = NDArray(jnp.zeros_like(self._data), self._ctx)
        self._grad_req = grad_req

    def detach(self):
        out = NDArray(self._data, self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None,
                          retain_graph=retain_graph, train_mode=train_mode)

    # ------------------------------------------------------------------
    # shape ops as methods (subset of the reference's fluent API)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = kwargs.get("shape", shape)
        return invoke(_reg.get("Reshape"), [self], {"shape": tuple(shape)})[0]

    def reshape_like(self, other):
        return invoke(_reg.get("Reshape"), [self],
                      {"shape": other.shape})[0]

    def expand_dims(self, axis):
        return invoke(_reg.get("expand_dims"), [self], {"axis": axis})[0]

    def flatten(self):
        return invoke(_reg.get("Flatten"), [self], {})[0]

    def squeeze(self, axis=None):
        return invoke(_reg.get("squeeze"), [self], {"axis": axis})[0]

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return invoke(_reg.get("transpose"), [self],
                      {"axes": axes or None})[0]

    def flip(self, axis):
        return invoke(_reg.get("flip"), [self], {"axis": axis})[0]

    def sum(self, axis=None, keepdims=False):
        return invoke(_reg.get("sum"), [self],
                      {"axis": axis, "keepdims": keepdims})[0]

    def mean(self, axis=None, keepdims=False):
        return invoke(_reg.get("mean"), [self],
                      {"axis": axis, "keepdims": keepdims})[0]

    def max(self, axis=None, keepdims=False):
        return invoke(_reg.get("max"), [self],
                      {"axis": axis, "keepdims": keepdims})[0]

    def min(self, axis=None, keepdims=False):
        return invoke(_reg.get("min"), [self],
                      {"axis": axis, "keepdims": keepdims})[0]

    def argmax(self, axis=None):
        return invoke(_reg.get("argmax"), [self], {"axis": axis})[0]

    def argmin(self, axis=None):
        return invoke(_reg.get("argmin"), [self], {"axis": axis})[0]

    def norm(self):
        return invoke(_reg.get("norm"), [self], {})[0]

    def abs(self):
        return invoke(_reg.get("abs"), [self], {})[0]

    def clip(self, a_min, a_max):
        return invoke(_reg.get("clip"), [self],
                      {"a_min": a_min, "a_max": a_max})[0]

    def slice_axis(self, axis, begin, end):
        return invoke(_reg.get("slice_axis"), [self],
                      {"axis": axis, "begin": begin, "end": end})[0]

    def take(self, indices, axis=0):
        return invoke(_reg.get("take"), [self, _as_nd(indices)],
                      {"axis": axis})[0]

    def one_hot(self, depth, **kw):
        return invoke(_reg.get("one_hot"), [self], dict(depth=depth, **kw))[0]

    def tostype(self, stype):
        from . import sparse
        return sparse.cast_storage(self, stype)

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # operators
    # ------------------------------------------------------------------
    def _binop(self, other, op_name, scalar_op_name, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return invoke(_reg.get(op_name), [a, b], {})[0]
        if isinstance(other, (int, float, bool, np.number)):
            name = ("_r" + scalar_op_name.lstrip("_")) if reverse and \
                _reg.exists("_r" + scalar_op_name.lstrip("_")) else scalar_op_name
            return invoke(_reg.get(name), [self],
                          {"scalar": float(other)
                           if not isinstance(other, bool) else other})[0]
        return NotImplemented

    def __add__(self, o):
        return self._binop(o, "broadcast_add", "_plus_scalar")

    __radd__ = __add__

    def __sub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, o):
        return self._binop(o, "broadcast_sub", "_minus_scalar", reverse=True)

    def __mul__(self, o):
        return self._binop(o, "broadcast_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, o):
        return self._binop(o, "broadcast_div", "_div_scalar", reverse=True)

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, o):
        return self._binop(o, "broadcast_mod", "_mod_scalar", reverse=True)

    def __pow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar")

    def __rpow__(self, o):
        return self._binop(o, "broadcast_power", "_power_scalar", reverse=True)

    def __neg__(self):
        return invoke(_reg.get("negative"), [self], {})[0]

    def __abs__(self):
        return invoke(_reg.get("abs"), [self], {})[0]

    def __eq__(self, o):
        if o is None:
            return False
        return self._binop(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binop(o, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, o):
        return self._binop(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binop(o, "broadcast_greater_equal",
                           "_greater_equal_scalar")

    def __lt__(self, o):
        return self._binop(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binop(o, "broadcast_lesser_equal",
                           "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __iadd__(self, o):
        out = self.__add__(o)
        self._data = out._data
        return self

    def __isub__(self, o):
        out = self.__sub__(o)
        self._data = out._data
        return self

    def __imul__(self, o):
        out = self.__mul__(o)
        self._data = out._data
        return self

    def __itruediv__(self, o):
        out = self.__truediv__(o)
        self._data = out._data
        return self

    def __bool__(self):
        if self.size == 1:
            return bool(self.asscalar())
        raise MXNetError("truth value of multi-element NDArray is ambiguous")

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of 0-d array")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------
    # indexing. NOTE: unlike the reference, basic slicing COPIES (jax
    # arrays are immutable); in-place writes rebind this NDArray's buffer.
    # ------------------------------------------------------------------
    def _conv_index(self, key):
        if isinstance(key, NDArray):
            return key._data.astype(jnp.int32)
        if isinstance(key, tuple):
            return tuple(self._conv_index(k) for k in key)
        return key

    def __getitem__(self, key):
        out = self._data[self._conv_index(key)]
        return NDArray(out, self._ctx)

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value._data
        if isinstance(key, slice) and key == slice(None):
            self._data = jnp.broadcast_to(
                jnp.asarray(value, self.dtype), self.shape)
        else:
            # the value adopts THIS array's dtype (reference setitem
            # semantics: a[0] = 9.0 into int32 stores 9) — also keeps
            # jax's scatter from warning on unsafe float->int casts
            value = jnp.asarray(value).astype(self.dtype)
            self._data = self._data.at[self._conv_index(key)].set(value)

    def __repr__(self):
        return "\n%s\n<NDArray %s @%s>" % (
            np.asarray(self._data),
            "x".join(str(s) for s in self.shape), self.context)

    # in-place fill used by initializers / optimizer states
    def _set(self, jax_value):
        """Overwrite the backing buffer, keeping the existing device
        placement (so initializers can't silently migrate a committed
        array across backends)."""
        old = self._data
        if isinstance(old, jax.Array) and isinstance(jax_value, jax.Array):
            try:
                if old.sharding != jax_value.sharding:
                    jax_value = jax.device_put(jax_value, old.sharding)
            except (AttributeError, ValueError):
                pass
        self._data = jax_value
        return self


def _as_nd(x, ctx=None, dtype=None):
    if isinstance(x, NDArray):
        return x
    return array(x, ctx=ctx, dtype=dtype)


# ---------------------------------------------------------------------------
# eager invoke: per-(op, static params) cached jit executables
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=8192)
def _compiled(op_name, hparams):
    op = _reg.get(op_name)
    params = dict(hparams)

    def run(*arrays):
        return op.fn(*arrays, **params)

    run.__name__ = "op_" + op_name      # the program's name in a trace
    return jax.jit(run)


def invoke(op, inputs, params, name=None):
    """Eager dispatch of a registered op on NDArrays.

    Returns a list of *visible* output NDArrays; hidden aux outputs (e.g.
    BatchNorm moving stats) are written back into their input arrays,
    matching the reference's mutable-aux semantics.
    """
    from .. import autograd
    from .. import random as _random

    params = _reg.apply_defaults(op, params)
    is_train = autograd.is_training()
    if op.takes_mode:
        params["_mode"] = "train" if is_train else "predict"
    hparams = _reg.hashable_params(params)

    arrays = [x._data if isinstance(x, NDArray) else jnp.asarray(x)
              for x in inputs]
    if op.needs_rng:
        arrays = [_random.next_key()] + arrays

    from .. import profiler as _prof
    prof_t0 = time.perf_counter() if _prof._active() else None

    recording = autograd.is_recording()
    if recording:
        pdict = dict(hparams)

        def fn(*arrs):
            out = op.fn(*arrs, **pdict)
            return out if isinstance(out, tuple) else (out,)

        raw, vjp_fn = jax.vjp(fn, *arrays)
    else:
        raw = _compiled(op.name, hparams)(*arrays)
        if not isinstance(raw, tuple):
            raw = (raw,)
        vjp_fn = None

    vis = op.visible_outputs
    if callable(vis):
        n_visible = vis(params)
    else:
        n_visible = vis or len(raw)
    ctx = inputs[0]._ctx if inputs and isinstance(inputs[0], NDArray) else None
    outputs = [NDArray(r, ctx) for r in raw[:n_visible]]

    # aux write-back (training mode only — eval returns unchanged stats)
    if op.aux_write and (not op.takes_mode or params.get("_mode") == "train"):
        for out_idx, in_idx in op.aux_write.items():
            tgt = inputs[in_idx]
            if isinstance(tgt, NDArray):
                tgt._data = raw[out_idx]

    if recording:
        rng_key = arrays[0] if op.needs_rng else None
        in_arrays = arrays[1:] if op.needs_rng else arrays
        autograd._record(op, inputs, outputs, raw, vjp_fn,
                         replay=fn, in_arrays=in_arrays, rng_key=rng_key)
    if prof_t0 is not None:
        _prof.record_op(op.name, prof_t0, time.perf_counter())
    from .. import engine as _engine
    _engine._naive_sync_hook(outputs)
    return outputs


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------


_NO_SPAN = contextlib.nullcontext()


def _place(arr, ctx):
    ctx = ctx or current_context()
    return NDArray(jax.device_put(arr, ctx.jax_device), ctx)


def array(source, ctx=None, dtype=None):
    if isinstance(source, NDArray):
        source = source._data
    if dtype is None:
        if isinstance(source, (np.ndarray, jax.Array)):
            dtype = source.dtype
            if dtype == np.float64:
                dtype = np.float32
            if dtype == np.int64:
                dtype = np.int32
        else:
            dtype = np.float32
    # `input.stage`: a host batch copied to its context on the
    # consumer's thread
    with (trace_span("input.stage") if isinstance(source, np.ndarray)
          else _NO_SPAN):
        arr = jnp.asarray(np.asarray(source, dtype=dtype_from_name(dtype)))
        return _place(arr, ctx)


def zeros(shape, ctx=None, dtype="float32", stype=None, **kw):
    if isinstance(shape, int):
        shape = (shape,)
    if stype not in (None, "default"):
        from . import sparse
        return sparse.zeros(stype, shape, ctx=ctx, dtype=dtype)
    return _place(jnp.zeros(shape, dtype_from_name(dtype)), ctx)


def ones(shape, ctx=None, dtype="float32", **kw):
    if isinstance(shape, int):
        shape = (shape,)
    return _place(jnp.ones(shape, dtype_from_name(dtype)), ctx)


def full(shape, val, ctx=None, dtype="float32", **kw):
    if isinstance(shape, int):
        shape = (shape,)
    return _place(jnp.full(shape, val, dtype_from_name(dtype)), ctx)


def empty(shape, ctx=None, dtype="float32"):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    arr = jnp.arange(start, stop, step, dtype_from_name(dtype))
    if repeat != 1:
        arr = jnp.repeat(arr, repeat)
    return _place(arr, ctx)


def zeros_like(other):
    return NDArray(jnp.zeros_like(other._data), other._ctx)


def ones_like(other):
    return NDArray(jnp.ones_like(other._data), other._ctx)


def concatenate(arrays, axis=0, always_copy=True):
    return NDArray(jnp.concatenate([a._data for a in arrays], axis=axis),
                   arrays[0]._ctx)


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor._data, source, destination),
                   tensor._ctx)


def waitall():
    """Block until all pending computation completes (reference:
    MXNDArrayWaitAll -> Engine::WaitForAll). A TRUE fence: blocks on the
    current buffer of every live NDArray (JAX async dispatch), flushes
    effectful computations, and drains the native host engine."""
    with _live_lock:
        snapshot = list(_live_arrays)
    _fence_many([arr._data for arr in snapshot])
    jax.effects_barrier()
    from .. import engine as _engine
    _engine._waitall_native()


def imdecode(buf, **kw):
    raise MXNetError("imdecode: use mxnet_tpu.image")


# ---------------------------------------------------------------------------
# serialization (reference: NDArray::Save/Load, python utils.py save/load)
# format: numpy .npz with a manifest — round-trips names + dtypes.
# ---------------------------------------------------------------------------


def save(fname, data):
    """Save arrays in the reference's binary .params container
    (reference: ndarray/utils.py:222 -> src/ndarray/ndarray.cc:1735);
    files round-trip with the reference framework.

    Crash-consistent: the bytes land in a same-directory temp file and
    os.replace swings the name, so a process killed mid-save (the
    preemption mode) never leaves a truncated .params blob. Covers
    model.save_checkpoint, ParameterDict.save, save_parameters."""
    from .serialization import dumps
    from ..resilience.atomic import atomic_write
    with atomic_write(fname) as f:
        f.write(dumps(data))


def load(fname):
    """Load a reference-format .params file (also reads this repo's
    older .npz checkpoints; reference: ndarray/utils.py:149)."""
    with open(fname, "rb") as f:
        buf = f.read()
    return load_frombuffer(buf)


def load_frombuffer(buf):
    """Deserialize arrays from a byte buffer
    (reference: ndarray/utils.py:185)."""
    from .serialization import loads
    if buf[:2] == b"PK":  # legacy .npz checkpoint from round 1
        import io as _io
        with np.load(_io.BytesIO(buf), allow_pickle=False) as f:
            fmt = str(f["__format__"])
            if fmt == "dict":
                return {k: array(f[k]) for k in f.files
                        if k != "__format__"}
            items = sorted((k for k in f.files if k != "__format__"),
                           key=lambda k: int(k.split("_")[1]))
            return [array(f[k]) for k in items]
    return loads(buf)
