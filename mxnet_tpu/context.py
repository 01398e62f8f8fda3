"""Device contexts.

Reference: include/mxnet/base.h:133 (Context) and python/mxnet/context.py.
TPU-native: a Context names a jax.Device. `tpu()` is the first-class
accelerator; `gpu()` is accepted as an alias for accelerator code written
against the reference API. The with-statement scoping semantics
(`with mx.Context(...)`) are preserved.
"""
from __future__ import annotations

import threading

import jax

from .base import MXNetError

_local = threading.local()


class Context:
    """A device context. devtype in {'cpu', 'tpu', 'gpu'} ('gpu' aliases 'tpu'
    when no GPU backend exists, which is the normal case here)."""

    devtype2mask = {"cpu": 1, "gpu": 2, "tpu": 2, "cpu_pinned": 3, "cpu_shared": 5}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in self.devtype2mask:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = int(device_id)
        self._old_ctx = None

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        devs = _devices_for(self.device_type)
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s: only %d %s device(s) available"
                % (self, len(devs), self.device_type))
        return devs[self.device_id]

    def is_accelerator(self) -> bool:
        return self.device_type in ("tpu", "gpu")

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __str__ = __repr__

    # -- scoping ----------------------------------------------------------
    def __enter__(self):
        self._old_ctx = getattr(_local, "default_ctx", None)
        _local.default_ctx = self
        return self

    def __exit__(self, *exc):
        _local.default_ctx = self._old_ctx
        return False

    @classmethod
    def default_ctx(cls):
        ctx = getattr(_local, "default_ctx", None)
        if ctx is None:
            ctx = cls("cpu", 0)
            _local.default_ctx = ctx
        return ctx


_backend_guard = {"checked": False}


def _ensure_backend_alive():
    """First backend touch goes through the health watchdog: a device
    that does not answer raises a typed `DeviceUnreachable` with lease-
    holder diagnostics instead of hanging `jax.devices()` forever.
    `MXTPU_WATCHDOG_INIT_S=0` disables; every later call is one flag
    check."""
    if _backend_guard["checked"]:
        return
    from .base import getenv
    # first backend touch is also the compile entry point: activate the
    # persistent compilation cache BEFORE anything can compile, so a
    # restarted process replays executables instead of re-lowering them
    # (docs/compilation.md; MXTPU_COMPILE_CACHE=0 disables)
    from .compile.cache import enable_cache
    enable_cache()
    timeout = getenv("MXTPU_WATCHDOG_INIT_S", 180.0)
    if timeout > 0:
        from .resilience.watchdog import HealthWatchdog
        HealthWatchdog(init_timeout_s=timeout).init_devices()
    # only a successful probe latches: a DeviceUnreachable caller that
    # retries after recovery must be re-checked, not waved through
    _backend_guard["checked"] = True


def _devices_for(device_type):
    _ensure_backend_alive()
    # LOCAL devices only: in a multi-process (dist kvstore) run each
    # worker's ctx ids index its own addressable devices, like the
    # reference where every worker sees its own gpu(0)
    backend = jax.default_backend()
    if device_type.startswith("cpu"):    # cpu / cpu_pinned / cpu_shared
        if backend == "cpu":
            return jax.local_devices()
        try:
            return jax.local_devices(backend="cpu")
        except RuntimeError:
            return jax.local_devices()
    # accelerator ('tpu'/'gpu'): the default backend's devices — and no
    # fallback that hides the device: on the CPU backend there is no
    # accelerator, and code that named one must hear so
    if backend == "cpu":
        raise MXNetError(
            "context %s(...): no accelerator — the jax backend is %r "
            "(devices: %s); use mx.cpu() to run on the host on purpose"
            % (device_type, backend, jax.local_devices()[:1]))
    return jax.local_devices()


def cpu(device_id=0):
    return Context("cpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias for accelerator context, for reference-API compatibility."""
    return Context("gpu", device_id)


def num_gpus():
    return num_tpus()


def num_tpus():
    # local count, consistent with Context's local-device indexing
    if jax.default_backend() == "cpu":
        return 0
    return len(jax.local_devices())


def current_context():
    return Context.default_ctx()
