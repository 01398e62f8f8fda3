"""Host→device double buffering for input pipelines.

Reference role: src/io/iter_prefetcher.h (PrefetcherIter — a
background thread keeps `prefetch_buffer` batches decoded ahead of the
consumer) and the device-staging half of the reference's
`--use-device-mem` training loops.

TPU-native design: `jax.device_put` is asynchronous (the host→HBM DMA
runs in the background), so true double buffering only needs the
*iterator pull + staging call* off the critical path: a daemon thread
pulls batch k+1..k+depth from the (possibly slow: JPEG decode,
augmentation) iterator and issues their device_put with the right
`NamedSharding` while step k executes. The consumer then dispatches
step k+1 on buffers whose transfer has already started — or finished.
"""
from __future__ import annotations

import queue
import threading
import time

from ..observability import registry as _obs
from ..observability import trace as _trace

__all__ = ["DevicePrefetcher"]

_END = object()

# same histogram io.DataIter.__next__ feeds: a blocking get() here is
# the consumer stalled on input, wherever the wrapping happened
_BATCH_WAIT = _obs.histogram("io.batch_wait.seconds",
                             "Time the consumer blocked waiting for a batch")


class DevicePrefetcher:
    """Iterate `source`, running `stage(item)` on a background thread,
    keeping up to `depth` staged items ready (reference:
    iter_prefetcher.h, default buffer depth 4; here 2 = classic double
    buffering).

    Exceptions in the source/stage propagate to the consumer at the
    point of `next()`. The thread is a daemon and also shuts down
    cleanly via `close()` (or exhausting the iterator).
    """

    def __init__(self, source, stage=None, depth=2):
        if depth < 1:
            raise ValueError("DevicePrefetcher: depth must be >= 1")
        self._source = iter(source)
        self._stage = stage or (lambda x: x)
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _worker(self):
        from ..observability.telemetry import mark_producer_thread
        mark_producer_thread()
        try:
            for n, item in enumerate(self._source):
                # the staging thread runs ahead of the step that will
                # eat the batch: a context of its own, one a batch
                with _trace.trace_span(
                        "input.stage",
                        ctx=_trace.step_trace_context("input", n),
                        batch=n):
                    staged = self._stage(item)
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
            self._q.put(_END)
        except BaseException as e:  # noqa: BLE001 — handed on to consumer
            self._q.put(e)

    def __iter__(self):
        return self

    def __next__(self):
        if self._stop.is_set():
            raise StopIteration
        t0 = time.perf_counter()
        with _trace.trace_span("input.wait"):
            item = self._q.get()
        _BATCH_WAIT.observe(time.perf_counter() - t0)
        if item is _END:
            self._stop.set()
            raise StopIteration
        if isinstance(item, BaseException):
            self._stop.set()
            raise item
        return item

    next = __next__  # DataIter-style alias

    def close(self, timeout=2.0):
        """Stop the background thread without draining the source.

        Joins the worker (bounded wait) so that by the time close()
        returns no stale worker can still pull from the shared source —
        fit() re-wraps the same DataIter next epoch, and a lingering
        worker would race its reset()/next() and swallow a batch.
        """
        import time as _time
        import warnings
        self._stop.set()
        deadline = _time.monotonic() + timeout
        while self._thread.is_alive() and _time.monotonic() < deadline:
            # unblock a worker waiting on a full queue, repeatedly: it may
            # complete one more put after each drain before seeing _stop
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(0.1)
        if self._thread.is_alive():
            warnings.warn(
                "DevicePrefetcher.close: worker still blocked in the source "
                "after %.1fs; it may consume one more batch before exiting"
                % timeout, RuntimeWarning)
            return False
        return True


def stage_databatch(batch):
    """Stage one io.DataBatch's arrays onto the default device (the
    stage fn Module.fit uses; sharded trainers use
    ShardedTrainer.prefetched, which also applies input shardings).

    Returns a NEW DataBatch: iterators that recycle one batch object
    (the reference PrefetcherIter copies into its own buffers for the
    same reason) must not see batch k's arrays swapped while the
    consumer still trains on them."""
    if isinstance(batch, list):  # pre-sliced multi-batch: stage each
        return [stage_databatch(b) for b in batch]
    if not hasattr(batch, "data"):
        return batch
    import jax
    import jax.numpy as jnp
    from ..io import DataBatch
    from ..ndarray import NDArray

    def put(x):
        arr = x._data if isinstance(x, NDArray) else jnp.asarray(x)
        return NDArray(jax.device_put(arr))

    return DataBatch(
        data=([put(d) for d in batch.data]
              if batch.data is not None else None),
        label=([put(d) for d in batch.label]
               if batch.label is not None else None),
        pad=batch.pad, index=batch.index,
        bucket_key=getattr(batch, "bucket_key", None),
        provide_data=getattr(batch, "provide_data", None),
        provide_label=getattr(batch, "provide_label", None))
