"""Double-buffered device prefetch: overlap the host→device upload of
batch *k+1* with the jitted step running on batch *k*.

Why a separate stage: PJRT dispatch is asynchronous, but a training
loop that calls ``device_put`` (or ``nd.array``) *inline* only issues
the upload when the host thread reaches it — i.e. after the previous
step's dispatch, serializing decode+upload behind the step on the host
timeline. :class:`DevicePrefetcher` moves the pull-from-source and the
``device_put`` onto a background thread with a one-deep (configurable)
buffer, so by the time the consumer asks for batch k+1 its transfer
was issued a whole step earlier and has been overlapping compute.

The effect is largest where the upload dominates end-to-end
real-data training; docs/perf.md ("Real-data input pipeline") has an
earlier machine's measurement. Transfers are lossless — the
prefetched stream is bit-identical to the source stream
(tier-1-gated in tests/test_gluon_data.py).

Works over both batch protocols:

- ``mx.io.DataIter`` sources (e.g. ``NativeImageRecordIter``) yielding
  :class:`~mxtpu.io.DataBatch` — data/label NDArrays are re-emitted
  device-resident, numpy leaves are uploaded;
- plain iterables of numpy/jax pytrees (dict/list/tuple), as used by
  ``bench.py`` and functional train steps.
"""
from __future__ import annotations

import queue as _queue
import threading
import time as _time
from typing import Any, Iterable, Optional

import numpy as _np

from ... import telemetry

__all__ = ["DevicePrefetcher"]

_SENTINEL = object()


class DevicePrefetcher:
    """Background-thread device prefetch with a bounded buffer.

    Parameters
    ----------
    source : iterable or DataIter
        Yields batches. ``reset()``/``close()`` are forwarded when the
        source has them.
    depth : int
        Batches buffered beyond the one the consumer holds (1 = classic
        double buffering: one on device computing, one in flight).
    device : optional jax device
        Target device (default: ``jax.devices()[0]``).
    timeout : float
        Seconds the consumer waits for the producer before raising —
        a stuck decode surfaces as an error, never a silent hang.
    """

    def __init__(self, source, depth: int = 1,
                 device: Optional[Any] = None, timeout: float = 120.0):
        self._source = source
        self._depth = max(1, int(depth))
        self._device = device
        self._timeout = timeout
        # queue + stop event are created PER producer generation and
        # passed into the thread: a producer that outlives a timed-out
        # join (stuck decode) keeps its own (already-stopped) pair and
        # can never touch a successor generation's state
        self._q: Optional[_queue.Queue] = None
        self._stop: Optional[threading.Event] = None
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # the data-wait leg of the step-time split: time the CONSUMER
        # spends blocked on the producer (0 when prefetch is winning)
        self._m_wait = telemetry.histogram(
            "train_data_wait_ms",
            "Time the training loop blocked waiting for the next "
            "prefetched batch")

    # -- device placement -------------------------------------------------
    def _to_device(self, obj):
        import jax
        from ...io import DataBatch
        from ...ndarray import NDArray

        dev = self._device
        if isinstance(obj, DataBatch):
            out = DataBatch(
                data=[self._to_device(d) for d in (obj.data or [])],
                label=[self._to_device(l) for l in (obj.label or [])],
                pad=obj.pad, index=obj.index, bucket_key=obj.bucket_key,
                provide_data=obj.provide_data,
                provide_label=obj.provide_label)
            return out
        if isinstance(obj, NDArray):
            # already device-resident (nd.array device_puts at
            # construction); re-wrapping would add a device copy
            return obj
        if isinstance(obj, (_np.ndarray, _np.generic)) or \
                isinstance(obj, jax.Array):
            return jax.device_put(obj, dev)
        if isinstance(obj, dict):
            return {k: self._to_device(v) for k, v in obj.items()}
        if isinstance(obj, tuple):
            return tuple(self._to_device(v) for v in obj)
        if isinstance(obj, list):
            return [self._to_device(v) for v in obj]
        return obj

    # -- producer ---------------------------------------------------------
    @staticmethod
    def _bounded_put(q, stop, item) -> bool:
        # give up when the consumer is gone so close() can't deadlock
        # against a full queue
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def _producer(self, q, stop):
        try:
            for batch in self._source:
                if stop.is_set():
                    return
                if not self._bounded_put(q, stop, self._to_device(batch)):
                    return
        except StopIteration:
            pass
        except Exception as e:          # surfaced on the consumer side
            self._bounded_put(q, stop, e)
        self._bounded_put(q, stop, _SENTINEL)

    def _ensure_started(self):
        if self._closed:
            raise RuntimeError("DevicePrefetcher is closed")
        if self._thread is None:
            self._stop = threading.Event()
            self._q = _queue.Queue(maxsize=self._depth)
            self._thread = threading.Thread(
                target=self._producer, args=(self._q, self._stop),
                daemon=True, name="mxtpu-device-prefetch")
            self._thread.start()

    def _stop_producer(self):
        if self._stop is not None:
            self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            # drain so a blocked put() notices the stop event promptly
            try:
                while True:
                    self._q.get_nowait()
            except _queue.Empty:
                pass
            t.join(timeout=30)
        self._q = None

    # -- consumer protocol ------------------------------------------------
    def __iter__(self):
        self._ensure_started()
        return self

    def __next__(self):
        self._ensure_started()
        t0 = _time.perf_counter()
        try:
            item = self._q.get(timeout=self._timeout)
            if item is not _SENTINEL:      # epoch-end is not data wait
                self._m_wait.observe(1e3 * (_time.perf_counter() - t0))
        except _queue.Empty:
            raise RuntimeError(
                f"DevicePrefetcher: no batch from source within "
                f"{self._timeout}s (stuck decode/upload?)") from None
        if item is _SENTINEL:
            self._thread = None         # epoch done; reset() restarts
            raise StopIteration
        if isinstance(item, Exception):
            self._stop_producer()
            raise item
        return item

    next = __next__                     # DataIter spelling

    def reset(self):
        """End the current epoch (if mid-flight), reset the source, and
        restart prefetch lazily on the next pull. The source must be
        resettable: silently resuming a plain iterator mid-stream would
        drop the in-flight buffered batches."""
        mid_flight = self._thread is not None
        self._stop_producer()
        if hasattr(self._source, "reset"):
            self._source.reset()
        elif mid_flight:
            raise RuntimeError(
                "DevicePrefetcher.reset(): source has no reset() and an "
                "epoch is mid-flight — buffered batches would be lost. "
                "Wrap a resettable iterator (DataIter/DataLoader) to use "
                "reset().")

    def close(self):
        """Stop the producer, drain the buffer, close the source."""
        if self._closed:
            return
        self._closed = True
        self._stop_producer()
        if hasattr(self._source, "close"):
            self._source.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __getattr__(self, name):
        # delegate metadata (provide_data/provide_label/batch_size/...)
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.__dict__["_source"], name)
