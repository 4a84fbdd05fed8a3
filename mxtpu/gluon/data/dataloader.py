"""DataLoader (reference ``python/mxnet/gluon/data/dataloader.py``
[path cite]).

Worker model, mirroring the reference:

- ``num_workers == 0`` — load in the iterating thread (with optional
  background prefetch threads via ``prefetch``).
- ``num_workers > 0, thread_pool=True`` — threaded prefetch pipeline.
  On this 1-core box (and generally under PJRT, where the device owns
  transfers) this is the recommended fast path.
- ``num_workers > 0, thread_pool=False`` — REAL worker processes (the
  reference's multiprocessing pool + shared-memory NDArray IPC).
  Workers batchify with ``default_mp_batchify_fn`` (numpy — worker
  children must not touch the PJRT device) and ship batches back to
  the parent, which converts to NDArray. Datasets must yield
  numpy-convertible samples on this path; use ``thread_pool`` for
  datasets whose transforms need device ops.

  Workers start via the ``forkserver`` context by default: ``fork`` of
  a JAX-initialized (multithreaded) parent can deadlock in the child
  regardless of what the dataset holds, so the dataset + batchify_fn
  are instead pickled to freshly-started workers. Set
  ``MXTPU_MP_START_METHOD=fork`` to ride copy-on-write for huge
  unpicklable datasets — at your own risk, and before JAX dispatches
  work.
"""
from __future__ import annotations

import multiprocessing as _mp
import os as _os
import queue as _queue
import threading
from typing import Callable, List, Optional

import numpy as _np

from ... import ndarray as nd
from ...ndarray import NDArray
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, Sampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]


def default_batchify_fn(data):
    """Stack samples into a batch (reference ``default_batchify_fn``)."""
    if isinstance(data[0], NDArray):
        return nd.stack(*data)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    out = _np.asarray(data)
    return nd.array(out, dtype=out.dtype)


def default_mp_batchify_fn(data):
    """Worker-side batchify: numpy only (reference's variant built
    shared-memory NDArrays; forked children here must stay off the
    PJRT device, so batches cross the process boundary as numpy)."""
    if isinstance(data[0], tuple):
        return [default_mp_batchify_fn(list(i)) for i in zip(*data)]
    arrs = [x.asnumpy() if isinstance(x, NDArray) else _np.asarray(x)
            for x in data]
    return _np.stack(arrs)


def _np_to_nd(batch):
    if isinstance(batch, (list, tuple)):
        return [_np_to_nd(b) for b in batch]
    if isinstance(batch, _np.ndarray):
        return nd.array(batch, dtype=batch.dtype)
    return batch


# worker-process globals (set once per worker by the fork initializer —
# the reference passes the dataset the same way, riding fork COW)
_worker_dataset = None
_worker_batchify = None


def _worker_init(dataset, batchify_fn):
    global _worker_dataset, _worker_batchify
    # workers are numpy-only: pin any lazy jax init in this process to
    # the CPU — a chip belongs to one process, the trainer's, and a
    # worker that tried to open it would fail or hang. A forked worker
    # inherits jax already imported, where the env var comes too late
    _os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    _worker_dataset = dataset
    _worker_batchify = batchify_fn


def _worker_fn(indices):
    samples = [_worker_dataset[i] for i in indices]
    return _worker_batchify(samples)


class DataLoader:
    """Iterates a Dataset in mini-batches with background prefetch."""

    def __init__(self, dataset: Dataset, batch_size: Optional[int] = None,
                 shuffle: bool = False, sampler: Optional[Sampler] = None,
                 last_batch: Optional[str] = None,
                 batch_sampler: Optional[BatchSampler] = None,
                 batchify_fn: Optional[Callable] = None,
                 num_workers: int = 0, pin_memory: bool = False,
                 prefetch: Optional[int] = None, thread_pool: bool = False,
                 timeout: int = 120):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError(
                    "batch_size is required when batch_sampler is not given")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle and sampler are exclusive")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError(
                "batch_size/shuffle/sampler/last_batch are exclusive with "
                "batch_sampler")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._thread_pool = thread_pool
        self._mp = self._num_workers > 0 and not thread_pool
        self._batchify_fn = batchify_fn or (
            default_mp_batchify_fn if self._mp else default_batchify_fn)
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * max(1, num_workers))
        self._timeout = timeout

    def __len__(self):
        return len(self._batch_sampler)

    def _make_batch(self, indices) -> object:
        samples = [self._dataset[i] for i in indices]
        return self._batchify_fn(samples)

    def _check_mp_safe(self):
        """Probe ONE sample in the parent: device-backed samples
        (anywhere in a nested tuple/list/dict sample) would make the
        worker child touch the PJRT client (deadlock risk on TPU) —
        fail loudly with the fix instead."""
        import jax
        if len(self._dataset) == 0 or jax.default_backend() == "cpu":
            return

        def has_nd(x):
            if isinstance(x, NDArray):
                return True
            if isinstance(x, (tuple, list)):
                return any(has_nd(i) for i in x)
            if isinstance(x, dict):
                return any(has_nd(v) for v in x.values())
            return False

        if has_nd(self._dataset[0]):
            raise ValueError(
                "DataLoader(num_workers>0) runs worker processes, but "
                "this dataset yields device-backed NDArrays — worker "
                "children must not touch the TPU. Use thread_pool=True "
                "or make the dataset/transforms yield numpy.")

    @property
    def _pool(self):
        """Worker pool, started once and reused across epochs (the
        reference creates its pool in __init__). forkserver by default
        (see module docstring); MXTPU_MP_START_METHOD overrides."""
        pool = getattr(self, "_pool_cache", None)
        if pool is None:
            method = _os.environ.get("MXTPU_MP_START_METHOD")
            if not method:
                method = ("forkserver"
                          if "forkserver" in _mp.get_all_start_methods()
                          else "fork")
            ctx = _mp.get_context(method)
            # children capture the env at process start: force CPU so
            # neither the forkserver process nor a worker ever opens
            # the accelerator client (see _worker_init)
            old = _os.environ.get("JAX_PLATFORMS")
            _os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                pool = ctx.Pool(self._num_workers,
                                initializer=_worker_init,
                                initargs=(self._dataset,
                                          self._batchify_fn))
            finally:
                if old is None:
                    _os.environ.pop("JAX_PLATFORMS", None)
                else:
                    _os.environ["JAX_PLATFORMS"] = old
            self._pool_cache = pool
        return pool

    def _track_workers(self) -> None:
        """Remember every worker Process the pool has ever run: the
        pool's maintenance thread reaps+replaces dead workers, so by
        the time a timeout fires the corpse may be gone from
        ``pool._pool`` — but the Process objects keep their exitcode."""
        reg = getattr(self, "_worker_registry", None)
        if reg is None:
            reg = self._worker_registry = {}
        pool = getattr(self, "_pool_cache", None)
        if pool is not None:
            for p in list(getattr(pool, "_pool", [])):
                reg[p.pid] = p

    def _dead_worker_report(self) -> str:
        self._track_workers()
        purged = getattr(self, "_purged_pids", set())
        dead = sorted(
            (pid, p.exitcode)
            for pid, p in getattr(self, "_worker_registry", {}).items()
            if p.exitcode not in (None, 0) and pid not in purged)
        if not dead:
            return "no worker exited abnormally (stuck, not dead?)"
        return "dead worker exit codes: " + ", ".join(
            f"pid {pid} -> {code}" for pid, code in dead)

    def _restart_pool(self) -> None:
        pool = getattr(self, "_pool_cache", None)
        if pool is not None:
            self._track_workers()
            # workers still alive here die by OUR terminate() below —
            # blaming their SIGTERM exit code in a later report would
            # misdiagnose a stuck worker as a crashed one
            purged = getattr(self, "_purged_pids", None)
            if purged is None:
                purged = self._purged_pids = set()
            purged.update(p.pid for p in list(getattr(pool, "_pool", []))
                          if p.is_alive())
            try:
                pool.terminate()
            except Exception:
                pass
            self._pool_cache = None

    def __del__(self):
        pool = getattr(self, "_pool_cache", None)
        if pool is not None:
            try:
                pool.terminate()
            except Exception:
                pass

    def _iter_multiprocess(self):
        """Forked worker pool: batches built in child processes
        (numpy), converted to NDArray in the parent — the reference's
        multiprocessing DataLoader shape. A bounded window of
        apply_async tasks gives backpressure (imap would eagerly
        compute and buffer the whole epoch) while preserving batch
        order.

        Dead-worker recovery: a worker that dies (``os._exit``, OOM
        kill, segfault) takes its in-flight task with it — mp.Pool
        replaces the worker but never completes the task, so the
        result surfaces as a timeout. On the FIRST timeout the loader
        restarts the pool and resubmits every pending batch once; a
        second timeout on the same batch raises, naming the dead
        workers' exit codes."""
        import collections
        self._check_mp_safe()
        pool = self._pool
        window = max(self._prefetch, self._num_workers)
        # (indices, async_result): indices are kept so pending work
        # can be resubmitted to a fresh pool after a worker death
        pending = collections.deque()
        sampler_it = iter(self._batch_sampler)

        def fill():
            try:
                while len(pending) < window:
                    indices = next(sampler_it)
                    pending.append(
                        (indices,
                         pool.apply_async(_worker_fn, (indices,))))
            except StopIteration:
                pass
            finally:
                # register BEFORE any worker can die: a crash between
                # submission and the timeout report must find its
                # Process handle (and exit code) in the registry
                self._track_workers()

        fill()
        retried = False
        while pending:
            indices, res = pending[0]
            try:
                batch = res.get(self._timeout)
            except _mp.TimeoutError:
                report = self._dead_worker_report()
                if retried:
                    raise RuntimeError(
                        f"DataLoader worker timed out after "
                        f"{self._timeout}s twice for one batch "
                        f"({report})")
                retried = True
                # one recovery attempt: fresh pool, resubmit all
                # pending batches in order (completed-but-unread
                # results from the old pool are recomputed — cheaper
                # than reasoning about which worker died holding what)
                self._restart_pool()
                pool = self._pool
                pending = collections.deque(
                    (idx, pool.apply_async(_worker_fn, (idx,)))
                    for idx, _ in pending)
                continue
            retried = False
            pending.popleft()
            fill()
            yield _np_to_nd(batch)

    def __iter__(self):
        if self._mp:
            yield from self._iter_multiprocess()
            return
        if self._prefetch == 0:
            for indices in self._batch_sampler:
                yield self._make_batch(indices)
            return
        q: _queue.Queue = _queue.Queue(maxsize=self._prefetch)
        sentinel = object()
        stop = threading.Event()

        def _put(item) -> bool:
            # bounded put that gives up when the consumer is gone, so an
            # abandoned iterator (break/exception mid-epoch) can't pin
            # the producer thread + in-flight batches forever
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def _producer():
            try:
                for indices in self._batch_sampler:
                    if stop.is_set() or not _put(self._make_batch(indices)):
                        return
            except Exception as e:  # surfaced on the consumer side
                _put(e)
            _put(sentinel)

        t = threading.Thread(target=_producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get(timeout=self._timeout)
                if item is sentinel:
                    break
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()
