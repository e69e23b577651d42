"""DataLoader (ref python/mxnet/gluon/data/dataloader.py:27-131).

Reference parity: batchify, samplers, num_workers, process workers. Two
worker modes (selected by ``thread_pool`` like the reference):

- thread_pool=True: a thread pool feeds a bounded prefetch queue — cheap
  when __getitem__ releases the GIL (IO, native decode) or transforms are
  jax ops.
- thread_pool=False: spawned PROCESS workers (the reference's
  multiprocessing+shared-memory pipeline, dataloader.py:27-131). The
  dataset/batchify are pickled to each worker once; workers run pure
  numpy/PIL transforms GIL-free and return host batches the parent uploads.
  Workers force JAX_PLATFORMS=cpu and never touch the TPU (spawn, not fork:
  forking a process with live TPU handles is unsafe).

Host→device transfer overlaps with compute because jax.device_put is async.
A C++ RecordIO/decode pipeline (native/) backs the heavy image path.
"""
from __future__ import annotations

import pickle

from concurrent.futures import ThreadPoolExecutor
from queue import Queue

import numpy as onp

from ... import ndarray as nd
from ...ndarray import NDArray
from .sampler import BatchSampler, RandomSampler, SequentialSampler

__all__ = ["DataLoader", "default_batchify_fn"]

_MP_DATASET = None
_MP_BATCHIFY = None


def _mp_init(ds_bytes, bf_bytes):
    import os
    # workers must come up clean on CPU — the parent owns the chip (one
    # process per chip), and no distributed init
    os.environ.pop("MXTPU_COORD_ADDR", None)
    os.environ["JAX_PLATFORMS"] = "cpu"
    global _MP_DATASET, _MP_BATCHIFY
    _MP_DATASET = pickle.loads(ds_bytes)
    _MP_BATCHIFY = pickle.loads(bf_bytes)


def _np_tree(x):
    if isinstance(x, NDArray):
        return x.asnumpy()
    if isinstance(x, dict):
        return {k: _np_tree(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return type(x)(_np_tree(i) for i in x)
    return onp.asarray(x)


def _mp_worker_fn(indices):
    batch = _MP_BATCHIFY([_MP_DATASET[i] for i in indices])
    return _np_tree(batch)  # host arrays cross the pipe; parent uploads


def _nd_tree(x):
    if isinstance(x, dict):
        return {k: _nd_tree(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return type(x)(_nd_tree(i) for i in x)
    return nd.array(x)


def default_batchify_fn(data):
    """Stack samples into a batch (ref dataloader.py default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return nd.stack(*data, axis=0)
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = onp.asarray(data)
    return nd.array(data)


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None, thread_pool=True,
                 timeout=120):
        self._mp_pool = None  # before any raise: __del__ reads it
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler is specified")
            batch_sampler = BatchSampler(sampler, batch_size, last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or last_batch:
            raise ValueError("batch_size, shuffle, sampler and last_batch must "
                             "not be specified if batch_sampler is specified.")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = num_workers
        self._thread_pool = thread_pool
        self._timeout = timeout
        self._prefetch = max(0, int(prefetch) if prefetch is not None else 2 * num_workers)

    def _make_batch(self, indices):
        return self._batchify_fn([self._dataset[i] for i in indices])

    def _get_mp_pool(self):
        if self._mp_pool is None:
            import multiprocessing
            import os
            ctx = multiprocessing.get_context("spawn")
            # spawn snapshots the PARENT env at Pool() time, and the package
            # __init__ the child imports (to unpickle) initializes TPU /
            # jax.distributed from these vars — sanitize BEFORE spawning,
            # restore after (the _mp_init cleanup would run too late)
            drop = ("MXTPU_COORD_ADDR", "MXTPU_NUM_PROC", "MXTPU_PROC_ID")
            saved = {k: os.environ.pop(k) for k in drop if k in os.environ}
            saved_jp = os.environ.get("JAX_PLATFORMS")
            os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                self._mp_pool = ctx.Pool(
                    self._num_workers, initializer=_mp_init,
                    initargs=(pickle.dumps(self._dataset),
                              pickle.dumps(self._batchify_fn)))
            finally:
                os.environ.update(saved)
                if saved_jp is None:
                    os.environ.pop("JAX_PLATFORMS", None)
                else:
                    os.environ["JAX_PLATFORMS"] = saved_jp
        return self._mp_pool

    def __iter__(self):
        if self._num_workers == 0:
            for batch in self._batch_sampler:
                yield self._make_batch(batch)
            return
        if not self._thread_pool:
            yield from self._iter_multiprocess()
            return
        # threaded pipeline with bounded prefetch (≙ PrefetcherIter double-buffer)
        with ThreadPoolExecutor(max_workers=self._num_workers) as pool:
            futures = Queue()
            batches = iter(self._batch_sampler)

            def submit_next():
                try:
                    b = next(batches)
                except StopIteration:
                    return False
                futures.put(pool.submit(self._make_batch, b))
                return True

            live = 0
            for _ in range(max(1, self._prefetch)):
                if submit_next():
                    live += 1
                else:
                    break
            while live:
                f = futures.get()
                live -= 1
                if submit_next():
                    live += 1
                yield f.result()

    def _iter_multiprocess(self):
        """Process workers: ordered async map with bounded in-flight window."""
        pool = self._get_mp_pool()
        batches = iter(self._batch_sampler)
        inflight = []

        def submit_next():
            try:
                b = next(batches)
            except StopIteration:
                return False
            inflight.append(pool.apply_async(_mp_worker_fn, (list(b),)))
            return True

        for _ in range(max(2, self._prefetch)):
            if not submit_next():
                break
        while inflight:
            res = inflight.pop(0)
            out = res.get(self._timeout)
            submit_next()
            yield _nd_tree(out)

    def __del__(self):
        if self._mp_pool is not None:
            try:
                self._mp_pool.terminate()
            except Exception:
                pass  # interpreter shutdown: pool internals already torn down

    def __len__(self):
        return len(self._batch_sampler)
