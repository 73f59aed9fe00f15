"""DataLoader (reference python/mxnet/gluon/data/dataloader.py).

Worker plane, TPU-host edition.  The reference forks workers that build
batches into shared-memory NDArrays (dataloader.py:23-150); the goal is
the same here — keep Python-level decode/augment off the training
process — with one hard constraint the reference didn't have: a forked
child must NEVER touch JAX (the inherited PJRT client is not
fork-safe).  So the worker plane is **numpy-only**:

* ``num_workers > 0`` forks worker processes (fork context, Linux).
  Each worker pulls batch-index lists from a task queue, materialises
  samples, collates them into numpy arrays, and ships each array
  through ``multiprocessing.shared_memory`` — a zero-copy handoff; the
  parent wraps the block, uploads (``nd_array`` → device) and unlinks.
* Datasets consumed by multiprocess workers must yield numpy/PIL/python
  values (every file-backed dataset here does); jax-backed NDArray
  samples would require touching jax in the child and raise.
* ``thread_workers=True`` keeps the round-3 threaded pipeline (numpy/
  PIL release the GIL during decode) for datasets that do hold device
  arrays; it is also the automatic fallback where fork is unavailable.

Against a decode-bound dataset on an 8-core host the process pool
clears the GIL ceiling the thread pool hits.
"""
from __future__ import annotations

import multiprocessing as mp
import queue
import threading

import numpy as np

from ...ndarray.ndarray import NDArray, array as nd_array
from .sampler import BatchSampler, RandomSampler, SequentialSampler

try:
    from multiprocessing import shared_memory as _shm
except ImportError:          # pragma: no cover
    _shm = None


def default_batchify_fn(data):
    """Collate samples into a batch (reference default_batchify_fn)."""
    if isinstance(data[0], NDArray):
        return nd_array(np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        data = zip(*data)
        return [default_batchify_fn(i) for i in data]
    data = np.asarray(data)
    return nd_array(data)


def _np_batchify(data):
    """Numpy-only collate used inside forked workers (no jax allowed)."""
    if isinstance(data[0], NDArray):
        raise TypeError(
            "multiprocess workers cannot collate jax-backed NDArray "
            "samples (fork + PJRT); make the dataset yield numpy, or "
            "use thread_workers=True")
    if isinstance(data[0], tuple):
        return tuple(_np_batchify(list(x)) for x in zip(*data))
    if isinstance(data[0], np.ndarray):
        return np.stack(data)
    return np.asarray(data)


def _flatten_np(tree, out):
    """Flatten a nested tuple/list of numpy arrays; returns a spec."""
    if isinstance(tree, (tuple, list)):
        return ("T", [_flatten_np(t, out) for t in tree])
    out.append(np.ascontiguousarray(tree))
    return ("A", len(out) - 1)


def _unflatten(spec, leaves):
    tag, payload = spec
    if tag == "T":
        return [_unflatten(s, leaves) for s in payload]
    return leaves[payload]


def _fork_safe_sample(dataset):
    """True when dataset[0] is numpy/python all the way down — the
    requirement for forked workers (an NDArray sample means __getitem__
    touches jax, which is not fork-safe)."""
    try:
        sample = dataset[0]
    except Exception:
        return False

    def ok(v):
        if isinstance(v, NDArray):
            return False
        if isinstance(v, (tuple, list)):
            return all(ok(x) for x in v)
        return isinstance(v, (np.ndarray, np.generic, int, float, str,
                              bytes, type(None)))
    return ok(sample)


def _worker_loop(dataset, task_q, result_q):
    """Forked worker: indices in, shared-memory batches out."""
    while True:
        job = task_q.get()
        if job is None:
            return
        seq, indices = job
        try:
            arrays = []
            spec = _flatten_np(_np_batchify([dataset[i] for i in indices]),
                               arrays)
            blocks = []
            for a in arrays:
                block = _shm.SharedMemory(create=True, size=max(a.nbytes, 1))
                np.ndarray(a.shape, a.dtype, buffer=block.buf)[...] = a
                blocks.append((block.name, a.shape, str(a.dtype)))
                block.close()
                # the parent owns unlinking; keep this process's resource
                # tracker from double-unlinking at shutdown
                try:
                    from multiprocessing import resource_tracker
                    resource_tracker.unregister(block._name, "shared_memory")
                except Exception:
                    pass
            result_q.put((seq, spec, blocks, None))
        except BaseException as e:     # surface, don't hang the parent
            result_q.put((seq, None, None, "%s: %s" % (type(e).__name__, e)))


class DataLoader:
    """reference dataloader.py DataLoader."""

    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, thread_workers=None):
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size must be specified unless "
                                 "batch_sampler is specified")
            if sampler is None:
                if shuffle:
                    sampler = RandomSampler(len(dataset))
                else:
                    sampler = SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must not be specified if sampler "
                                 "is specified")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None or \
                last_batch is not None:
            raise ValueError("batch_size, shuffle, sampler and last_batch "
                             "must not be specified if batch_sampler is "
                             "specified.")
        self._batch_sampler = batch_sampler
        self._num_workers = num_workers
        self._custom_batchify = batchify_fn is not None
        self._batchify_fn = batchify_fn or default_batchify_fn
        if thread_workers is None and num_workers > 0:
            # adaptive default: process workers only where they can work
            # AND pay off — the dataset must yield fork-safe (numpy/
            # python) samples, the collate must be the default (a custom
            # batchify_fn runs in the parent's jax world), and the host
            # must have cores to spend (on a 1-core box threads win 3×)
            import os
            thread_workers = (
                (os.cpu_count() or 1) < 4
                or self._custom_batchify
                or not _fork_safe_sample(dataset))
        self._thread_workers = bool(thread_workers) or _shm is None or \
            "fork" not in mp.get_all_start_methods()

    # -- single process ----------------------------------------------------

    def _iter_sync(self):
        for batch in self._batch_sampler:
            yield self._batchify_fn([self._dataset[idx] for idx in batch])

    # -- threaded fallback (round-3 pipeline) ------------------------------

    def _iter_threads(self, batches):
        out_q = queue.Queue(maxsize=2 * self._num_workers)
        lock = threading.Lock()
        cursor = [0]

        def worker():
            while True:
                with lock:
                    if cursor[0] >= len(batches):
                        return
                    my_idx = cursor[0]
                    cursor[0] += 1
                out_q.put((my_idx, self._batchify_fn(
                    [self._dataset[i] for i in batches[my_idx]])))

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(self._num_workers)]
        for t in threads:
            t.start()
        yield from self._emit_in_order(len(batches), out_q.get)

    # -- forked workers + shared memory ------------------------------------

    def _iter_processes(self, batches):
        if self._custom_batchify:
            raise ValueError(
                "process workers collate with the default (numpy) "
                "batchify; pass thread_workers=True to combine "
                "num_workers with a custom batchify_fn")
        ctx = mp.get_context("fork")
        task_q = ctx.Queue()
        result_q = ctx.Queue()
        procs = [ctx.Process(target=_worker_loop,
                             args=(self._dataset, task_q, result_q),
                             daemon=True)
                 for _ in range(self._num_workers)]
        for p in procs:
            p.start()
        # bounded in-flight window: workers stay busy, memory stays bounded
        window = 2 * self._num_workers
        submitted = [0]
        consumed = [0]

        def submit_up_to(limit):
            while submitted[0] < min(limit, len(batches)):
                task_q.put((submitted[0], batches[submitted[0]]))
                submitted[0] += 1

        def receive():
            seq, spec, blocks, err = result_q.get()
            consumed[0] += 1
            if err is not None:
                raise RuntimeError("DataLoader worker failed: " + err)
            leaves = []
            for name, shape, dtype in blocks:
                block = _shm.SharedMemory(name=name)
                # copy OUT of the block before unlinking: device_put on
                # the CPU backend aliases host numpy buffers zero-copy,
                # and an aliased-then-unlinked block is a segfault
                host = np.array(np.ndarray(shape, np.dtype(dtype),
                                           buffer=block.buf))
                block.close()
                block.unlink()
                leaves.append(nd_array(host))
            submit_up_to(submitted[0] + 1)   # keep the window full
            return seq, _unflatten(spec, leaves)

        try:
            submit_up_to(window)
            yield from self._emit_in_order(len(batches), receive)
        finally:
            for _ in procs:
                task_q.put(None)
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    p.terminate()
            # the parent owns every segment: on error or an abandoned
            # iterator, drain undelivered results and unlink their
            # blocks so nothing is stranded in /dev/shm
            while consumed[0] < submitted[0]:
                try:
                    _, _, blocks, err = result_q.get(timeout=1)
                except Exception:
                    break
                consumed[0] += 1
                for name, _, _ in blocks or ():
                    try:
                        b = _shm.SharedMemory(name=name)
                        b.close()
                        b.unlink()
                    except FileNotFoundError:
                        pass

    @staticmethod
    def _emit_in_order(total, get_one):
        results = {}
        next_emit = 0
        while next_emit < total:
            if next_emit in results:
                yield results.pop(next_emit)
                next_emit += 1
                continue
            seq, batch = get_one()
            results[seq] = batch

    def __iter__(self):
        if self._num_workers == 0:
            yield from self._iter_sync()
            return
        batches = list(self._batch_sampler)
        if self._thread_workers:
            yield from self._iter_threads(batches)
        else:
            yield from self._iter_processes(batches)

    def __len__(self):
        return len(self._batch_sampler)
