"""DataLoader (reference: python/mxnet/gluon/data/dataloader.py:123-305).

Worker modes:
  * num_workers=0 — synchronous in the caller.
  * num_workers>0 — a pool of workers, each making whole collated batches,
    behind ONE loop that keeps `prefetch` batches outstanding and hands
    them out in the sampler's order. The workers are
      - forked processes, like the reference's _MultiWorkerIter (decode
        and augmenters hold the GIL, so processes are how they scale). A
        worker loads its samples, runs the batchify function's host half
        (NumPy alone: children never touch jax, the chip belongs to the
        parent) and writes the arrays into a slot of memory that parent
        and workers share; only their places, shapes and types cross the
        pool's pipe. The parent maps the slot and starts the copy to the
        device; the slot is written again only when that copy has arrived
        (reference: default_mp_batchify_fn, cpu_shared_storage_manager.h);
      - threads, which hand over the batch itself, where a batch can only
        be made in this process (`_thread_bound`).
"""
from __future__ import annotations

import collections
import dataclasses
import mmap
import multiprocessing as _mp
import multiprocessing.pool
import os
import threading
import warnings

import numpy as _np

from ...diagnostics import spans as _spans
from ...telemetry import instruments as _telemetry
from .batchify import (
    Staged,
    default_batchify_fn,
    host_half,
    map_leaves,
    to_nd,
)
from .sampler import BatchSampler, RandomSampler, SequentialSampler


@dataclasses.dataclass(frozen=True)
class _Leaf:
    """Where one array of a batch lies in its slot."""
    offset: int
    shape: tuple
    dtype: _np.dtype
    staged: bool


class _Slot:
    """One batch's arrays in memory that the parent and its forked
    workers share: a memfd made before the fork, so it has no name to
    leak and goes with the last process that holds it. It grows to the
    largest batch written; each side keeps its own mapping."""

    def __init__(self):
        self.fd = os.memfd_create("mxtpu-loader")
        self.map = None

    def _mapped(self, nbytes):
        if self.map is None or len(self.map) < nbytes:
            # a mapping still read (a view, a copy to the device under
            # way) lives on until its last reader lets go of it
            self.map = mmap.mmap(self.fd, 0)
        return self.map

    def pack(self, batch):
        """Worker: the batch with a _Leaf in the place of each array
        written to the slot."""
        arrays, end = [], 0

        def place(x):
            nonlocal end
            if not isinstance(x, _np.ndarray) or x.dtype.hasobject:
                return x
            offset = -(-end // 64) * 64
            end = offset + x.nbytes
            arrays.append((offset, x))
            return _Leaf(offset, x.shape, x.dtype, isinstance(x, Staged))

        tree = map_leaves(place, batch)
        end = max(end, mmap.PAGESIZE)
        if os.fstat(self.fd).st_size < end:
            os.ftruncate(self.fd, end)
        for offset, x in arrays:
            _np.copyto(_np.ndarray(x.shape, x.dtype, self._mapped(end),
                                   offset), x)
        return tree

    def unpack(self, tree, sent):
        """Parent: the batch as the loader's consumer gets it. A Staged
        array goes to the device as `batchify.to_nd` sends it, and its
        device array is added to `sent`; any other array is copied out."""
        from ...device import current_device

        aliased = current_device().jax_device.platform == "cpu"

        def take(x):
            if not isinstance(x, _Leaf):
                return x
            nbytes = x.dtype.itemsize * int(_np.prod(x.shape))
            view = _np.ndarray(x.shape, x.dtype,
                               self._mapped(x.offset + nbytes), x.offset)
            if aliased or not x.staged:
                # the CPU backend's device_put keeps the host memory
                view = view.copy()
            if not x.staged:
                return view
            nd = to_nd(view.view(Staged))
            sent.append(nd._data)
            return nd

        return map_leaves(take, tree)

    def release(self):
        """Give the memory back; the slot stays usable."""
        os.ftruncate(self.fd, 0)
        self.map = None


# --- what a forked worker holds (reference: worker_loop; fork inherits
# the dataset copy-on-write, so a task is a list of indices and a slot)
_WORKER = None


def _worker_init(*state):
    global _WORKER
    _WORKER = state


def _worker_fn(indices, slot):
    dataset, batchify, slots = _WORKER
    return slots[slot].pack(batchify([dataset[i] for i in indices]))


__all__ = ["DataLoader", "default_batchify_fn"]


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=False, timeout=120, try_nopython=None,  # noqa: ARG002
                 device_prefetch=None):
        self._dataset = dataset
        self._pin_memory = pin_memory
        self._timeout = timeout
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size required without batch_sampler")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle \
                    else SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle and sampler are exclusive")
            batch_sampler = BatchSampler(sampler, batch_size,
                                         last_batch or "keep")
        elif batch_size is not None or shuffle or sampler is not None \
                or last_batch is not None:
            # reference dataloader.py: batch_sampler owns the batching —
            # a conflicting spec is an error, not silently ignored
            raise ValueError(
                "batch_size, shuffle, sampler and last_batch must not "
                "be specified if batch_sampler is specified")
        self._batch_sampler = batch_sampler
        self._num_workers = max(0, num_workers)
        self._thread_pool = bool(thread_pool)
        self._pool = None          # made by the first epoch, then kept
        self._slots = []           # forked workers' shared memory
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        # device_prefetch: keep up to N batches BEYOND the one being
        # consumed already jax.device_put to the accelerator, so the next
        # batch's host->device transfer rides the async dispatch stream
        # UNDER the current step's compute (double-buffered input
        # pipeline; docs/data.md).
        self._device_prefetch = int(device_prefetch or 0)
        self._batchify_fn = batchify_fn or default_batchify_fn

    def __iter__(self):
        # span-wrap each fetch so the diagnostics step table shows the
        # 'data' phase: time the training loop spends waiting on a batch
        # (pipeline-starved steps show up here, whatever the worker mode)
        it = self._iter_impl()
        if self._device_prefetch > 0:
            it = self._device_prefetch_iter(it, self._device_prefetch)
        while True:
            with _spans.span("dataloader_next", cat="data"):
                try:
                    batch = next(it)
                except StopIteration:
                    return
            yield batch

    @staticmethod
    def _to_device(batch):
        """Start the batch's host->device transfer (async device_put):
        NDArray leaves re-wrap their device array, numpy leaves become
        NDArrays on device (the h2d bytes telemetry counts). Containers
        keep their shape, so delivered batches only differ from the
        un-prefetched loader by already living on the accelerator."""
        import jax

        from ...ndarray.ndarray import NDArray

        def put(x):
            if isinstance(x, NDArray):
                return NDArray(jax.device_put(x._data))
            if isinstance(x, _np.ndarray):
                _telemetry.record_transfer("h2d", x.nbytes)
                return NDArray(jax.device_put(x))
            return x

        return map_leaves(put, batch)

    def _device_prefetch_iter(self, it, depth):
        """Double-buffered device prefetch: hold the next `depth` batches
        with their device_put already ISSUED while the consumer runs the
        current step — device_put is async, so the copies overlap the
        step's compute and next(loader) returns transferred arrays
        instead of starting a transfer (docs/data.md, docs/telemetry.md:
        data_prefetch_total / data_prefetch_depth)."""
        import collections

        pending = collections.deque()

        def top_up():
            while len(pending) <= depth:
                try:
                    nxt = next(it)
                except StopIteration:
                    return
                with _spans.span("device_prefetch", cat="data"):
                    pending.append(self._to_device(nxt))
                _telemetry.record_device_prefetch(len(pending))

        top_up()
        while pending:
            batch = pending.popleft()
            # issue the NEXT transfers before handing this batch out —
            # they run on the async stream while the consumer computes
            top_up()
            yield batch

    def _iter_impl(self):
        if self._num_workers == 0:
            for indices in self._batch_sampler:
                yield self._batchify_fn([self._dataset[i] for i in indices])
            return
        batches = list(self._batch_sampler)
        if not batches:
            return
        import jax

        pool, task = self._ensure_pool()
        # `window` batches outstanding (back-pressure, like
        # iter_prefetcher.h), batch k in slot k mod window: the slot of
        # the batch handed out last is written again only after that
        # batch's copy to the device has arrived
        window = max(self._prefetch, 1)
        pending = collections.deque()
        sent = []
        done = False
        try:
            for j in range(1 - window, len(batches)):
                k = j + window - 1          # hand out batch j, start k
                jax.block_until_ready(sent)
                if k < len(batches):
                    pending.append(pool.apply_async(
                        task, (batches[k], k % window)))
                if j < 0:
                    continue
                batch = pending.popleft().get(timeout=self._timeout)
                sent = []
                if self._slots:
                    batch = self._slots[j % window].unpack(batch, sent)
                yield batch
            jax.block_until_ready(sent)
            for slot in self._slots:
                slot.release()
            done = True
        finally:
            if not done:
                # a break, a worker's exception, a timeout: workers may
                # be hung, or still writing to the slots
                self._shutdown_pool()

    def _thread_bound(self):
        """Why this loader's batches can only be made in this process, or
        None where forked workers can make them. Forked workers must
        never touch jax: an initialized jax is not fork-safe, and the
        chip belongs to one process, the parent. So one sample is made
        here and run through the batchify function's host half."""
        from ...ndarray.ndarray import NDArray

        if self._thread_pool:
            return "thread_pool=True"
        found = []

        def look(x):
            found.append(isinstance(x, NDArray))

        try:
            if len(self._dataset):
                sample = self._dataset[0]
                map_leaves(look, sample)
                if not any(found):
                    map_leaves(look, host_half(self._batchify_fn)([sample]))
        except Exception as e:
            return f"the first sample could not be made here: {e!r}"
        if any(found):
            return "samples or batchify_fn yield device arrays"
        # fork is cheap (COW dataset) but unsafe from a parent whose
        # other threads may hold a lock at that instant. jax's internal
        # threads are not Python's, and workers never call jax. Framework
        # service threads (all named "mxtpu-*": the watchdog scanner,
        # serving batcher, a pool's handlers) do not count either: the
        # subsystems they hold locks in (flight recorder, telemetry
        # registry, span ring, watchdog) reinstall fresh locks via
        # os.register_at_fork(after_in_child=...).
        others = [t.name for t in threading.enumerate()
                  if t is not threading.main_thread()
                  and not t.name.startswith("mxtpu-")]
        if others:
            warnings.warn(
                f"DataLoader: num_workers={self._num_workers} run as "
                f"threads, not forked processes, because this process "
                f"has threads of its own ({', '.join(others[:4])}) and a "
                f"fork beside them is unsafe; Python-bound loading will "
                f"not scale", RuntimeWarning, stacklevel=5)
            return "the parent has threads of its own"
        return None

    def _ensure_pool(self):
        """The worker pool and what it runs for (indices, slot), made by
        the first epoch and kept for the loader's lifetime (reference:
        _MultiWorkerIter keeps its workers alive across epochs)."""
        if self._pool is None:
            before = set(threading.enumerate())
            if self._thread_bound():
                dataset, batchify = self._dataset, self._batchify_fn
                self._pool = _mp.pool.ThreadPool(self._num_workers), \
                    lambda indices, slot: batchify(
                        [dataset[i] for i in indices])
            else:
                self._slots = [_Slot()
                               for _ in range(max(self._prefetch, 1))]
                self._pool = _mp.get_context("fork").Pool(
                    self._num_workers, initializer=_worker_init,
                    initargs=(self._dataset, host_half(self._batchify_fn),
                              self._slots)), _worker_fn
            for t in set(threading.enumerate()) - before:
                t.name = "mxtpu-data-" + t.name     # see _thread_bound
        return self._pool

    def _shutdown_pool(self):
        if self._pool is not None:
            # terminate() joins forked workers itself; a hung THREAD
            # cannot be joined, and is left behind
            self._pool[0].terminate()
            self._pool = None
        for slot in self._slots:
            os.close(slot.fd)
        self._slots = []

    def __del__(self):
        try:
            self._shutdown_pool()
        except Exception:
            pass

    def __len__(self):
        return len(self._batch_sampler)
