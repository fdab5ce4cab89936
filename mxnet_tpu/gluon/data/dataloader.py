"""DataLoader (reference: python/mxnet/gluon/data/dataloader.py:123-305).

Worker modes (matching the reference's semantics):
  * num_workers=0 — synchronous in the caller.
  * num_workers>0 (default) — multiprocessing fork workers, like the
    reference's _MultiWorkerIter: each worker loads + batchifies to plain
    numpy in its own interpreter (PIL decode and augmenters hold the GIL,
    so processes are the only way decode scales; what this path costs
    on the chip's machine is PERF.md section 5); the parent converts to
    device arrays so children never touch jax: the chip belongs to the
    parent process.
  * num_workers>0, thread_pool=True — prefetching thread pool over the
    native C++ pipeline (iter_prefetcher.h analog): right when samples
    are already numpy (no GIL-bound decode) or datasets are unpicklable.
"""
from __future__ import annotations

import multiprocessing as _mp
import queue
import threading

import numpy as _np

from ...diagnostics import spans as _spans
from ...telemetry import instruments as _telemetry
from .batchify import default_batchify_fn
from .sampler import BatchSampler, RandomSampler, SequentialSampler


# --- multiprocessing worker plumbing (reference: worker_loop,
# dataloader.py:123-305; fork start method inherits the dataset copy-on-
# write, so nothing is pickled per batch except indices out / batch back)
_WORKER_DATASET = None


def _mp_worker_init(dataset):
    global _WORKER_DATASET
    _WORKER_DATASET = dataset


def _mp_worker_fn(indices):
    """Load samples in the child; collation happens in the parent with the
    user's batchify_fn (children never create device arrays — jax stays
    un-initialized there)."""
    return [_WORKER_DATASET[i] for i in indices]

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
        self._mp_pool = None       # persistent worker pool (mp mode)
        self._fork_safe_cache = None
        self._prefetch = max(0, prefetch if prefetch is not None
                             else 2 * self._num_workers)
        # device_prefetch: keep up to N batches BEYOND the one being
        # consumed already jax.device_put to the accelerator, so the next
        # batch's host->device transfer rides the async dispatch stream
        # UNDER the current step's compute (double-buffered input
        # pipeline; docs/data.md). None defers to MXTPU_DEVICE_PREFETCH.
        self._device_prefetch = device_prefetch
        self._batchify_fn = batchify_fn or default_batchify_fn

    def _make_batch(self, indices):
        samples = [self._dataset[i] for i in indices]
        return self._batchify_fn(samples)

    def __iter__(self):
        # span-wrap each fetch so the diagnostics step table shows the
        # 'data' phase: time the training loop spends waiting on a batch
        # (pipeline-starved steps show up here, whatever the worker mode)
        it = self._iter_impl()
        depth = self._device_prefetch
        if depth is None:
            from ... import env as _env

            depth = _env.get("MXTPU_DEVICE_PREFETCH")
        if depth and depth > 0:
            it = self._device_prefetch_iter(it, int(depth))
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

        def walk(x):
            if isinstance(x, tuple):
                return tuple(walk(v) for v in x)
            if isinstance(x, list):
                return [walk(v) for v in x]
            if isinstance(x, dict):
                return {k: walk(v) for k, v in x.items()}
            return put(x)

        return walk(batch)

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
                yield self._make_batch(indices)
            return
        if not self._thread_pool and self._fork_safe():
            yield from self._mp_iter()
            return
        from ... import _native
        if _native.available():
            yield from self._native_iter()
        else:
            yield from self._threaded_iter()

    def _fork_safe(self):
        """Fork workers must never touch jax: an initialized jax is not
        fork-safe, and the chip belongs to one process — the parent.
        Probe one sample in the parent: datasets yielding device arrays
        fall back to the threaded/native path."""
        from ...ndarray.ndarray import NDArray

        def has_nd(x):
            if isinstance(x, (tuple, list)):
                return any(has_nd(i) for i in x)
            if isinstance(x, dict):  # dict samples batch per key now
                return any(has_nd(v) for v in x.values())
            return isinstance(x, NDArray)

        if self._fork_safe_cache is None:
            try:
                self._fork_safe_cache = (len(self._dataset) == 0
                                         or not has_nd(self._dataset[0]))
            except Exception:
                self._fork_safe_cache = False
        return self._fork_safe_cache

    def _mp_iter(self):
        """Multiprocessing workers (the reference's default mode,
        _MultiWorkerIter). Workers load samples; the parent collates with
        the user batchify_fn and device-puts (async H2D overlaps compute).
        Submission is windowed to `prefetch` outstanding batches
        (back-pressure, like iter_prefetcher.h) with the loader timeout."""
        import collections

        batches = list(self._batch_sampler)
        if not batches:
            return
        pool = self._ensure_pool()
        window = max(self._prefetch, 1)
        pending = collections.deque()
        try:
            submitted = 0
            while pending or submitted < len(batches):
                while submitted < len(batches) and len(pending) < window:
                    pending.append(pool.apply_async(
                        _mp_worker_fn, (batches[submitted],)))
                    submitted += 1
                samples = pending.popleft().get(timeout=self._timeout)
                yield self._batchify_fn(samples)
        except Exception:
            self._shutdown_pool()  # hung/broken workers: don't reuse
            raise

    def _ensure_pool(self):
        """Persistent worker pool, created on first epoch and reused for
        the loader's lifetime (reference: _MultiWorkerIter keeps its
        workers alive across epochs)."""
        if self._mp_pool is not None:
            return self._mp_pool
        # fork is cheap (COW dataset) but risky from a multi-threaded
        # parent (the reference accepted the same trade-off — its workers
        # fork after MXNet init). USER Python threads force spawn; jax's
        # internal threads only warn, since workers never call jax.
        # Framework service threads (all named "mxtpu-*": the watchdog
        # scanner, serving batcher, prefetch producers) don't gate the
        # choice either — a long-lived observability thread must not
        # silently flip every loader to spawn (which also requires
        # picklable datasets). That exemption is safe because the
        # subsystems those threads hold locks in (flight recorder,
        # telemetry registry, span ring, watchdog) reinstall fresh locks
        # via os.register_at_fork(after_in_child=...), so user dataset
        # code touching NDArray ops or telemetry in a forked worker
        # can't inherit a lock a service thread held mid-fork. Set
        # MXTPU_MP_START=spawn for full isolation. MXTPU_MP_START
        # overrides the heuristic either way.
        from ... import env as _env

        user_threads = [
            t for t in threading.enumerate()
            if t is not threading.main_thread()
            and not t.name.startswith("mxtpu-")]
        start = _env.get("MXTPU_MP_START") or (
            "fork" if not user_threads else "spawn")
        ctx = _mp.get_context(start)
        self._mp_pool = ctx.Pool(self._num_workers,
                                 initializer=_mp_worker_init,
                                 initargs=(self._dataset,))
        return self._mp_pool

    def _shutdown_pool(self):
        if self._mp_pool is not None:
            self._mp_pool.terminate()
            self._mp_pool.join()
            self._mp_pool = None

    def __del__(self):
        try:
            self._shutdown_pool()
        except Exception:
            pass

    def _native_iter(self):
        """Native ordered pipeline: batches decode on C++ worker threads
        (num_workers wide), pop in order with back-pressure
        (native/mxtpu_runtime.cc Pipeline; reference: _MultiWorkerIter)."""
        from ... import _native

        batches = list(self._batch_sampler)
        pipe = _native.NativePipeline(
            num_threads=self._num_workers,
            capacity=max(self._prefetch, self._num_workers))
        try:
            submitted = 0
            popped = 0
            # prime the pipeline, then steady-state: pop one / push one
            while popped < len(batches):
                while (submitted < len(batches)
                       and submitted - popped < max(self._prefetch, 1)):
                    indices = batches[submitted]
                    pipe.submit(lambda ix=indices: self._make_batch(ix))
                    submitted += 1
                try:
                    yield pipe.pop(timeout=self._timeout)
                except TimeoutError:
                    # a hung worker can't be joined — abandon, not close
                    pipe.abandon()
                    raise
                popped += 1
        finally:
            pipe.close()

    def _threaded_iter(self):
        """Prefetching thread pool (the iter_prefetcher.h analog)."""
        batches = list(self._batch_sampler)
        out_q = queue.Queue(maxsize=max(self._prefetch, 1))
        stop = threading.Event()

        def producer():
            try:
                for indices in batches:
                    if stop.is_set():
                        return
                    out_q.put(self._make_batch(indices))
            except Exception as e:  # propagate to consumer
                out_q.put(e)
            finally:
                out_q.put(StopIteration)

        t = threading.Thread(target=producer, name="mxtpu-data-producer",
                             daemon=True)
        t.start()
        try:
            while True:
                item = out_q.get(timeout=self._timeout)
                if item is StopIteration:
                    return
                if isinstance(item, Exception):
                    raise item
                yield item
        finally:
            stop.set()

    def __len__(self):
        return len(self._batch_sampler)
