"""DataLoader batching contracts (reference:
tests/python/unittest/test_gluon_data.py — last_batch modes, Pad/Stack
batchify, sampler exclusivity, nested-structure batching), and the
contracts of its workers: one loop over forked processes or threads, a
collated batch handed over in shared memory.
"""
import gc
import multiprocessing
import os
import pickle
import threading
import time

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.data import batchify, dataloader

rs = onp.random.RandomState(31)


def _ds(n=10):
    return gluon.data.SimpleDataset(
        [(onp.full((2,), i, "f"), i) for i in range(n)])


@pytest.mark.parametrize("mode,want_batches,last_size", [
    ("keep", 4, 1), ("discard", 3, 3), ("rollover", 3, 3)])
def test_last_batch_modes(mode, want_batches, last_size):
    loader = gluon.data.DataLoader(_ds(10), batch_size=3,
                                   last_batch=mode)
    batches = list(loader)
    assert len(batches) == want_batches
    assert batches[-1][0].shape[0] == last_size


def test_rollover_carries_remainder_to_next_epoch():
    loader = gluon.data.DataLoader(_ds(10), batch_size=3,
                                   last_batch="rollover")
    epoch1 = list(loader)        # 9 consumed, 1 rolls over
    epoch2 = list(loader)        # 1 + 10 = 11 -> 3 batches, 2 roll
    seen1 = sorted(int(v) for b in epoch1 for v in b[1].asnumpy())
    assert len(seen1) == 9
    seen2 = [int(v) for b in epoch2 for v in b[1].asnumpy()]
    assert len(seen2) == 9
    # the rolled-over sample from epoch 1 leads epoch 2
    leftover = set(range(10)) - set(seen1)
    assert seen2[0] in leftover


def test_pad_batchify_variable_length():
    data = [onp.arange(n, dtype="f") for n in (2, 5, 3)]
    out = batchify.Pad(val=-1)(data)
    assert out.shape == (3, 5)
    got = out.asnumpy()
    onp.testing.assert_array_equal(got[0], [0, 1, -1, -1, -1])
    onp.testing.assert_array_equal(got[1], [0, 1, 2, 3, 4])


def test_pad_axis_and_dtype():
    data = [onp.zeros((2, n), "f") for n in (1, 4)]
    out = batchify.Pad(axis=1, val=9, dtype="int32")(data)
    assert out.shape == (2, 2, 4)
    assert out.asnumpy().dtype == onp.int32
    assert (out.asnumpy()[0, :, 1:] == 9).all()


def test_group_batchify_in_loader():
    ds = gluon.data.SimpleDataset(
        [(onp.arange(n, dtype="f"), n) for n in (1, 2, 3, 4)])
    loader = gluon.data.DataLoader(
        ds, batch_size=2,
        batchify_fn=batchify.Group(batchify.Pad(), batchify.Stack()))
    xb, yb = next(iter(loader))
    assert xb.shape[0] == 2 and xb.shape[1] == 2  # padded to batch max
    assert yb.shape == (2,)


def test_batch_sampler_excludes_batch_size():
    sampler = gluon.data.BatchSampler(
        gluon.data.SequentialSampler(7), batch_size=3, last_batch="keep")
    with pytest.raises((ValueError, TypeError)):
        gluon.data.DataLoader(_ds(7), batch_size=3,
                              batch_sampler=sampler)
    loader = gluon.data.DataLoader(_ds(7), batch_sampler=sampler)
    sizes = [b[0].shape[0] for b in loader]
    assert sizes == [3, 3, 1]


def test_shuffle_covers_all_samples():
    loader = gluon.data.DataLoader(_ds(12), batch_size=4, shuffle=True)
    seen = sorted(int(v) for b in loader for v in b[1].asnumpy())
    assert seen == list(range(12))


def test_nested_dict_structure_batching():
    ds = gluon.data.SimpleDataset(
        [{"x": onp.full((3,), i, "f"), "y": i} for i in range(4)])
    loader = gluon.data.DataLoader(ds, batch_size=2)
    batch = next(iter(loader))
    assert isinstance(batch, dict)
    assert batch["x"].shape == (2, 3)
    assert batch["y"].shape == (2,)


def test_dict_sample_with_ndarray_not_forked(monkeypatch):
    """A dict sample holding device arrays must be classified NOT
    fork-safe (a forked child of a jax-initialized parent must stay off
    jax: the device belongs to the parent)."""
    ds = gluon.data.SimpleDataset(
        [{"x": mx.np.array([1.0, 2.0]), "y": 0} for _ in range(4)])
    loader = gluon.data.DataLoader(ds, batch_size=2, num_workers=2)
    assert "device arrays" in loader._thread_bound()
    batch = next(iter(loader))  # thread workers, works
    assert batch["x"].shape == (2, 2)
    assert not loader._slots


# -- workers ------------------------------------------------------------------

KINDS = {"process": {}, "thread": {"thread_pool": True}}
kinds = pytest.mark.parametrize("kind", sorted(KINDS))


@pytest.fixture(autouse=True)
def _strays_exempt():
    """A thread that an earlier test file of this process left running
    would turn every loader here to thread workers: it carries the
    exempt name while a test of this file runs."""
    strays = [t for t in threading.enumerate()
              if t is not threading.main_thread()
              and not t.name.startswith("mxtpu-")]
    for t in strays:
        t.name = "mxtpu-stray-" + t.name
    yield
    for t in strays:
        t.name = t.name[len("mxtpu-stray-"):]


def _same(got, want):
    """Same containers, same leaf types, same dtypes, same bits."""
    assert type(got) is type(want)
    if isinstance(want, (tuple, list)):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _same(g, w)
    elif isinstance(want, dict):
        assert list(got) == list(want)
        for k in want:
            _same(got[k], want[k])
    elif isinstance(want, (mx.nd.NDArray, onp.ndarray)):
        g, w = onp.asarray(got), onp.asarray(want)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    else:
        assert got == want


def _values(n, dtype):
    v = onp.random.RandomState(n).uniform(-100, 100, (n, 3))
    return (v > 0) if dtype == "bool" else v.astype(dtype)


class _Samples:
    """`form` of sample `i` from row `i` of `rows`, made on demand (what a
    forked worker runs), after `delay(i)` seconds."""

    def __init__(self, rows, form, delay=None):
        self.rows, self.form, self.delay = rows, form, delay

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        if self.delay:
            time.sleep(self.delay(i))
        row = self.rows[i]
        return {"array": lambda: row,
                "tuple": lambda: (row, i, row[:1]),
                "dict": lambda: {"x": row, "id": onp.int64(i)},
                "ragged": lambda: (onp.resize(row, i % 5 + 1), i),
                }[self.form]()


_PAD = batchify.Group(batchify.Pad(val=7), batchify.Stack())


@kinds
@pytest.mark.parametrize("form", ["array", "tuple", "dict", "ragged"])
@pytest.mark.parametrize(
    "dtype", ["float32", "float64", "float16", "int64", "uint8", "bool"])
def test_worker_batches_equal_the_synchronous_loader(kind, form, dtype):
    ds = _Samples(_values(22, dtype), form)
    fn = _PAD if form == "ragged" else None
    want = list(gluon.data.DataLoader(ds, batch_size=4, batchify_fn=fn))
    loader = gluon.data.DataLoader(ds, batch_size=4, batchify_fn=fn,
                                   num_workers=3, **KINDS[kind])
    got = list(loader)
    assert bool(loader._slots) == (kind == "process")
    _same(got, want)


def _first_rows(data):
    return onp.stack([d[:1] for d in data])


@kinds
def test_user_batchify_keeps_its_numpy_leaves(kind):
    """A callable from outside batchify.py runs in the worker as it is;
    what it returns as NumPy arrives as NumPy, beside the NDArrays of
    this package's own functions."""
    ds = _Samples(_values(12, "float32"), "array")
    fn = batchify.Group(batchify.Stack(), _first_rows)
    ds2 = gluon.data.SimpleDataset([(r, r) for r in ds.rows])
    want = list(gluon.data.DataLoader(ds2, batch_size=4, batchify_fn=fn))
    assert isinstance(want[0][1], onp.ndarray)
    loader = gluon.data.DataLoader(ds2, batch_size=4, batchify_fn=fn,
                                   num_workers=2, **KINDS[kind])
    _same(list(loader), want)
    assert bool(loader._slots) == (kind == "process")


def test_batchify_yielding_device_arrays_runs_in_threads():
    ds = _Samples(_values(8, "float32"), "array")
    loader = gluon.data.DataLoader(
        ds, batch_size=4, num_workers=2,
        batchify_fn=lambda data: mx.np.array(onp.stack(data)) * 2)
    assert "device arrays" in loader._thread_bound()
    got = onp.concatenate([b.asnumpy() for b in loader])
    onp.testing.assert_array_equal(got, ds.rows * 2)
    assert not loader._slots


def test_worker_result_is_places_not_bytes():
    """What crosses the pool's pipe for a 4 MB batch: under 4 KB."""
    rows = onp.random.RandomState(0).rand(64, 128, 128).astype("float32")
    ds = gluon.data.SimpleDataset([(r, i) for i, r in enumerate(rows)])
    slot = dataloader._Slot()
    try:
        dataloader._worker_init(
            ds, batchify.host_half(batchify.default_batchify_fn), [slot])
        out = dataloader._worker_fn(list(range(64)), 0)
        assert rows.nbytes == 4 << 20
        assert len(pickle.dumps(out)) < 4096
        sent = []
        data, label = slot.unpack(out, sent)
        assert len(sent) == 2
        onp.testing.assert_array_equal(data.asnumpy(), rows)
        onp.testing.assert_array_equal(label.asnumpy(), onp.arange(64))
    finally:
        dataloader._worker_init()
        os.close(slot.fd)


@kinds
def test_order_kept_when_workers_finish_out_of_order(kind):
    # the first batch of every four is the slowest of them
    ds = _Samples(onp.arange(48, dtype="float32").reshape(48, 1), "array",
                  delay=lambda i: 0.03 if i % 8 < 2 else 0.0)
    loader = gluon.data.DataLoader(ds, batch_size=2, num_workers=4,
                                   **KINDS[kind])
    for _epoch in range(2):
        got = onp.concatenate([b.asnumpy() for b in loader])
        onp.testing.assert_array_equal(got, ds.rows)


@kinds
def test_early_batch_keeps_its_values(kind):
    """A slot is written again only after its batch has reached the
    device: a batch drawn early still holds its values after more than
    `prefetch` + 2 others were drawn."""
    ds = _Samples(_values(60, "float32"), "array")
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                   prefetch=2, **KINDS[kind])
    it = iter(loader)
    first, second = next(it), next(it)
    later = [next(it) for _ in range(2 + 2 + 3)]
    onp.testing.assert_array_equal(first.asnumpy(), ds.rows[:4])
    onp.testing.assert_array_equal(second.asnumpy(), ds.rows[4:8])
    onp.testing.assert_array_equal(later[-1].asnumpy(), ds.rows[32:36])
    it.close()


def _left_behind():
    """(mappings of a loader's memory in this process, bytes its memfds
    hold, memfds open)."""
    gc.collect()
    with open("/proc/self/maps") as f:
        maps = sum("memfd:mxtpu-loader" in line for line in f)
    sizes = []
    for fd in os.listdir("/proc/self/fd"):
        try:
            if "memfd:mxtpu-loader" in os.readlink(f"/proc/self/fd/{fd}"):
                sizes.append(os.fstat(int(fd)).st_size)
        except OSError:       # listdir's own descriptor
            pass
    return maps, sum(sizes), len(sizes)


class _Faulty(_Samples):
    def __getitem__(self, i):
        if i == 13:
            raise KeyError("sample 13 is broken")
        return super().__getitem__(i)


@pytest.mark.parametrize(
    "ending", ["exhaustion", "break", "del", "raise", "timeout"])
def test_nothing_of_the_loader_is_left(ending):
    assert _left_behind() == (0, 0, 0)
    rows = _values(40, "float32")
    ds = {"raise": _Faulty(rows, "array"),
          "timeout": _Samples(rows, "array",
                              delay=lambda i: 20 if i == 13 else 0),
          }.get(ending, _Samples(rows, "array"))
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                   timeout=1.0)
    it = iter(loader)
    first = next(it)
    maps, held, fds = _left_behind()
    assert maps >= 1 and held > 0 and fds >= 4   # four slots, some filled
    if ending == "exhaustion":
        assert len(list(it)) == 9
        # the pool and its (empty) slots stay for the next epoch
        assert _left_behind() == (0, 0, 4)
        assert len(multiprocessing.active_children()) == 2
        return
    if ending == "break":
        it.close()
    elif ending == "del":
        del it, loader
    else:
        error = KeyError if ending == "raise" \
            else multiprocessing.TimeoutError
        with pytest.raises(error):
            list(it)
    assert _left_behind() == (0, 0, 0)
    assert not multiprocessing.active_children()
    onp.testing.assert_array_equal(first.asnumpy(), rows[:4])


@kinds
@pytest.mark.parametrize("failure", ["raise", "timeout"])
def test_loader_works_again_after_a_failed_epoch(kind, failure):
    ds = _Faulty(_values(16, "float32"), "array") if failure == "raise" \
        else _Samples(_values(16, "float32"), "array",
                      delay=lambda i: 3 if i == 13 else 0)
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                   timeout=0.5, **KINDS[kind])
    with pytest.raises(KeyError if failure == "raise"
                       else multiprocessing.TimeoutError):
        list(loader)
    assert loader._pool is None
    ds.__class__, ds.delay = _Samples, None
    assert len(list(loader)) == 4


@kinds
def test_second_epoch_reuses_the_pool(kind):
    ds = _Samples(_values(12, "float32"), "array")
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                   **KINDS[kind])
    epoch1 = list(loader)
    pool = loader._pool
    children = multiprocessing.active_children()
    assert pool is not None
    _same(list(loader), epoch1)
    assert loader._pool is pool
    assert multiprocessing.active_children() == children
    assert len(children) == (2 if kind == "process" else 0)


@kinds
def test_native_library_changes_nothing(kind, monkeypatch):
    """The loader does not ask whether libmxtpu.so loaded (it did once,
    and a checkout without a compiler got another loader)."""
    from mxnet_tpu import _native

    x = onp.arange(64, dtype=onp.float32).reshape(32, 2)
    ds = gluon.data.ArrayDataset(x, onp.arange(32, dtype=onp.int32))

    def batches():
        return list(gluon.data.DataLoader(ds, batch_size=4, num_workers=3,
                                          **KINDS[kind]))

    with_it = batches()
    monkeypatch.setattr(_native, "available", lambda: False)
    monkeypatch.setattr(_native, "NativePipeline", None)
    without = batches()
    assert len(without) == 8
    _same(without, with_it)
    onp.testing.assert_array_equal(
        onp.concatenate([b[0].asnumpy() for b in without]), x)


def _pool_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("mxtpu-data-")]


@kinds
def test_a_loaders_threads_do_not_unfork_the_next_loader(kind):
    """Every thread a pool starts carries the mxtpu- name that the census
    of `_thread_bound` exempts."""
    ds = _Samples(_values(8, "float32"), "array")
    first = gluon.data.DataLoader(ds, batch_size=4, num_workers=2,
                                  **KINDS[kind])
    assert len(list(first)) == 2
    assert _pool_threads()
    second = gluon.data.DataLoader(ds, batch_size=4, num_workers=2)
    assert second._thread_bound() is None
    assert len(list(second)) == 2 and second._slots
    del first, second
    gc.collect()
    deadline = time.monotonic() + 5
    while _pool_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not [t for t in _pool_threads() if "worker" not in t.name]


@pytest.mark.parametrize("name,forks", [("mxtpu-watchdog", True),
                                        ("someone-elses", False)])
def test_census_of_threads_decides_fork_or_threads(name, forks):
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name=name, daemon=True)
    t.start()
    try:
        ds = _Samples(_values(8, "float32"), "array")
        loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2)
        if forks:
            assert loader._thread_bound() is None
            got = list(loader)
        else:
            with pytest.warns(RuntimeWarning, match="someone-elses"):
                got = list(loader)
        assert bool(loader._slots) == forks
        _same(got, list(gluon.data.DataLoader(ds, batch_size=4)))
    finally:
        stop.set()
        t.join(5)
        assert not t.is_alive()


def test_deleted_variables_change_nothing(monkeypatch):
    """MXTPU_MP_START and MXTPU_DEVICE_PREFETCH are gone: set, the loader
    forks as the census says and prefetches nothing to the device."""
    from mxnet_tpu import env
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setenv("MXTPU_MP_START", "spawn")
    monkeypatch.setenv("MXTPU_DEVICE_PREFETCH", "2")
    assert not {"MXTPU_MP_START", "MXTPU_DEVICE_PREFETCH"} & set(
        env.all_vars())
    ds = _Samples(_values(8, "float32"), "array")
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2)
    mx.telemetry.enable()
    try:
        base = ti.data_prefetch_total.value
        assert len(list(loader)) == 2
        assert ti.data_prefetch_total.value == base
    finally:
        mx.telemetry.disable()
    assert type(loader._pool[0]._ctx).__name__ == "ForkContext"


def test_more_workers_than_cores_keep_every_batch():
    """Time-bounded stress: 3x the cores' worth of forked workers over
    two slots, ragged batches so that the slots grow while in use."""
    workers = 3 * (os.cpu_count() or 2)
    rows = onp.arange(997 * 3, dtype="float32").reshape(997, 3)
    ds = _Samples(rows, "ragged")
    want = list(gluon.data.DataLoader(ds, batch_size=5, batchify_fn=_PAD))
    loader = gluon.data.DataLoader(ds, batch_size=5, batchify_fn=_PAD,
                                   num_workers=workers, prefetch=2,
                                   timeout=60)
    t0 = time.monotonic()
    for _epoch in range(2):
        _same(list(loader), want)
    assert time.monotonic() - t0 < 60
    assert len(loader._slots) == 2
