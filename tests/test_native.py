"""Native runtime tests: C++ engine deps/versions/exceptions, ordered
pipeline, pooled storage, RecordIO (reference test models:
tests/cpp/engine/threaded_engine_test.cc, tests/python/unittest/
test_engine.py, test_exc_handling.py, test_recordio.py)."""
import os
import struct
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import _native, engine, recordio, storage

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

pytestmark = pytest.mark.skipif(not _native.available(),
                                reason="native lib unavailable")


class TestEngine:
    def test_serialized_writes(self):
        eng = engine.native_engine()
        v = eng.new_var()
        out = []
        for i in range(50):
            eng.push(lambda i=i: out.append(i), mutable_vars=[v])
        eng.wait_for_var(v)
        assert out == list(range(50))

    def test_version_bumps_on_write_only(self):
        eng = engine.native_engine()
        v = eng.new_var()
        assert eng.var_version(v) == 0
        for _ in range(3):
            eng.push(lambda: None, mutable_vars=[v])
        eng.push(lambda: None, const_vars=[v])
        eng.wait_for_var(v)
        assert eng.var_version(v) == 3

    def test_parallel_reads_single_writer(self):
        eng = engine.native_engine()
        v = eng.new_var()
        state = {"writers": 0, "max_readers": 0, "readers": 0}
        lock = threading.Lock()

        def read():
            with lock:
                state["readers"] += 1
                state["max_readers"] = max(state["max_readers"],
                                           state["readers"])
                assert state["writers"] == 0
            time.sleep(0.002)
            with lock:
                state["readers"] -= 1

        def write():
            with lock:
                assert state["readers"] == 0
                assert state["writers"] == 0
                state["writers"] += 1
            time.sleep(0.002)
            with lock:
                state["writers"] -= 1

        for _ in range(5):
            for _ in range(4):
                eng.push(read, const_vars=[v])
            eng.push(write, mutable_vars=[v])
        eng.wait_for_var(v)

    def test_read_after_write_sees_data(self):
        eng = engine.native_engine()
        v = eng.new_var()
        box = {}
        eng.push(lambda: box.setdefault("x", 41), mutable_vars=[v])
        got = []
        eng.push(lambda: got.append(box["x"] + 1), const_vars=[v])
        eng.wait_all()
        assert got == [42]

    def test_exception_deferred_to_wait(self):
        eng = engine.native_engine()
        v = eng.new_var()

        def boom():
            raise ValueError("deliberate failure")

        eng.push(boom, mutable_vars=[v])
        with pytest.raises(ValueError, match="deliberate failure"):
            eng.wait_for_var(v)

    def test_waitall_raises_global_exception(self):
        eng = engine.native_engine()
        v = eng.new_var()
        eng.push(lambda: (_ for _ in ()).throw(RuntimeError("async fail")),
                 mutable_vars=[v])
        with pytest.raises(RuntimeError, match="async fail"):
            eng.wait_all()
        eng.wait_all()  # exception consumed; engine still serviceable

    def test_independent_vars_run_concurrently(self):
        eng = engine.native_engine()
        va, vb = eng.new_var(), eng.new_var()
        barrier = threading.Barrier(2, timeout=5)
        # two ops on independent vars must overlap (both reach the barrier)
        eng.push(barrier.wait, mutable_vars=[va])
        eng.push(barrier.wait, mutable_vars=[vb])
        eng.wait_all()

    def test_module_level_push_api(self):
        out = []
        v = engine.new_var()
        engine.push(lambda: out.append(1), mutable_vars=[v])
        engine.wait_for_var(v)
        assert out == [1]


class TestPipeline:
    def test_ordered_results(self):
        pipe = _native.NativePipeline(num_threads=4, capacity=8)
        delays = [0.01, 0.0, 0.005, 0.0, 0.002, 0.0]
        for i, d in enumerate(delays):
            pipe.submit(lambda i=i, d=d: (time.sleep(d), i)[1])
        got = [pipe.pop() for _ in delays]
        assert got == list(range(len(delays)))
        pipe.close()

    def test_task_exception_raised_at_pop(self):
        pipe = _native.NativePipeline(num_threads=2, capacity=4)
        pipe.submit(lambda: 1)
        pipe.submit(lambda: (_ for _ in ()).throw(KeyError("bad sample")))
        assert pipe.pop() == 1
        with pytest.raises(KeyError):
            pipe.pop()
        pipe.close()


class TestStorage:
    def test_alloc_free_reuse(self):
        h1 = storage.alloc(1000)
        p1 = h1.ptr
        storage.free(h1)
        h2 = storage.alloc(1000)  # same pow2 bucket -> reused
        assert h2.ptr == p1
        storage.free(h2)

    def test_numpy_view_roundtrip(self):
        h = storage.alloc(256 * 4)
        arr = h.as_numpy(np.float32, (16, 16))
        arr[:] = np.arange(256, dtype=np.float32).reshape(16, 16)
        arr2 = h.as_numpy(np.float32, (16, 16))
        np.testing.assert_array_equal(arr, arr2)
        storage.direct_free(h)

    def test_stats(self):
        s0 = storage.stats()
        h = storage.alloc(4096)
        s1 = storage.stats()
        assert s1["used_bytes"] >= s0["used_bytes"] + 4096
        storage.free(h)

    def test_empty_pinned(self):
        arr, h = storage.empty_pinned((8, 8), np.float32)
        arr[:] = 7.0
        assert arr.sum() == 448.0
        assert h.ptr % 64 == 0  # 64B aligned for fast DMA
        storage.direct_free(h)


class TestRecordIO:
    def test_roundtrip_native(self, tmp_path):
        path = str(tmp_path / "t.rec")
        payloads = [bytes([i]) * (i * 7 + 1) for i in range(20)]
        w = recordio.MXRecordIO(path, "w")
        for p in payloads:
            w.write(p)
        w.close()
        r = recordio.MXRecordIO(path, "r")
        got = []
        while True:
            rec = r.read()
            if rec is None:
                break
            got.append(rec)
        r.close()
        assert got == payloads

    def test_wire_format_is_dmlc(self, tmp_path):
        """The native writer must produce [magic][lrec][payload][pad]."""
        path = str(tmp_path / "w.rec")
        w = recordio.MXRecordIO(path, "w")
        w.write(b"abcde")
        w.close()
        raw = open(path, "rb").read()
        magic, lrec = struct.unpack("<II", raw[:8])
        assert magic == 0xCED7230A
        assert lrec & ((1 << 29) - 1) == 5
        assert raw[8:13] == b"abcde"
        assert len(raw) == 16  # padded to 4B

    def test_indexed_random_access(self, tmp_path):
        rec = str(tmp_path / "i.rec")
        idx = str(tmp_path / "i.idx")
        w = recordio.MXIndexedRecordIO(idx, rec, "w")
        for i in range(10):
            w.write_idx(i, f"payload-{i}".encode())
        w.close()
        r = recordio.MXIndexedRecordIO(idx, rec, "r")
        assert r.read_idx(7) == b"payload-7"
        assert r.read_idx(2) == b"payload-2"
        r.close()

    def test_pack_unpack_header(self):
        hdr = recordio.IRHeader(0, 3.0, 42, 0)
        s = recordio.pack(hdr, b"data")
        hdr2, payload = recordio.unpack(s)
        assert payload == b"data"
        assert hdr2.label == 3.0 and hdr2.id == 42


def test_checkpoint_io_through_engine(tmp_path):
    """save_parameters pushes the .npz write through the native engine
    (IO thread); load barriers on the path var (VERDICT r1 weak #10 —
    checkpoint IO is now an engine consumer)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import engine, gluon

    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    path = str(tmp_path / "ck.params")
    net.save_parameters(path)       # async behind the engine
    net2 = gluon.nn.Dense(4, in_units=3)
    net2.load_parameters(path)      # waits for the write, then reads
    onp.testing.assert_allclose(net.weight.data().asnumpy(),
                                net2.weight.data().asnumpy())
    # repeated writes to one path serialize; waitall drains them
    for _ in range(3):
        net.save_parameters(path)
    engine.waitall()
    net2.load_parameters(path)


def test_export_imports_races_async_save(tmp_path):
    """export() pushes the params write async; SymbolBlock.imports must
    barrier before reading (code-review regression)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(2))
    net.initialize()
    net.hybridize()
    x = mx.np.array(onp.random.RandomState(0).rand(2, 4).astype("f"))
    y_ref = net(x).asnumpy()
    sym_file, _ = net.export(str(tmp_path / "m"))
    # immediately import — no explicit waitall between
    blk = gluon.SymbolBlock.imports(sym_file, ["data"])
    onp.testing.assert_allclose(y_ref, blk(x).asnumpy(), rtol=1e-5,
                                atol=1e-5)


def test_nd_save_load_async_barrier(tmp_path):
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu.ndarray.utils import load, save

    path = str(tmp_path / "arrs")
    data = {"a": mx.np.ones((4,)), "b": mx.np.zeros((2, 2))}
    save(path, data)           # async
    out = load(path)           # barriers on the path var
    onp.testing.assert_allclose(out["a"].asnumpy(), onp.ones(4))


def test_priority_scheduling_order():
    """Higher-priority ops run first when queued (reference:
    ThreadedEnginePerDevice priority queues, threaded_engine_perdevice.cc).
    Runs in a 1-worker subprocess so queue order is observable."""
    import subprocess
    import sys

    script = r"""
import jax; jax.config.update("jax_platforms", "cpu")
import threading
from mxnet_tpu import engine

eng = engine.native_engine()
assert eng is not None
gate = threading.Event()
started = threading.Event()
order = []
blocker_var = eng.new_var()
# occupy the single worker so subsequent pushes stack in the queue — and
# WAIT until it is occupied: on a loaded host the worker can wake late,
# and a blocker still queued next to op 0 (equal priority) may lose the tie
def blocker():
    started.set()
    gate.wait()
eng.push(blocker, mutable_vars=[blocker_var])
assert started.wait(60), "worker never picked up the blocker"
vars_ = [eng.new_var() for _ in range(4)]
for i, prio in enumerate([0, 5, -3, 9]):
    eng.push(lambda i=i: order.append(i), mutable_vars=[vars_[i]],
             priority=prio)
gate.set()
engine.waitall()
# expected: priority 9 (op 3), 5 (op 1), 0 (op 0), -3 (op 2)
assert order == [3, 1, 0, 2], order
print("PRIORITY OK", order)
"""
    env = dict(os.environ, MXTPU_CPU_WORKER_NTHREADS="1",
               JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    run = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "PRIORITY OK" in run.stdout
