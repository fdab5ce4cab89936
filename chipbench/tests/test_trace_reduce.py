import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _small():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    return devices, [tuple(n) for n in d["notes"]], d["expect"]


def test_union_merges_nested_and_touching():
    assert trace_reduce.union([(5, 9), (0, 3), (3, 4), (6, 7), (2, 2)]) == [
        [0, 4], [5, 9]]


def test_small_trace_hand_checked():
    # busy: [1000, 20000) 19 us, [30000, 50000) 20 us (the while and its
    # nested body count once), [52000, 55000) 3 us: 42 us.  The window
    # runs 1000 -> 55000: 54 us.  Gaps: 20000 -> 30000 (10 us, the host
    # was fetching the loss) and 50000 -> 52000 (2 us, waiting).
    devices, notes, expect = _small()
    r = trace_reduce.reduce(devices, notes)
    assert r["window_s"] == pytest.approx(expect["window_s"])
    assert r["busy_s"] == pytest.approx(expect["busy_s"])
    assert dict(map(tuple, r["idle_gaps"])) == pytest.approx(
        dict(map(tuple, expect["idle_gaps"])))
    ops = dict(map(tuple, r["device_ops"]))
    assert ops == pytest.approx(dict(map(tuple, expect["device_ops"])))
    # idle share as the per-layer metric reads it
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        100 * 12 / 54)


def test_two_devices_average():
    devices, notes, _ = _small()
    devices["/device:TPU:1"] = [("fusion.1", 0, 54000)]
    r = trace_reduce.reduce(devices, notes)
    assert r["busy_s"] == pytest.approx((42e-6 + 54e-6) / 2)


def test_no_device_operation_is_nothing():
    assert trace_reduce.reduce({}, []) is None


def test_short_name():
    line = ("%fusion.49 = bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)} "
            "fusion(bf16[256,56,56,256] %x), kind=kLoop")
    assert trace_reduce.short_name(line) == "fusion.49"
    assert trace_reduce.short_name("copy.3") == "copy.3"


def test_load_reads_a_recorded_xplane(tmp_path):
    """A trace recorded here, on the CPU: the XLA client's threads stand
    in for the device, and the benchmark's annotations are found."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(2):
        with jax.profiler.TraceAnnotation("chipbench:enqueue_step"):
            y = f(x)
        y.block_until_ready()
    jax.profiler.stop_trace()
    loaded = trace_reduce.load(trace_reduce.newest_xplane(str(tmp_path)),
                               platform="cpu")
    assert [n for n, _s, _d in loaded["notes"]] == [
        "chipbench:enqueue_step"] * 2
    r = trace_reduce.reduce(loaded["devices"], loaded["notes"])
    assert r is not None and 0 < r["busy_s"] <= r["window_s"]


def test_op_seconds_and_kernels_are_there_for_single_kernel_readers():
    devices, notes, _ = _small()
    name = devices["/device:TPU:0"][0][0]
    r = trace_reduce.reduce(devices, notes, kernels=[name, "not-run"])
    assert r["kernels"] == [name]
    assert sum(r["op_s"].values()) == pytest.approx(
        sum(d for evs in devices.values() for _n, _s, d in evs) / 1e9)


def test_mfu_is_read_from_the_traced_steps_device_time():
    import flops
    import run as harness

    cfg = harness._load_json("configs", "resnet50_v1.json")
    run = {"cfg": cfg, "batch": 256, "traced_steps": 10, "platform": "tpu",
           "device_kind": "TPU v5 lite"}
    read = harness._load_reader("train_mfu_pct").read
    # 10 steps of 256 images in 1.0 s of device time, first op to last
    want = 100 * 3 * flops.resnet_v1_forward(50, 224, 1000) * 2560 / 197e12
    assert read({"window_s": 1.0}, run) == pytest.approx(want)
    assert 29 < read({"window_s": 1.0}, run) < 31
    assert read({"window_s": 1.0}, dict(run, traced_steps=0)) is None
    assert read({"window_s": 1.0}, dict(run, platform="cpu")) is None
