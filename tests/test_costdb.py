"""Measurement plane (observability/measure.py + costdb.py;
docs/performance.md "measured vs modeled"): MXTPU_MEASURE unset/off is
bitwise-identical with zero extra jit traces and an empty CostDB;
on_compile measures the whole-step program; the CostDB round-trips across processes through merge-on-load;
a monkeypatched byte model trips the cost_drift flight event and shows
up in opsd /costdb, diagnose --passes, and a postmortem bundle.
"""
import json
import os
import sys
import urllib.request

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, np as mnp, telemetry
from mxnet_tpu.observability import costdb, flight, measure, opsd, postmortem
from mxnet_tpu.passes import memory as pmem
from mxnet_tpu.telemetry import instruments as ti

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(autouse=True)
def fresh(tmp_path, monkeypatch):
    """Every test gets its own CostDB file and a clean measurement
    plane; nothing here leaks into the shared default path."""
    monkeypatch.setenv("MXTPU_COSTDB_PATH", str(tmp_path / "costdb.jsonl"))
    monkeypatch.delenv("MXTPU_MEASURE", raising=False)
    costdb.reset()
    measure.reset()
    yield
    costdb.reset()
    measure.reset()


def _trace_count(block="whole_step"):
    return sum(c.value for labels, c in ti.jit_trace_total.series()
               if labels[0] == block)


def _train_bn_net(steps=2):
    """A whole-step workload: bf16 net with a BatchNorm +
    multi-precision SGD."""
    mx.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.Dense(4))
    net.initialize()
    net.cast("bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.05, "momentum": 0.9, "multi_precision": True})
    r = onp.random.RandomState(7)
    xs = [mnp.array(r.standard_normal((8, 128)).astype("float32"),
                    dtype="bfloat16") for _ in range(steps)]
    ys = [mnp.array(r.standard_normal((8, 4)).astype("float32"),
                    dtype="bfloat16") for _ in range(steps)]
    mx.seed(99)
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    losses = []
    for k in range(steps):
        losses.append(step(xs[k], ys[k]).asnumpy().astype("float32").copy())
    assert step.last_path == "whole_step", step.ineligible_reason()
    params = {n: p.data().asnumpy().copy()
              for n, p in sorted(net.collect_params().items())}
    return losses, params


def _normal_entry(i, bw_bytes=1_000_000):
    """A well-behaved synthetic measurement: 1e6 predicted bytes per ms
    anchors the platform's median bandwidth."""
    return {"fingerprint": f"norm{i}", "platform": "cpu",
            "block": "steady", "variant": f"v{i}",
            "wall_ms_p50": 1.0, "wall_ms_p95": 1.2,
            "predicted_bytes": bw_bytes, "time": 100.0 + i}


# -- mode resolution ---------------------------------------------------------

def test_mode_fails_closed(monkeypatch):
    for raw, want in [("", "off"), ("off", "off"), ("bogus", "off"),
                      ("on_compile", "on_compile"), ("ON", "on_compile"),
                      ("cli", "cli"), ("deferred", "cli")]:
        monkeypatch.setenv("MXTPU_MEASURE", raw)
        assert measure.mode() == want, raw
    monkeypatch.delenv("MXTPU_MEASURE")
    assert not measure.enabled()


# -- the kill switch: off is bitwise-identical and measures nothing ----------

def test_measure_off_bitwise_and_trace_parity(monkeypatch):
    telemetry.enable()
    monkeypatch.delenv("MXTPU_MEASURE", raising=False)
    t0 = _trace_count()
    unset_losses, unset_params = _train_bn_net()
    unset_traces = _trace_count() - t0

    monkeypatch.setenv("MXTPU_MEASURE", "off")
    t0 = _trace_count()
    off_losses, off_params = _train_bn_net()
    off_traces = _trace_count() - t0

    assert off_traces == unset_traces  # zero EXTRA traces under 'off'
    for a, b in zip(unset_losses, off_losses):
        onp.testing.assert_array_equal(a, b)
    for n in unset_params:
        onp.testing.assert_array_equal(unset_params[n], off_params[n]), n
    # and nothing was measured, stashed, or persisted
    assert len(costdb.db()) == 0
    assert measure.pending() == []
    assert not os.path.exists(costdb.default_path())


# -- on_compile: measure the live programs -----------------------------------

def test_on_compile_measures_whole_step(monkeypatch):
    telemetry.enable()
    monkeypatch.setenv("MXTPU_MEASURE", "on_compile")
    monkeypatch.setenv("MXTPU_MEASURE_RUNS", "2")
    monkeypatch.setenv("MXTPU_MEASURE_WARMUP", "1")
    _train_bn_net()

    entries = costdb.db().entries()
    assert entries, "on_compile run recorded nothing"
    whole = [e for e in entries if e["block"] == "whole_step"]
    assert whole, [e["block"] for e in entries]
    e = whole[0]
    assert e["platform"] == jax.default_backend()
    assert e["wall_ms_p50"] is not None and e["wall_ms_p50"] > 0
    assert e["wall_ms_p95"] >= e["wall_ms_p50"]
    assert int(e["predicted_bytes"]) > 0
    assert int(e["predicted_peak_bytes"]) > 0
    assert len(e["fingerprint"]) == 16

    # the auditor published the program's drift gauge
    gauges = {labels for labels, _ in ti.cost_model_drift_ratio.series()}
    assert ("program", f"{e['block']}/{e['variant']}") in gauges
    # measurement counted + flight-evented
    assert sum(c.value for labels, c in ti.cost_measure_total.series()
               if labels[0] == "whole_step") >= 1
    # and persisted: a fresh "process" (new CostDB) sees the entry
    other = costdb.CostDB(costdb.default_path())
    assert other.get(e["fingerprint"], e["platform"]) is not None


def test_on_compile_entry_fingerprint_is_stable(monkeypatch):
    monkeypatch.setenv("MXTPU_MEASURE", "on_compile")
    monkeypatch.setenv("MXTPU_MEASURE_RUNS", "1")
    monkeypatch.setenv("MXTPU_MEASURE_WARMUP", "0")
    f = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    x = jnp.ones((32, 32), jnp.float32)
    e1 = measure.measure_callable(f, (x,), block="b", variant="v")
    # same structure, different callable object and buffer
    g = jax.jit(lambda y: jnp.tanh(y) * 2.0)
    e2 = measure.measure_callable(
        g, (jnp.zeros((32, 32), jnp.float32),), block="b", variant="v")
    assert e1["fingerprint"] == e2["fingerprint"]
    assert len(costdb.db()) == 1  # same (fingerprint, platform) key


# -- cli mode: stash now, sweep later ----------------------------------------

def test_cli_mode_stashes_then_sweeps(monkeypatch):
    monkeypatch.setenv("MXTPU_MEASURE", "cli")
    monkeypatch.setenv("MXTPU_MEASURE_RUNS", "2")
    f = jax.jit(lambda x: (x * x).sum(axis=-1))
    measure.maybe_register("blk", "v1", f, (jnp.ones((64, 64)),))
    assert measure.pending() == ["blk/v1"]
    assert len(costdb.db()) == 0  # nothing measured yet
    entries = measure.sweep()
    assert [e["block"] for e in entries] == ["blk"]
    assert measure.pending() == []
    assert costdb.db().get(entries[0]["fingerprint"],
                           entries[0]["platform"]) is not None


def test_registration_does_not_pin_large_buffers(monkeypatch):
    monkeypatch.setenv("MXTPU_MEASURE", "cli")
    big = jnp.ones((256, 256), jnp.float32)  # 256 KiB > SMALL_LEAF_BYTES
    small = jnp.float32(3.0)
    measure.maybe_register("blk", "spec", jax.jit(lambda a, b: a + b),
                           (big, small))
    rec = measure._pending[("blk", "spec")]
    assert isinstance(rec["args"][0], jax.ShapeDtypeStruct)
    assert not isinstance(rec["args"][1], jax.ShapeDtypeStruct)


# -- persistence: atomic file, merge-on-load across processes ----------------

def test_costdb_roundtrip_across_processes(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COSTDB_AUTOSAVE", "0")
    path = str(tmp_path / "shared.jsonl")
    a = costdb.CostDB(path)
    a.put(_normal_entry(0))
    a.save()
    # "process" B starts later, loads A's entry, adds its own
    b = costdb.CostDB(path)
    assert b.get("norm0", "cpu") is not None
    b.put(_normal_entry(1))
    b.save()
    # A saves an entry of its own: save() re-merges, so B's survives
    a.put(_normal_entry(2))
    a.save()
    c = costdb.CostDB(path)
    assert len(c) == 3
    assert {e["fingerprint"] for e in c.entries()} == \
        {"norm0", "norm1", "norm2"}


def test_costdb_newest_wins_and_tolerates_torn_lines(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_COSTDB_AUTOSAVE", "0")
    path = str(tmp_path / "db.jsonl")
    d = costdb.CostDB(path)
    d.put(dict(_normal_entry(0), wall_ms_p50=1.0, time=100.0))
    d.put(dict(_normal_entry(0), wall_ms_p50=2.0, time=200.0))  # newer
    d.put(dict(_normal_entry(0), wall_ms_p50=9.0, time=50.0))   # stale
    assert d.get("norm0", "cpu")["wall_ms_p50"] == 2.0
    d.save()
    # a crashed writer leaves a torn line; loads must skip it
    with open(path, "a") as f:
        f.write('{"fingerprint": "torn", "pla\n')
        f.write("not json at all\n")
    d2 = costdb.CostDB(path)
    assert len(d2) == 1
    assert d2.get("norm0", "cpu")["wall_ms_p50"] == 2.0


def test_costdb_autosave_follows_env(monkeypatch):
    monkeypatch.setenv("MXTPU_COSTDB_AUTOSAVE", "1")
    d = costdb.db()
    d.put(_normal_entry(0))
    assert os.path.exists(costdb.default_path())


# -- drift auditing ----------------------------------------------------------

def test_drift_report_self_calibrates():
    entries = [_normal_entry(i) for i in range(3)]
    # 4x the median bandwidth: hot, but within the default 8x threshold
    entries.append(dict(_normal_entry(9), fingerprint="hot",
                        predicted_bytes=4_000_000))
    rep = costdb.drift_report(entries=entries)
    assert rep["calibration"]["cpu"] == pytest.approx(1_000_000, rel=0.5)
    by_fp = {r["fingerprint"]: r for r in rep["programs"]}
    assert by_fp["norm0"]["drift_ratio"] == pytest.approx(1.0, rel=0.2)
    assert by_fp["hot"]["drift_ratio"] == pytest.approx(4.0, rel=0.2)
    assert not rep["tripped"]
    # the same outlier trips a tighter threshold, in either direction
    rep = costdb.drift_report(entries=entries, threshold=2.0)
    assert [r["fingerprint"] for r in rep["tripped"]] == ["hot"]
    slow = dict(_normal_entry(9), fingerprint="cold",
                predicted_bytes=100_000)
    rep = costdb.drift_report(entries=entries + [slow], threshold=2.0)
    assert {r["fingerprint"] for r in rep["tripped"]} == {"hot", "cold"}


def test_mispredicted_program_trips_everywhere(monkeypatch, tmp_path):
    """The acceptance spine: a deliberately mis-predicted program
    (monkeypatched byte model) trips a cost_drift flight event visible
    in opsd /costdb, diagnose --passes, and a postmortem bundle."""
    telemetry.enable()
    flight.reset()
    monkeypatch.setenv("MXTPU_MEASURE", "on_compile")
    monkeypatch.setenv("MXTPU_MEASURE_RUNS", "1")
    monkeypatch.setenv("MXTPU_MEASURE_WARMUP", "0")
    # three honest measurements anchor the platform median...
    for i in range(3):
        costdb.db().put(dict(_normal_entry(i),
                             platform=jax.default_backend()))
    # ...then the byte model lies about the next program by ~9 orders
    monkeypatch.setattr(
        pmem, "estimate_region_bytes",
        lambda closed, **kw: [{"eqns": 1, "external_bytes": 10 ** 15,
                               "input_bytes": 0, "output_bytes": 0,
                               "prims": {}}])
    entry = measure.measure_callable(
        jax.jit(lambda x: x + 1.0), (jnp.ones((16, 16), jnp.float32),),
        block="suspect", variant="v0")
    assert entry["predicted_bytes"] == 10 ** 15

    rep = costdb.drift_report()
    assert any(r["program"] == "suspect/v0" for r in rep["tripped"])
    # flight event, fired once (measure_callable already ran audit())
    costdb.audit()
    evs = [e for e in flight.events(kind="cost_drift")
           if e.get("program") == "suspect/v0"]
    assert len(evs) == 1, "cost_drift must fire once per program"
    assert evs[0]["drift_ratio"] > rep["threshold"]
    # opsd payload + live endpoint
    payload = opsd.costdb_payload()
    assert "suspect/v0" in [r["program"] for r in payload["tripped"]]
    s = opsd.OpsServer(port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{s.port}/costdb?n=8", timeout=5) as r:
            served = json.loads(r.read().decode())
    finally:
        s.stop()
    assert "suspect/v0" in [r["program"] for r in served["tripped"]]
    assert served["entries"]  # newest-n entry view rode along
    # diagnose --passes report section
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import diagnose

    crep = diagnose._costdb_report()
    assert "suspect/v0" in crep["tripped"]
    # postmortem bundle carries the measurement cache + drift join
    bundle = postmortem.build_bundle("drift-test")
    assert "suspect/v0" in [r["program"]
                            for r in bundle["costdb"]["drift"]["tripped"]]
    assert any(e["fingerprint"] == entry["fingerprint"]
               for e in bundle["costdb"]["entries"])


def test_audit_never_raises_on_garbage():
    rep = costdb.audit(entries=[{"predicted_bytes": "nan-ish",
                                 "wall_ms_p50": None}])
    assert rep["programs"] == [] and rep["tripped"] == []
