"""Observability plane (ISSUE-8): flight recorder, numerics pass,
postmortem bundles, fused AMP overflow check, clip_global_norm
attribution.

The acceptance spine: an injected NaN in a whole-step training run is
attributed to a specific jaxpr equation (op name + shapes + which
operand was non-finite) inside an atomic postmortem bundle, and the
flight recorder's bounded ring captures the runtime event stream every
crash path serializes.
"""
import json
import math
import os
import warnings

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, observability
from mxnet_tpu.observability import flight, numerics, postmortem
from mxnet_tpu.gluon import Trainer, TrainStep, nn


@pytest.fixture(autouse=True)
def fresh(tmp_path, monkeypatch):
    """Clean flight ring + numerics trips, bundles into tmp."""
    monkeypatch.setenv("MXTPU_FLIGHTREC_DIR", str(tmp_path))
    observability.reset()
    yield
    observability.reset()


def _net(outs=4):
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(outs))
    net.initialize()
    net.hybridize()
    return net


def _step_fixture():
    net = _net()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.05})
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), trainer)
    rs = onp.random.RandomState(0)
    x = mx.np.array(rs.rand(8, 12).astype("f"))
    y = mx.np.array(rs.rand(8, 4).astype("f"))
    return step, x, y


# -- flight recorder --------------------------------------------------------

def test_flight_ring_is_bounded_and_ordered():
    prev = flight.set_capacity(16)
    try:
        for i in range(40):
            flight.record("tick", i=i)
        evs = flight.events()
        assert len(evs) == 16
        assert [e["i"] for e in evs] == list(range(24, 40))  # newest 16
        assert all(e["kind"] == "tick" for e in evs)
        assert all("t" in e and "pc" in e and "step" in e for e in evs)
    finally:
        flight.set_capacity(prev)


def test_flight_disabled_records_nothing(monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHTREC", "0")
    assert flight.record("tick") is None
    assert flight.events() == []


def test_flight_identity_and_trace_id(monkeypatch):
    # whatever an earlier test of this worker stamped (a plan's mesh)
    monkeypatch.setattr(flight, "_identity", {})
    monkeypatch.setenv("MXTPU_JOB_ID", "jobX")
    ident = flight.identity()
    assert ident["job"] == "jobX"
    assert ident["rank"] == 0
    assert flight.trace_id(step=7) == ("jobX", 7)
    # explicit set_identity wins over env, and lands in span records
    from mxnet_tpu.diagnostics import spans

    flight.set_identity(rank=3, world=8, job="jobY")
    try:
        assert flight.identity() == {"rank": 3, "world": 8, "job": "jobY"}
        with spans.span("probe"):
            pass
        rec = spans.records()[-1]
        assert rec["job"] == "jobY" and rec["rank"] == 3
    finally:
        flight._identity.clear()
        spans._trace_ctx.clear()


def test_step_events_flow_from_trainer():
    step, x, y = _step_fixture()
    step(x, y)
    kinds = [e["kind"] for e in flight.events()]
    assert "step" in kinds
    ev = next(e for e in flight.events() if e["kind"] == "step")
    assert ev["examples"] == 8
    assert ev["lr"] == pytest.approx(0.05)


# -- numerics: step mode ----------------------------------------------------

def test_numerics_step_clean_run_matches_off(monkeypatch):
    losses = {}
    for mode in ("off", "step"):
        monkeypatch.setenv("MXTPU_NUMERICS", mode)
        mx.seed(0)
        step, x, y = _step_fixture()
        losses[mode] = float(step(x, y).asnumpy())
        assert step.last_path == "whole_step"
    # the instrumented program computes the SAME outputs
    assert losses["step"] == pytest.approx(losses["off"], rel=0, abs=0)
    assert not numerics.tripped()


def test_numerics_step_trip_bisects_and_bundles(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_NUMERICS", "step")
    step, x, y = _step_fixture()
    step(x, y)  # clean warmup
    xbad = mx.np.array(onp.full((8, 12), onp.nan, dtype="f"))
    w_before = {n: onp.asarray(p.data().asnumpy())
                for n, p in step._net.collect_params().items()}
    with pytest.raises(observability.NonFiniteError) as ei:
        step(xbad, y)
    err = ei.value
    # attributed to a specific equation with operand-level stats
    assert err.report is not None
    assert err.report["op"]  # e.g. dot_general
    assert err.report["out_shapes"]
    bad_ops = [o for o in err.report["operands"]
               if o.get("finite_frac", 1.0) < 1.0]
    assert bad_ops, "which operand was non-finite must be identified"
    # the postmortem bundle holds the bisect + the trip event
    assert err.bundle and os.path.exists(err.bundle)
    b = json.load(open(err.bundle))
    assert b["reason"] == "numerics"
    assert b["numerics_bisect"]["op"] == err.report["op"]
    assert any(e["kind"] == "numerics_trip" for e in b["events"])
    # the rejected step did NOT write back: params kept pre-step values
    for n, p in step._net.collect_params().items():
        assert onp.array_equal(onp.asarray(p.data().asnumpy()),
                               w_before[n]), n


def test_numerics_off_lets_nan_through(monkeypatch):
    monkeypatch.setenv("MXTPU_NUMERICS", "off")
    step, x, y = _step_fixture()
    step(x, y)
    xbad = mx.np.array(onp.full((8, 12), onp.nan, dtype="f"))
    loss = step(xbad, y)  # no raise — the pre-PR behavior
    assert not math.isfinite(float(loss.asnumpy()))


# -- numerics: op mode ------------------------------------------------------

def test_numerics_op_mode_attributes_block_trip(monkeypatch):
    monkeypatch.setenv("MXTPU_NUMERICS", "op")
    net = _net()
    x = mx.np.array(onp.full((2, 12), onp.inf, dtype="f"))
    net(x).asnumpy()
    numerics.effects_barrier()
    trips = numerics.trips()
    assert trips, "op mode must trip on an inf input"
    eq = trips[0].get("equation")
    assert eq and eq["op"] and eq["out_shapes"]


def test_numerics_op_mode_clean_is_silent(monkeypatch):
    monkeypatch.setenv("MXTPU_NUMERICS", "op")
    net = _net()
    x = mx.np.array(onp.ones((2, 12), dtype="f"))
    net(x).asnumpy()
    numerics.effects_barrier()
    assert not numerics.tripped()


def test_numerics_op_mode_trip_leaves_params_live(monkeypatch):
    monkeypatch.setenv("MXTPU_NUMERICS", "op")
    step, x, y = _step_fixture()
    step(x, y)  # clean warmup
    w_before = {n: onp.asarray(p.data().asnumpy())
                for n, p in step._net.collect_params().items()}
    xbad = mx.np.array(onp.full((8, 12), onp.nan, dtype="f"))
    with pytest.raises(observability.NonFiniteError):
        step(xbad, y)
    # every active mode disables donation: the rejected step raised
    # before writeback, so the containers must still hold LIVE pre-step
    # buffers a caller that catches the error can read and resume on
    for n, p in step._net.collect_params().items():
        assert onp.array_equal(onp.asarray(p.data().asnumpy()),
                               w_before[n]), n
    loss = step(x, y)  # resume on the same containers
    assert math.isfinite(float(loss.asnumpy()))


def test_numerics_unrecognized_value_is_off(monkeypatch):
    for raw in ("none", "1", "true", "stepp"):
        monkeypatch.setenv("MXTPU_NUMERICS", raw)
        assert numerics.mode() == "off"
    # pass installation and the step-boundary poll share normalize():
    # a value that installs no NumericsPass behaves exactly like 'off'
    # (no donation opt-out, no barrier) and a NaN sails through
    monkeypatch.setenv("MXTPU_NUMERICS", "none")
    step, x, y = _step_fixture()
    step(x, y)
    xbad = mx.np.array(onp.full((8, 12), onp.nan, dtype="f"))
    loss = step(xbad, y)  # no raise
    assert not math.isfinite(float(loss.asnumpy()))


# -- bisect interpreter -----------------------------------------------------

def test_bisect_finds_first_bad_equation():
    import jax.numpy as jnp

    def f(a):
        b = a * 2.0          # fine
        c = jnp.log(b)       # log(-2) -> nan, the first bad eqn
        return jnp.sum(c * 3.0)

    rep = numerics.bisect_callable(f, jnp.array([-1.0, 1.0]))
    assert rep is not None
    assert rep["op"] == "log"
    assert rep["first_bad_output"] == 0
    assert rep["operands"][0]["finite_frac"] == 1.0  # input WAS finite
    assert "log" in numerics.format_report(rep)


def test_bisect_clean_program_returns_none():
    import jax.numpy as jnp

    rep = numerics.bisect_callable(
        lambda a: jnp.sum(a * a), jnp.array([1.0, 2.0]))
    assert rep is None


# -- postmortem bundles -----------------------------------------------------

def test_dump_bundle_contents_and_atomicity(tmp_path):
    flight.record("probe", x=1)
    path = str(tmp_path / "b.json")
    got = postmortem.dump(reason="unit", path=path)
    assert got == path
    b = json.load(open(path))
    for key in ("events", "telemetry", "spans", "step_table",
                "compile_registry", "env", "identity", "reason"):
        assert key in b, key
    assert b["reason"] == "unit"
    assert any(e["kind"] == "probe" for e in b["events"])
    assert "MXTPU_NUMERICS" in b["env"]
    # atomic commit: no tmp file left behind
    assert [f for f in os.listdir(tmp_path)] == ["b.json"]
    # a second dump atomically replaces (never torn, never appended)
    postmortem.dump(reason="unit2", path=path)
    assert json.load(open(path))["reason"] == "unit2"


def test_periodic_flush_leaves_bundle(monkeypatch, tmp_path):
    monkeypatch.setenv("MXTPU_FLIGHTREC_FLUSH_STEPS", "2")
    monkeypatch.setenv("MXTPU_FLIGHTREC_DIR", str(tmp_path))
    for _ in range(4):
        flight.record("step")
    from mxnet_tpu import _checkpoint_io

    _checkpoint_io.flush_all()
    path = postmortem.default_path()
    assert os.path.exists(path)
    assert json.load(open(path))["reason"] == "periodic"


def test_watchdog_fire_writes_bundle(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_FLIGHTREC_DIR", str(tmp_path))
    from mxnet_tpu.diagnostics import watchdog

    watchdog.configure(MXTPU_WATCHDOG_FILE=os.devnull)
    try:
        watchdog.dump_now("observability-site")
    finally:
        watchdog.reset()
    from mxnet_tpu import _checkpoint_io

    _checkpoint_io.flush_all()
    b = json.load(open(postmortem.default_path()))
    assert b["reason"].startswith("watchdog:")
    assert b["watchdog_dump"] and "observability-site" in b["watchdog_dump"]
    assert any(e["kind"] == "watchdog" for e in b["events"])


def test_crash_hooks_install_once():
    import sys

    prev_hook = sys.excepthook
    first = postmortem.install_crash_hooks()
    second = postmortem.install_crash_hooks()
    assert postmortem.crash_hooks_installed()
    assert second is False  # idempotent
    if first:
        sys.excepthook = prev_hook  # don't leak into other tests


# -- telemetry counters -----------------------------------------------------

def test_flight_and_trip_counters():
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import instruments

    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset()
    try:
        flight.record("tick")
        assert instruments.flight_events_total.labels("tick").value == 1
        numerics._record_trip(
            numerics._register_program("prog/x", "step", 1))
        assert instruments.numerics_trip_total.labels("prog/x").value == 1
        tr = numerics.take_trip("prog")
        assert tr["label"] == "prog/x"
        assert not numerics.tripped()
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()


# -- satellite: fused AMP overflow check ------------------------------------

def test_loss_scaler_fused_has_overflow():
    from mxnet_tpu.amp import LossScaler

    params = []
    for i, fill in enumerate((1.0, 2.0, 3.0)):
        p = gluon.Parameter(f"w{i}", shape=(4, 4))
        p.initialize()
        g = p.grad()
        g._data = mx.np.full((4, 4), fill)._data
        params.append(p)
    scaler = LossScaler()
    assert scaler.has_overflow(params) is False
    assert len(scaler._check_cache) == 1  # ONE fused jitted check
    params[1].grad()._data = mx.np.array(
        onp.array([[onp.inf] + [0.0] * 3] + [[0.0] * 4] * 3, dtype="f"))._data
    assert scaler.has_overflow(params) is True
    assert len(scaler._check_cache) == 1  # same signature, same program
    kinds = [e["kind"] for e in flight.events()]
    assert "amp_overflow" in kinds


def test_loss_scaler_empty_and_null_grads():
    from mxnet_tpu.amp import LossScaler

    p = gluon.Parameter("w", shape=(2,), grad_req="null")
    p.initialize()
    assert LossScaler().has_overflow([p]) is False
    assert LossScaler().has_overflow([]) is False


# -- satellite: clip_global_norm attribution --------------------------------

def test_clip_global_norm_names_first_offender():
    arrays = [mx.np.ones((3,)),
              mx.np.array(onp.array([1.0, onp.nan], dtype="f")),
              mx.np.ones((2, 2))]
    with warnings.catch_warnings(record=True) as ws:
        warnings.simplefilter("always")
        norm = gluon.utils.clip_global_norm(arrays, 1.0)
    assert not math.isfinite(norm)
    msg = str(ws[-1].message)
    assert "first non-finite array: #1" in msg
    assert "(2,)" in msg and "float32" in msg
    ev = next(e for e in flight.events() if e["kind"] == "clip_nonfinite")
    assert ev["offenders"] == [1]
    assert ev["arrays"] == 3


def test_clip_global_norm_finite_path_unchanged():
    arrays = [mx.np.full((4,), 3.0), mx.np.full((4,), 4.0)]
    norm = gluon.utils.clip_global_norm(arrays, 1.0)
    assert norm == pytest.approx(10.0, rel=1e-5)
    joint = math.sqrt(sum(
        float((a * a).sum().asnumpy()) for a in arrays))
    assert joint == pytest.approx(1.0, rel=1e-4)  # clipped to max_norm
    assert not any(e["kind"] == "clip_nonfinite" for e in flight.events())
