"""The program's own spans, scopes and compile counters as the per-layer
readers see them: on a small hand-made trace (trace_program_small.json),
on a trace recorded here, and on a program that has none of it."""
import json
import os

import pytest

import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RUN = {"steps": 2, "traced_steps": 2, "platform": "tpu",
       "workload": {"name": "toy_train"}}


@pytest.fixture
def small(monkeypatch):
    """The small trace behind every source the readers use."""
    with open(os.path.join(HERE, "trace_program_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    host = [tuple(e) for e in d["host_spans"]]
    from mxnet_tpu.diagnostics import spans

    records = [{"name": n, "dur": dur / 1e9} for n, _s, dur in host]
    # two steps of set-up came first: the window is the LAST run["steps"]
    older = [{"name": n, "dur": 1.0} for n, _s, _d in host]
    monkeypatch.setattr(spans, "records", lambda: older + records)
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": dict(d["op_scopes"]))
    monkeypatch.setattr(program_spans, "xplane_of", lambda run: "small")
    monkeypatch.setattr(program_spans, "devices_of",
                        lambda xplane, platform: devices)
    monkeypatch.setattr(program_spans, "host_spans",
                        lambda xplane, prefix="mxtpu:": host)
    trace = trace_reduce.reduce(devices, [])
    return trace, devices, host, d["expect"]


def test_idle_under_hand_checked(small):
    # the device is idle 13500 -> 20000; the second whole_step annotation
    # covers 13600 -> 19000 of it: 5400 ns inside the call, 100 ns behind
    # train_step.operands and 1000 ns behind train_step.writeback
    _trace, devices, host, expect = small
    assert program_spans.idle_under(devices, host, "whole_step") == \
        pytest.approx(expect["idle_under_whole_step_s"])
    assert program_spans.idle_under(devices, host, "train_step.operands") \
        == pytest.approx(1e-7)
    assert program_spans.idle_under(devices, host, "train_step.writeback") \
        == pytest.approx(1e-6)
    assert program_spans.idle_under(devices, host, "no_such_span") is None
    assert program_spans.idle_under({}, host, "whole_step") is None


@pytest.mark.parametrize("name", [
    "idle_in_call_ms.train", "device_forward_ms.train",
    "device_backward_ms.train", "device_optimizer_ms.train",
    "device_batchnorm_ms.train", "device_scope_coverage_pct.train",
    "host_prologue_ms.train", "host_call_ms.train",
    "host_writeback_ms.train", "data_wait_ms.train",
    "data_wait_p95_ms.train"])
def test_reader_on_the_small_trace(small, name):
    # per traced step (2): forward fusion.1 + convolution.2 = 14 us / 2;
    # backward fusion.3 = 6 / 2; optimizer fusion.4 = 2 / 2; BatchNorm
    # fusion.1 + fusion.3 = 12 / 2; copy.5 (1 us of 23) has no scope.
    # Host, median of two steps: prologue + operands (1.5, 1.6 us),
    # the call (5.5, 5.4), write-back + bookkeeping (1.0, 2.0); the waits
    # for a batch (3.0, 4.0): median 3.5, 95th percentile 3.95
    trace, _devices, _host, expect = small
    value = harness._load_reader(name).read(trace, RUN)
    assert value == pytest.approx(expect[name])


def test_scope_helpers():
    fwd = "jit(whole_step)/jvp(forward)/BottleneckV1_3/BatchNorm_bn2/mul"
    bwd = ("jit(whole_step)/transpose(jvp(forward))/BottleneckV1_3/"
           "Conv2D_conv1/jit(_conv)/conv_general_dilated")
    assert program_spans.phase_of(fwd) == "forward"
    assert program_spans.phase_of(bwd) == "backward"
    assert program_spans.phase_of(
        "jit(whole_step)/jvp(loss)/SoftmaxCrossEntropyLoss/log") == "forward"
    assert program_spans.phase_of(
        "jit(whole_step)/transpose(jvp(loss))/L2Loss/mul") == "backward"
    assert program_spans.phase_of("jit(whole_step)/optimizer/sub") \
        == "optimizer"
    assert program_spans.phase_of("jit(whole_step)/grad_reduce/psum") \
        == "grad_reduce"
    assert program_spans.phase_of("jit(whole_step)/convert_element_type") \
        is None
    assert program_spans.phase_of("tws['features.0.weight']") is None
    assert program_spans.block_of(fwd) == "BatchNorm_bn2"
    assert program_spans.block_of(bwd) == "Conv2D_conv1"
    assert program_spans.block_of("jit(whole_step)/add") is None


def test_per_step_sum_pairs_from_the_newest_step():
    s = {"a": [9.0, 1.0, 2.0], "b": [10.0, 20.0]}
    assert program_spans.per_step_sum(s, ("a", "b")) == [11.0, 22.0]
    assert program_spans.per_step_sum(s, ("a", "missing")) is None
    assert program_spans.per_step_sum({}, ("a",)) is None


def test_host_spans_of_a_recorded_xplane(tmp_path):
    """Spans of the program, recorded here under the profiler: they are
    found on the host plane with their nesting, prefix cut."""
    import jax

    from mxnet_tpu.diagnostics import spans

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for k in range(2):
            with spans.span("train_step", cat=spans.STEP_CAT, step_num=k):
                with spans.span("whole_step", cat="fwd"):
                    pass
        with jax.profiler.TraceAnnotation("chipbench:enqueue_step"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = program_spans.host_spans(trace_reduce.newest_xplane(str(tmp_path)))
    assert [n for n, _s, _d in got] == ["train_step", "whole_step"] * 2
    (o0, s0, d0), (i0, s1, d1) = got[0], got[1]
    assert s0 <= s1 and s1 + d1 <= s0 + d0
    assert got == sorted(got, key=lambda e: e[1])


def test_readers_find_nothing_on_a_program_without_spans(monkeypatch):
    """The parent of the PR that added them: the ring has only
    `whole_step`, the registry no op_scopes, the registry of counters no
    xla_* counter; no reader raises."""
    from mxnet_tpu.diagnostics import introspect, spans
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(spans, "records", lambda: [
        {"name": "whole_step", "dur": 0.05}, {"name": "whole_step",
                                              "dur": 0.07}])
    monkeypatch.setattr(introspect, "compile_registry", lambda: {
        ("whole_step", "sgd-p2-b1-local"): {"flops": 1.0}})
    monkeypatch.delattr(ti, "xla_programs_total")
    monkeypatch.delattr(ti, "step_scalar_operands")
    monkeypatch.setattr(program_spans, "xplane_of", lambda run: None)
    trace = {"op_s": {"fusion.1": 1e-3}, "busy_s": 1e-3, "window_s": 2e-3}
    got = {n: harness._load_reader(n).read(trace, RUN) for n in (
        "host_prologue_ms.train", "host_call_ms.train",
        "host_writeback_ms.train", "host_scalar_operands.train",
        "idle_in_call_ms.train", "device_forward_ms.train",
        "device_backward_ms.train", "device_optimizer_ms.train",
        "device_batchnorm_ms.train", "device_scope_coverage_pct.train",
        "xla_backend_compile_s", "xla_programs_compiled",
        "data_wait_ms.train", "data_wait_p95_ms.train")}
    assert got.pop("host_call_ms.train") == pytest.approx(60.0)
    assert set(got.values()) == {None}


def test_xla_readers_leave_out_what_compiled_after_the_last_step():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.observability import flight

    x = jnp.arange(11.0)
    x.block_until_ready()
    jax.jit(lambda v: jnp.cos(v * 0.375).sum())(x)       # "set-up"
    flight.record("step", examples=1)
    before = program_spans.xla_compiles_of_setup()
    jax.jit(lambda v: jnp.sin(v * 0.625).sum())(x)       # "the reference"
    after = program_spans.xla_compiles_of_setup()
    assert before["backend_s"] > 0
    assert after == pytest.approx(before)
    read = harness._load_reader("xla_programs_compiled").read
    assert read({}, RUN) == before["built"]
    secs = harness._load_reader("xla_backend_compile_s").read({}, RUN)
    assert secs == pytest.approx(before["backend_s"]
                                 - before["cache_load_s"])
