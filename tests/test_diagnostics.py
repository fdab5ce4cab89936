"""Diagnostics subsystem: span tracer, compile registry, watchdog, report.

Covers the ISSUE-2 acceptance surface: span nesting + ring bounds, the
per-step phase table on a real hybridized train loop, compile-registry
entries with nonzero flops/peak-HBM from cost_analysis()/
memory_analysis(), the chrome-trace bridge, the watchdog firing on a
deliberate stall WITHOUT killing the process, and the report golden.
Plus the round-5 probe: visualization.print_summary deduces parameter
shapes instead of demanding every leaf.
"""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import diagnostics, telemetry
from mxnet_tpu.diagnostics import introspect, spans, watchdog
from mxnet_tpu.gluon import Trainer, nn


@pytest.fixture
def fresh():
    """Diagnostics + telemetry reset and enabled, restored afterwards."""
    prev_enabled = spans.enabled()
    prev_cap = spans.ring_capacity()
    prev_tel = telemetry.REGISTRY.enabled
    diagnostics.reset()
    telemetry.reset()
    spans.enable()
    telemetry.enable()
    yield
    diagnostics.reset()
    telemetry.reset()
    spans.set_ring_capacity(prev_cap)
    if not prev_enabled:
        spans.disable()
    telemetry.REGISTRY.enabled = prev_tel


# -- span tracer ------------------------------------------------------------

def test_span_nesting_depth_and_order(fresh):
    with spans.span("outer", cat="fwd"):
        assert spans.current_stack() == ["outer"]
        with spans.span("inner", cat="fwd"):
            assert spans.current_stack() == ["outer", "inner"]
    recs = spans.records()
    # inner closes first
    assert [r["name"] for r in recs] == ["inner", "outer"]
    by_name = {r["name"]: r for r in recs}
    assert by_name["outer"]["depth"] == 0
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]
    assert spans.current_stack() == []


def test_span_records_on_exception(fresh):
    with pytest.raises(RuntimeError):
        with spans.span("boom", cat="fwd"):
            raise RuntimeError("x")
    assert [r["name"] for r in spans.records()] == ["boom"]
    assert spans.current_stack() == []


def test_ring_buffer_bounded(fresh):
    spans.set_ring_capacity(8)
    for i in range(20):
        with spans.span(f"s{i}"):
            pass
    recs = spans.records()
    assert len(recs) == 8
    # oldest fell off: only the last 8 remain, in order
    assert [r["name"] for r in recs] == [f"s{i}" for i in range(12, 20)]


def test_disabled_spans_record_nothing(fresh):
    spans.disable()
    with spans.span("ghost"):
        assert spans.current_stack() == []
    assert spans.records() == []
    spans.enable()


def test_spans_thread_safety(fresh):
    spans.set_ring_capacity(10000)
    n_threads, n_spans = 4, 200

    def worker(k):
        for i in range(n_spans):
            with spans.span(f"t{k}", cat="other"):
                pass

    ts = [threading.Thread(target=worker, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = spans.records()
    assert len(recs) == n_threads * n_spans
    # every worker's spans all landed (tids can be reused by the OS, so
    # count by name, not by distinct tid)
    for k in range(n_threads):
        assert sum(r["name"] == f"t{k}" for r in recs) == n_spans


def test_step_attribution_and_table(fresh):
    # step 0 work, then mark_step, then step 1 work
    with spans.span("fwd0", cat="fwd"):
        time.sleep(0.002)
    spans.mark_step()
    with spans.span("fwd1", cat="fwd"):
        pass
    with spans.span("opt1", cat="optimizer"):
        pass
    table = spans.step_table()
    assert set(table) == {0, 1}
    assert table[0]["fwd"] >= 0.002
    assert "fwd" in table[1] and "optimizer" in table[1]
    text = spans.format_step_table()
    assert "step" in text and "optimizer" in text.splitlines()[0]
    assert len(text.splitlines()) == 3  # header + 2 step rows


def test_step_table_no_double_count_nested_same_cat(fresh):
    with spans.span("outer", cat="fwd"):
        with spans.span("inner", cat="fwd"):
            time.sleep(0.002)
    table = spans.step_table()
    outer = next(r for r in spans.records() if r["name"] == "outer")
    # only the outermost fwd span is summed, not outer+inner
    assert table[0]["fwd"] == pytest.approx(outer["dur"])


# -- compile registry -------------------------------------------------------

def test_compile_registry_on_hybrid_block(fresh):
    net = nn.Dense(4)
    net.initialize()
    net.hybridize()
    x = mx.np.ones((2, 8))
    net(x)
    mx.waitall()
    reg = diagnostics.compile_registry()
    assert ("Dense", "predict") in reg
    e = reg[("Dense", "predict")]
    assert e["flops"] > 0
    assert e["peak_hbm_bytes"] > 0
    assert e["argument_bytes"] > 0
    assert e["compile_seconds"] > 0
    # exported onto the telemetry gauges
    dumped = telemetry.dump()
    s = dumped["compile_flops"]["samples"]
    assert any(smp["labels"] == {"block": "Dense", "variant": "predict"}
               and smp["value"] > 0 for smp in s)
    txt = introspect.format_compile_table()
    assert "Dense" in txt and "predict" in txt


def test_compile_capture_disabled_by_env(fresh, monkeypatch):
    monkeypatch.setenv("MXTPU_DIAG_COMPILE", "0")
    net = nn.Dense(3)
    net.initialize()
    net.hybridize()
    net(mx.np.ones((2, 5)))
    mx.waitall()
    assert diagnostics.compile_registry() == {}


def test_device_memory_none_safe(fresh):
    mems = diagnostics.device_memory()
    assert mems, "at least one device"
    for dm in mems:
        assert "stats" in dm and "platform" in dm
    # CPU reports None stats; the gauge updater must not blow up either way
    diagnostics.update_device_memory_gauge()


# -- trainer/backward/engine integration ------------------------------------

def test_train_loop_phase_breakdown_and_report(fresh):
    net = nn.Dense(8)
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1})
    x = mx.np.ones((4, 16))
    for _ in range(2):
        with ag.record():
            y = net(x)
            loss = (y * y).sum()
        loss.backward()
        trainer.step(batch_size=4)
    mx.waitall()

    table = spans.step_table()
    for step in (0, 1):
        assert table[step]["fwd"] > 0
        assert table[step]["bwd"] > 0
        assert table[step]["optimizer"] > 0
    # waitall happened after the last mark_step
    assert table[2]["sync"] > 0
    assert spans.current_step() == 2

    # the acceptance-criteria golden: report carries the phase table, a
    # compile entry with real numbers, and every section header
    rep = diagnostics.report()
    for section in ("per-step phase breakdown", "compile registry",
                    "device memory", "sync & collectives", "watchdog"):
        assert section in rep, rep
    assert "Dense" in rep and "train" in rep
    reg = diagnostics.compile_registry()
    e = reg[("Dense", "train")]
    assert e["flops"] > 0 and e["peak_hbm_bytes"] > 0
    assert "sync_total{site=waitall}" in rep


def test_dataloader_emits_data_spans(fresh):
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(np.arange(16, dtype=np.float32).reshape(8, 2),
                      np.arange(8, dtype=np.float32))
    loader = DataLoader(ds, batch_size=4)
    batches = list(loader)
    assert len(batches) == 2
    data_recs = [r for r in spans.records() if r["cat"] == "data"]
    assert len(data_recs) >= 2


# -- watchdog ---------------------------------------------------------------

def test_watchdog_disabled_guard_is_noop(fresh):
    assert not watchdog.enabled()
    with watchdog.guard("idle"):
        pass
    assert watchdog.last_dump() is None


def test_watchdog_fires_on_stall_without_killing_process(fresh, tmp_path):
    crash = tmp_path / "dump.txt"
    watchdog.configure(MXTPU_WATCHDOG=1,
                       MXTPU_WATCHDOG_TIMEOUT_S=0.15,
                       MXTPU_WATCHDOG_FILE=str(crash),
                       MXTPU_WATCHDOG_RAISE=0)
    try:
        with spans.span("stuck_phase", cat="sync"), \
                watchdog.guard("test-stall"):
            time.sleep(0.6)  # deliberately past the deadline
    finally:
        watchdog.configure(MXTPU_WATCHDOG=None,
                           MXTPU_WATCHDOG_TIMEOUT_S=None,
                           MXTPU_WATCHDOG_FILE=None,
                           MXTPU_WATCHDOG_RAISE=None)
    # ...and we are still alive (no raise by default)
    dump = watchdog.last_dump()
    assert dump is not None
    assert "MXTPU WATCHDOG: site 'test-stall' stalled" in dump
    assert "python thread stacks" in dump
    assert "time.sleep" in dump          # the stalled frame is visible
    assert "stuck_phase" in dump         # live span stack included
    assert "device memory" in dump
    assert crash.read_text() == dump     # crash file got the same content


def test_watchdog_guard_exit_disarms(fresh):
    watchdog.configure(MXTPU_WATCHDOG=1,
                       MXTPU_WATCHDOG_TIMEOUT_S=0.15,
                       MXTPU_WATCHDOG_FILE=os.devnull)
    try:
        with watchdog.guard("quick"):
            pass  # exits well before the deadline
        time.sleep(0.4)  # scanner had time to (wrongly) fire
    finally:
        watchdog.reset()
    assert watchdog.last_dump() is None


def test_watchdog_dump_now(fresh):
    watchdog.configure(MXTPU_WATCHDOG_FILE=os.devnull)
    try:
        text = watchdog.dump_now("manual-site")
    finally:
        watchdog.reset()
    assert "manual-site" in text and "thread stacks" in text


def test_watchdog_fires_mid_whole_step_dispatch(fresh, tmp_path):
    """The stall dump fires WHILE a whole-step donated dispatch is in
    flight and names the `whole_step` guard + span (ISSUE-8 satellite:
    previously only the phased path was covered)."""
    from mxnet_tpu.gluon import TrainStep

    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), trainer)
    x = mx.np.ones((4, 6))
    y = mx.np.zeros((4, 4))
    step(x, y)  # compile the donated whole-step program
    assert step.last_path == "whole_step"

    # make the NEXT dispatch stall past the deadline without touching
    # the compiled program: wrap the cached jit variants
    def slow(fn):
        def wrapped(*a, **k):
            time.sleep(0.6)
            return fn(*a, **k)
        return wrapped

    step._jit_variants = {k: slow(v)
                          for k, v in step._jit_variants.items()}
    watchdog.configure(MXTPU_WATCHDOG=1,
                       MXTPU_WATCHDOG_TIMEOUT_S=0.15,
                       MXTPU_WATCHDOG_FILE=str(tmp_path / "wd.txt"),
                       MXTPU_WATCHDOG_RAISE=0)
    try:
        step(x, y)  # stalled dispatch; watchdog fires mid-flight
    finally:
        watchdog.configure(MXTPU_WATCHDOG=None,
                           MXTPU_WATCHDOG_TIMEOUT_S=None,
                           MXTPU_WATCHDOG_FILE=None,
                           MXTPU_WATCHDOG_RAISE=None)
    assert step.last_path == "whole_step"
    dump = watchdog.last_dump()
    assert dump is not None
    assert "site 'whole_step' stalled" in dump   # the guarded site
    assert "whole_step" in dump.split("live span stacks")[1] \
        .split("open watchdog guards")[0]        # the live span names it


# -- report with no activity -------------------------------------------------

def test_report_empty_state(fresh):
    rep = diagnostics.report()
    assert "no spans recorded" in rep
    assert "no compiles captured" in rep
    assert "disarmed" in rep


# -- round-5 probe: print_summary shape deduction ----------------------------

def test_print_summary_deduces_param_shapes(capsys):
    data = mx.sym.Variable("data")
    conv = mx.sym.Convolution(data=data, kernel=(3, 3), num_filter=4,
                              pad=(1, 1), name="c1")
    bn = mx.sym.BatchNorm(conv, name="bn1")
    fc = mx.sym.FullyConnected(bn, num_hidden=3, name="f1")
    # only the data shape given — c1_weight/bn1_gamma/f1_weight deduced
    out = mx.visualization.print_summary(fc, shape={"data": (2, 3, 8, 8)})
    capsys.readouterr()
    assert "(4, 3, 3, 3)" in out          # deduced conv weight
    assert "(3, 256)" in out              # deduced fc weight (4*8*8 in)
    # conv: 4*3*3*3+4 = 112; bn: gamma+beta = 8; fc: 3*256+3 = 771
    assert "Total params: 891" in out


def test_print_summary_still_errors_on_undeducible():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    out = a + b
    with pytest.raises(ValueError, match="shape for input"):
        mx.visualization.print_summary(out, shape={})


# -- one span API on the profiler's clock (ISSUE 25) ------------------------

STEP_CHILDREN = ["train_step.prologue", "train_step.operands", "whole_step",
                 "train_step.writeback", "train_step.bookkeeping"]


def _toy_train_step():
    from mxnet_tpu.gluon import TrainStep

    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), trainer)
    return step, mx.np.ones((4, 6)), mx.np.zeros((4, 4))


def _host_annotations(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the mxtpu: annotations."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.ANNOTATION_PREFIX):
                    out.append((ev.name[len(spans.ANNOTATION_PREFIX):],
                                ev.start_ns, ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture
def profiled_steps(fresh, tmp_path):
    """Three whole steps of a toy TrainStep under jax.profiler."""
    import jax

    step, x, y = _toy_train_step()
    step(x, y)          # compile outside the trace
    diagnostics.reset()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            step(x, y)
    finally:
        jax.profiler.stop_trace()
    return _host_annotations(tmp_path)


def test_profiled_steps_carry_step_num(profiled_steps):
    steps = [e for e in profiled_steps if e[0] == "train_step"]
    assert [e[3]["step_num"] for e in steps] == [0, 1, 2]


def test_profiled_children_inside_parent_in_order(profiled_steps):
    steps = [e for e in profiled_steps if e[0] == "train_step"]
    assert len(steps) == 3
    for _n, t0, t1, _s in steps:
        inside = [e for e in profiled_steps
                  if e[0] != "train_step" and t0 <= e[1] and e[2] <= t1]
        assert [e[0] for e in inside] == STEP_CHILDREN
        for a, b in zip(inside, inside[1:]):
            assert a[2] <= b[1]          # contiguous parts never overlap
    # every child annotation lies in some step: nothing is left outside
    assert len(profiled_steps) == 3 * (1 + len(STEP_CHILDREN))


def test_ring_records_carry_parent(fresh):
    with spans.span("outer", cat="fwd"):
        with spans.span("inner"):
            pass
    by_name = {r["name"]: r for r in spans.records()}
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["outer"]["parent"] is None


def test_train_step_children_cover_the_outer_span(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    diagnostics.reset()
    for _ in range(2):
        step(x, y)
    recs = [r for r in spans.records() if r["name"] != "train_step"]
    outer = [r for r in spans.records() if r["name"] == "train_step"]
    assert [r["name"] for r in recs] == STEP_CHILDREN * 2
    assert all(r["parent"] == "train_step" and r["depth"] == 1
               for r in recs)
    assert [r["step"] for r in outer] == [0, 1] and \
        [r["step"] for r in recs] == [0] * 5 + [1] * 5
    # structure, not wall time: the children start inside the outer span
    # and each starts where the one before it ended or later
    for o, kids in zip(outer, (recs[:5], recs[5:])):
        assert o["t0"] <= kids[0]["t0"]
        assert kids[-1]["t0"] + kids[-1]["dur"] <= o["t0"] + o["dur"]
        for a, b in zip(kids, kids[1:]):
            assert a["t0"] + a["dur"] <= b["t0"]


def test_first_call_has_compile_capture_child(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    names = [r["name"] for r in spans.records()
             if r.get("parent") == "train_step"]
    assert names.count("train_step.compile_capture") == 1
    assert names.index("whole_step") < names.index(
        "train_step.compile_capture") < names.index("train_step.writeback")


def test_step_table_leaves_out_the_step_span(fresh):
    with spans.span("train_step", cat=spans.STEP_CAT, step_num=0):
        with spans.span("whole_step", cat="fwd"):
            pass
    row = spans.step_table()[0]
    assert set(row) == {"fwd"}


def test_disabled_records_nothing_and_builds_no_annotation(
        fresh, monkeypatch):
    step, x, y = _toy_train_step()
    step(x, y)
    made = []

    class Counting:
        def __init__(self, *a, **k):
            made.append(a)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    monkeypatch.setattr(spans, "StepTraceAnnotation", Counting)
    diagnostics.reset()
    traces = step.jit_trace_count()
    spans.disable()
    try:
        step(x, y)
        with spans.span("off"):
            pass
    finally:
        spans.enable()
    assert spans.records() == [] and made == []
    assert step.jit_trace_count() == traces
    step(x, y)      # and on again: one annotation per span
    assert len(made) == 1 + len(STEP_CHILDREN)


def test_raising_body_closes_annotation_and_records(fresh, monkeypatch):
    closed = []

    class Recording:
        def __init__(self, name, **kv):
            self.name = name

        def __enter__(self):
            return self

        def __exit__(self, exc_type, exc, tb):
            closed.append((self.name, exc_type))
            return False

    monkeypatch.setattr(spans, "TraceAnnotation", Recording)
    with pytest.raises(KeyError):
        with spans.span("outer"):
            with spans.span("boom", cat="sync"):
                raise KeyError("x")
    assert closed == [("mxtpu:boom", KeyError), ("mxtpu:outer", KeyError)]
    assert [r["name"] for r in spans.records()] == ["boom", "outer"]
    assert spans.current_stack() == []
