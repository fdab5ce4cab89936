"""The start-up timeline (docs/diagnostics.md, "The spans of start-up"):
back-dated stage records per program from the one jax.monitoring
listener, the spans at set-up's seams, and diagnostics.startup_report().

CPU only, one process: every program here is a toy and every assertion
is on structure (which records, whose name, what lies inside what), never
on a duration's size.
"""
import itertools
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import diagnostics, telemetry
from mxnet_tpu.diagnostics import spans, startup
from mxnet_tpu.gluon import Trainer, TrainStep, nn
from mxnet_tpu.telemetry import instruments as ti

# pytest imports every test module before it runs a test: whatever a
# fixture of an earlier module does to the ring, this is the ring as the
# import of the package left it
_AT_COLLECTION = [r for r in spans.records() if r["name"] == "startup.import"]

STAGES = ("xla.trace", "xla.lower", "xla.backend")
CAPTURE_CHILDREN = ["compile_capture.lower", "compile_capture.compile",
                    "compile_capture.text", "compile_capture.op_scopes"]
_serial = itertools.count()


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


def _program(name=None, inner=None):
    """A jitted function no other test has: its own name (the persistent
    cache keys on it) and enough operations that its trace is a trace and
    not a cache lookup (the listener's floor is a millisecond)."""
    name = name or f"timeline_probe_{os.getpid()}_{next(_serial)}"

    def f(x):
        for i in range(120):
            x = jnp.sin(x) * 1.25 + i
        return inner(x) if inner is not None else x

    f.__name__ = f.__qualname__ = name
    return jax.jit(f), name


def _of(fun, name=None):
    return [r for r in spans.records() if r.get("fun") == fun
            and (name is None or r["name"] == name)]


def _toy_train_step():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9})
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(), trainer)
    return step, mx.np.ones((4, 6)), mx.np.zeros((4, 4))


# -- the ring: back-dated records, kv, order --------------------------------

def test_record_writes_a_finished_backdated_record(fresh):
    with spans.span("outer", cat="fwd"):
        spans.record("late", "compile", 12.5, 0.25, fun="f", how="built")
    late, outer = spans.records()
    assert (late["name"], late["cat"], late["t0"], late["dur"]) == \
        ("late", "compile", 12.5, 0.25)
    assert (late["fun"], late["how"], late["backdated"]) == \
        ("f", "built", True)
    # depth / parent / step are those of the instant it was written
    assert (late["depth"], late["parent"], late["step"]) == (1, "outer", 0)
    assert "backdated" not in outer


def test_record_enters_no_profiler_annotation(fresh, monkeypatch):
    made = []
    monkeypatch.setattr(spans, "TraceAnnotation",
                        lambda *a, **k: made.append(a))
    spans.record("late", "compile", 1.0, 1.0)
    assert made == [] and len(spans.records()) == 1


def test_a_fixed_field_wins_over_a_kv_of_its_name(fresh):
    spans.record("late", "compile", 1.0, 2.0, depth=99, fun="f")
    with spans.span("s", cat="fwd", parent="other", fun="g"):
        pass
    late, s = spans.records()
    assert late["depth"] == 0 and late["fun"] == "f"
    assert s["parent"] is None and s["fun"] == "g"


def test_span_kv_is_kept_in_the_ring(fresh):
    with spans.span("with_kv", cat="fwd", program="p", rows=3):
        pass
    with spans.span("without"):
        pass
    a, b = spans.records()
    assert (a["program"], a["rows"]) == ("p", 3)
    assert set(b) == {"name", "cat", "t0", "dur", "tid", "depth", "parent",
                      "step"}


def test_records_are_in_order_of_writing_which_is_order_of_end(fresh):
    """records(): oldest first by time of WRITING, and a record is written
    when what it timed ended -- so ends never decrease, a child precedes
    its parent, and t0 alone is in no order."""
    fn, name = _program()
    with spans.span("enclosing", cat="fwd"):
        fn(jnp.arange(5.0))
        with spans.span("child"):
            pass
    recs = spans.records()
    ends = [r["t0"] + r["dur"] for r in recs]
    assert all(a <= b + 1e-3 for a, b in zip(ends, ends[1:]))
    names = [r["name"] for r in recs]
    assert names.index("child") < names.index("enclosing") == len(recs) - 1
    starts = [r["t0"] for r in recs]
    assert starts != sorted(starts)      # the enclosing span began first


# -- stage records per program ----------------------------------------------

@pytest.mark.parametrize("stage", STAGES)
def test_a_fresh_program_yields_one_record_of_each_stage(fresh, stage):
    fn, name = _program()
    with spans.span("enclosing", cat="fwd"):
        fn(jnp.arange(5.0)).block_until_ready()
    read_at = time.perf_counter()
    (rec,) = _of(name, stage)
    enclosing = [r for r in spans.records() if r["name"] == "enclosing"][0]
    assert rec["cat"] == "compile" and rec["backdated"] and rec["dur"] > 0
    assert rec["t0"] + rec["dur"] <= read_at
    assert enclosing["t0"] - 1e-3 <= rec["t0"]
    assert rec["t0"] + rec["dur"] <= \
        enclosing["t0"] + enclosing["dur"] + 1e-3
    assert (rec["parent"], rec["depth"]) == ("enclosing", 1)
    if stage == "xla.backend":
        assert rec["how"] in ("loaded", "built")


def test_the_stages_of_one_program_follow_each_other(fresh):
    fn, name = _program()
    fn(jnp.arange(5.0))
    trace, lower, backend = (_of(name, s)[0] for s in STAGES)
    assert trace["t0"] + trace["dur"] <= lower["t0"] + 1e-3
    assert lower["t0"] + lower["dur"] <= backend["t0"] + 1e-3


@pytest.mark.parametrize("stage", STAGES)
def test_a_second_call_yields_none_and_a_new_shape_one_more(fresh, stage):
    fn, name = _program()
    fn(jnp.arange(5.0))
    assert len(_of(name, stage)) == 1
    fn(jnp.arange(5.0) + 1)
    assert len(_of(name, stage)) == 1
    fn(jnp.arange(7.0))
    assert len(_of(name, stage)) == 2


def test_a_nested_programs_trace_lies_inside_its_parents(fresh):
    inner, inner_name = _program()
    outer, outer_name = _program(inner=inner)
    outer(jnp.arange(5.0))
    (parent,) = _of(outer_name, "xla.trace")
    (child,) = _of(inner_name, "xla.trace")
    assert parent["t0"] - 1e-3 <= child["t0"]
    assert child["t0"] + child["dur"] <= parent["t0"] + parent["dur"] + 1e-3
    # traced into its parent, never lowered or compiled on its own
    assert [r["name"] for r in _of(inner_name)] == ["xla.trace"]
    names = [r["name"] for r in spans.records()
             if r.get("fun") in (inner_name, outer_name)]
    assert names == ["xla.trace", "xla.trace", "xla.lower", "xla.backend"]


@pytest.fixture
def empty_cache_dir(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    compilation_cache.reset_cache()
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_built_on_an_empty_cache_and_loaded_from_a_warm_one(
        fresh, empty_cache_dir):
    fn, name = _program()
    fn(jnp.arange(5.0))
    (built,) = _of(name, "xla.backend")
    assert built["how"] == "built" and _of(name, "xla.cache_load") == []
    again, _ = _program(name=name)       # a new jit of the same module
    again(jnp.arange(5.0))
    built, loaded = _of(name, "xla.backend")
    assert loaded["how"] == "loaded"
    (load,) = _of(name, "xla.cache_load")
    # the load carries its program's name and lies inside the backend
    # record that JAX timed around it; it was written just before it
    assert loaded["t0"] - 1e-3 <= load["t0"]
    assert load["t0"] + load["dur"] <= loaded["t0"] + loaded["dur"] + 1e-3
    names = [r["name"] for r in _of(name)]
    assert names.index("xla.cache_load") == len(names) - 2
    assert {k[0]: c.value for k, c in ti.xla_programs_total.series()} == \
        {"built": 1.0, "loaded": 1.0}
    # and the directory's grouping by module name finds the one entry
    contents = startup.cache_contents(empty_cache_dir)
    assert contents["entries"] == 1 and contents["bytes"] > 0
    assert contents["modules"][0]["module"] == "jit_" + name


def test_a_cache_lookup_under_the_floor_counts_and_writes_nothing(fresh):
    """JAX fires the trace event around a cached function too: thousands
    of microsecond events inside one step's trace.  The counter has them,
    the ring only what took a millisecond."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    ti._on_xla_duration(event, 2e-5, fun_name="multiply")
    ti._on_xla_duration(event, 5e-3, fun_name="multiply")
    ti._on_xla_duration("/jax/core/compile/jaxpr_to_mlir_module_duration",
                        2e-5, fun_name="jit(multiply)")
    assert [(r["name"], r["dur"]) for r in _of("multiply")] == \
        [("xla.trace", 5e-3), ("xla.lower", 2e-5)]
    seconds = {k[0]: c.value
               for k, c in ti.xla_compile_seconds_total.series()}
    assert seconds["trace"] == pytest.approx(5.02e-3)
    ti._on_xla_duration("/jax/some/other/event", 1.0, fun_name="x")
    assert len(spans.records()) == 2


@pytest.mark.parametrize("given, fun", [
    ("whole_step", "whole_step"), ("jit(whole_step)", "whole_step"),
    ("pmap(step)", "step"), ("jit(<lambda>)", "<lambda>"), (None, "None")])
def test_one_name_for_a_program_at_every_stage(given, fun):
    assert ti._fun_of(given) == fun


def test_disabled_spans_write_no_record_and_the_counters_still_count(fresh):
    fn, name = _program()
    spans.disable()
    try:
        fn(jnp.arange(5.0))
        spans.record("late", "compile", 1.0, 1.0)
    finally:
        spans.enable()
    assert spans.records() == []
    seconds = {k[0]: c.value
               for k, c in ti.xla_compile_seconds_total.series()}
    assert seconds["trace"] > 0 and seconds["lower"] > 0 \
        and seconds["backend"] > 0
    assert sum(c.value for _, c in ti.xla_programs_total.series()) == 1


def test_disabled_telemetry_still_writes_the_records(fresh):
    fn, name = _program()
    telemetry.disable()
    try:
        fn(jnp.arange(5.0))
    finally:
        telemetry.enable()
    assert [r["name"] for r in _of(name)] == list(STAGES)
    assert list(ti.xla_compile_seconds_total.series()) == []


def test_step_table_leaves_the_stage_records_out(fresh):
    """They lie inside `whole_step`'s fwd time (or inside no span at
    all): counted again under `compile` a second would be twice in a
    step's row."""
    fn, name = _program()
    with spans.span("train_step", cat=spans.STEP_CAT, step_num=0):
        with spans.span("whole_step", cat="fwd"):
            fn(jnp.arange(5.0))
    fn(jnp.arange(9.0))                  # and under no span
    assert len(_of(name)) == 6
    row = spans.step_table()[0]
    whole = [r for r in spans.records() if r["name"] == "whole_step"][0]
    assert row == {"fwd": whole["dur"]}


def test_step_table_of_a_compiling_train_step_counts_no_second_twice(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    recs = spans.records()
    row = spans.step_table()[0]
    named = {n: [r for r in recs if r["name"] == n][0]["dur"]
             for n in ("block.initialize", "train_step.build",
                       "train_step.compile_capture", "train_step")}
    # compile: initialize, the build and the capture -- not the states
    # created inside the build, not the capture's children, not the
    # stages inside whole_step
    assert row["compile"] == pytest.approx(
        named["block.initialize"] + named["train_step.build"]
        + named["train_step.compile_capture"])
    assert sum(row.values()) <= \
        named["train_step"] + named["block.initialize"]


# -- the spans of set-up ----------------------------------------------------

def test_the_import_is_on_the_ring():
    (rec,) = _AT_COLLECTION
    assert rec["cat"] == "startup" and rec["backdated"]
    assert rec["dur"] > 0 and rec["t0"] + rec["dur"] <= time.perf_counter()
    # conftest imports jax before any test module imports the package
    assert rec["jax_preloaded"] is True


def test_the_first_device_resolution_is_spanned_once(fresh, monkeypatch):
    from mxnet_tpu import device

    monkeypatch.setattr(device, "_backend_taken", False)
    mx.cpu(0).jax_device
    mx.num_tpus()
    mx.cpu(0).jax_device
    recs = [r for r in spans.records() if r["name"] == "startup.backend"]
    assert len(recs) == 1 and recs[0]["cat"] == "startup"


def test_initialize_is_one_span_for_the_tree(fresh):
    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=6), nn.Dense(4, in_units=8))
    net.initialize()
    recs = [r for r in spans.records() if r["name"] == "block.initialize"]
    assert len(recs) == 1 and recs[0]["cat"] == "compile"
    assert recs[0]["depth"] == 0


def test_the_offline_cast_is_spanned(fresh):
    from mxnet_tpu import amp

    net = nn.HybridSequential()
    net.add(nn.Dense(8, in_units=6))
    net.initialize()
    diagnostics.reset()
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    (rec,) = [r for r in spans.records() if r["name"] == "amp.convert"]
    assert rec["cat"] == "compile"
    inside = [r for r in spans.records() if r.get("parent") == "amp.convert"]
    assert all(r["name"].startswith("xla.") for r in inside)


@pytest.mark.parametrize("name", ["train_step.build",
                                  "trainer.create_states",
                                  "train_step.compile_capture"]
                         + CAPTURE_CHILDREN)
def test_first_train_step_call_has_the_span_and_the_second_has_not(
        fresh, name):
    step, x, y = _toy_train_step()
    step(x, y)
    first = [r for r in spans.records() if r["name"] == name]
    assert len(first) == 1 and first[0]["cat"] == "compile"
    parent = {"train_step.build": "train_step",
              "trainer.create_states": "train_step.build",
              "train_step.compile_capture": "train_step"}.get(
                  name, "train_step.compile_capture")
    assert first[0]["parent"] == parent
    diagnostics.reset()
    step(x, y)
    assert [r for r in spans.records() if r["name"] == name] == []
    assert not any(r["name"].startswith("xla.") for r in spans.records())


def test_the_capture_children_are_contiguous_inside_the_capture(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    recs = spans.records()
    kids = [r for r in recs if r["name"] in CAPTURE_CHILDREN]
    capture = [r for r in recs
               if r["name"] == "train_step.compile_capture"][0]
    assert [r["name"] for r in kids] == CAPTURE_CHILDREN
    assert capture["t0"] <= kids[0]["t0"]
    assert kids[-1]["t0"] + kids[-1]["dur"] <= capture["t0"] + capture["dur"]
    for a, b in zip(kids, kids[1:]):
        assert a["t0"] + a["dur"] <= b["t0"]


# -- the report -------------------------------------------------------------

SECONDS_KEYS = {
    "import", "backend", "block_initialize", "amp_convert", "create_states",
    "step_build", "state_build", "step_trace", "step_lower", "step_backend",
    "step_cache_load", "other_programs", "compile_capture", "capture_lower",
    "capture_compile", "capture_text", "capture_op_scopes", "first_run",
    "accounted"}


def test_startup_report_holds_the_documented_keys(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    rep = diagnostics.startup_report()
    assert set(rep) == {"seconds", "step", "other_programs", "programs",
                        "programs_left_out", "records", "from", "until",
                        "recompiles"}
    assert set(rep["seconds"]) == SECONDS_KEYS
    assert set(rep["step"]) == {"fun", "traced", "lowered", "obtained",
                                "loaded", "built"}
    for p in rep["programs"]:
        assert set(p) == {"fun", "trace_s", "lower_s", "backend_s",
                          "cache_load_s", "obtained", "how", "in_step"}
    sec = rep["seconds"]
    assert sec["step_trace"] > 0 and sec["step_lower"] > 0 \
        and sec["step_backend"] > 0 and sec["compile_capture"] > 0
    children = [sec[k] for k in ("capture_lower", "capture_compile",
                                 "capture_text", "capture_op_scopes")]
    assert all(v > 0 for v in children)
    assert sum(children) <= sec["compile_capture"]
    # create_states ran inside the build: the union does not add it again
    assert sec["state_build"] == pytest.approx(
        sec["block_initialize"] + sec["step_build"])
    assert sec["first_run"] >= 0
    assert sec["accounted"] <= rep["until"] - rep["from"] + 1e-9
    assert rep["recompiles"] == []
    assert set(diagnostics.startup_report(cache=True)["cache"]) == {
        "dir", "entries", "bytes", "modules", "modules_left_out"}


def test_a_sound_step_is_traced_lowered_and_obtained_once(fresh):
    step, x, y = _toy_train_step()
    for _ in range(3):
        step(x, y)
    st = diagnostics.startup_report()["step"]
    assert (st["fun"], st["traced"], st["lowered"], st["obtained"]) == \
        ("whole_step", 1, 1, 1)
    assert st["loaded"] + st["built"] == 1
    assert step.jit_trace_count() == 1


def test_the_report_outlives_the_ring(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    before = diagnostics.startup_report()
    for k in range(5000):
        with spans.span("filler"):
            pass
    assert not any(r["name"].startswith(("xla.", "train_step"))
                   for r in spans.records())
    assert diagnostics.startup_report() == before
    step(x, y)                           # a warm step takes no reduction
    assert diagnostics.startup_report() == before


def test_a_later_compile_is_a_second_entry(fresh):
    step, x, y = _toy_train_step()
    step(x, y)
    step(mx.np.ones((7, 6)), mx.np.zeros((7, 4)))   # a new shape: retrace
    rep = diagnostics.startup_report()
    assert rep["step"]["traced"] == 1
    (again,) = rep["recompiles"]
    assert again["step"]["traced"] == 1 and again["step"]["obtained"] == 1
    assert again["from"] >= rep["until"] - 1e-3
    assert again["seconds"]["step_build"] == 0.0


def test_before_any_step_the_report_reads_the_ring_as_it_is(fresh):
    fn, name = _program()
    fn(jnp.arange(5.0))
    rep = diagnostics.startup_report()
    assert rep["step"]["traced"] == 0 and rep["other_programs"] == 1
    assert [p["fun"] for p in rep["programs"]] == [name]
    assert rep["programs"][0]["in_step"] is False
    assert rep["seconds"]["other_programs"] > 0


def test_reduce_takes_unions_across_programs_and_sums_within_one():
    def x(stage, fun, t0, dur, **kv):
        return {"name": "xla." + stage, "t0": t0, "dur": dur, "fun": fun,
                **kv}

    recs = [
        x("trace", "kernel", 11.0, 1.0),            # inside the step's
        x("trace", "whole_step", 10.0, 4.0),
        x("lower", "whole_step", 14.0, 1.0),
        x("cache_load", "whole_step", 15.5, 2.0),
        x("backend", "whole_step", 15.0, 3.0, how="loaded"),
        {"name": "whole_step", "t0": 9.5, "dur": 9.0},
        x("lower", "whole_step", 19.0, 0.5),        # the capture's
        {"name": "train_step.compile_capture", "t0": 18.5, "dur": 1.5},
        {"name": "train_step", "t0": 9.0, "dur": 11.0},
        x("trace", "norms", 20.5, 0.5),
        x("backend", "norms", 20.75, 0.75, how="built"),   # overlapping
        {"name": "dataloader_next", "t0": 0.0, "dur": 5.0},  # not set-up's
    ]
    n_timeline = len(recs) - 1
    rep = startup.reduce(recs)
    sec = rep["seconds"]
    assert (sec["step_trace"], sec["step_lower"], sec["step_backend"],
            sec["step_cache_load"]) == (4.0, 1.5, 3.0, 2.0)
    assert sec["first_run"] == pytest.approx(9.0 - 8.0)
    assert sec["other_programs"] == pytest.approx(1.0)    # [20.5, 21.5]
    assert rep["other_programs"] == 1
    assert sec["accounted"] == pytest.approx(12.0)   # [9, 20] + [20.5, 21.5]
    assert rep["step"] == {"fun": "whole_step", "traced": 1, "lowered": 2,
                           "obtained": 1, "loaded": 1, "built": 0}
    by_fun = {p["fun"]: p for p in rep["programs"]}
    assert by_fun["kernel"]["in_step"] and not by_fun["norms"]["in_step"]
    assert (rep["records"], rep["from"], rep["until"]) == \
        (n_timeline, 9.0, 21.5)
    later = startup.reduce(recs, since=20.0)
    assert later["records"] == 2 and later["step"]["traced"] == 0


def test_cache_contents_groups_by_module_name(tmp_path):
    for name, size in (("jit_whole_step-" + "a" * 64 + "-cache", 900),
                       ("jit_whole_step-" + "b" * 64 + "-cache", 100),
                       ("jit__make-" + "c" * 64 + "-cache", 500),
                       ("jit_add-" + "d" * 64 + "-cache", 10),
                       ("jit_add-" + "d" * 64 + "-atime", 8)):
        (tmp_path / name).write_bytes(b"x" * size)
    got = startup.cache_contents(str(tmp_path))
    assert (got["entries"], got["bytes"]) == (4, 1510)
    assert [(m["module"], m["entries"], m["bytes"])
            for m in got["modules"]] == [
        ("jit_whole_step", 2, 1000), ("jit__make", 1, 500),
        ("jit_add", 1, 10)]
    assert got["modules_left_out"] == {"count": 0, "entries": 0, "bytes": 0}
    assert startup.cache_contents(str(tmp_path / "missing")) is None


def test_format_startup_table(fresh):
    assert "no start-up records" in diagnostics.format_startup_table()
    step, x, y = _toy_train_step()
    step(x, y)
    text = diagnostics.format_startup_table(
        diagnostics.startup_report(cache=True))
    for needle in ("import mxnet_tpu", "whole_step: trace",
                   "whole_step: first run", "compile capture",
                   "other programs (", "whole_step traced 1x, lowered 1x",
                   "compile cache "):
        assert needle in text, needle


def test_diagnose_prints_the_startup_report(fresh, capsys):
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools"))
    try:
        import diagnose
    finally:
        sys.path.pop(0)
    diagnose.main(["--steps", "2", "--startup"])
    out = capsys.readouterr().out
    assert "== start-up (s) " in out and "whole_step: trace" in out
    assert "compile cache " in out
