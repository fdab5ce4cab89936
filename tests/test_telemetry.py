"""Telemetry subsystem tests: registry semantics (counters / gauges /
histograms, labels), enable/disable gating, the JSON and Prometheus
exporters (golden + format-validity parse), the chrome-trace bridge, and
an end-to-end hybridized training loop incrementing the framework's own
instruments (docs/telemetry.md)."""
import json
import math
import re
import threading

import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon, np, profiler, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.telemetry import (Counter, Gauge, Histogram, Registry,
                                 dump, prometheus_text)


@pytest.fixture
def fresh():
    """Global registry, enabled + zeroed, restored afterwards."""
    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if not was:
        telemetry.disable()


# -- registry semantics -----------------------------------------------------

def test_counter_semantics():
    r = Registry()
    c = r.counter("c_total", "doc")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value == 3.5


def test_gauge_semantics():
    r = Registry()
    g = r.gauge("g", "doc")
    g.set(10)
    g.inc(2)
    g.dec(0.5)
    assert g.value == 11.5
    g.set(-3)  # gauges go down
    assert g.value == -3.0


def test_histogram_semantics():
    r = Registry()
    h = r.histogram("h_seconds", "doc", buckets=(0.5, 1.0, 2.0))
    for v in (0.1, 0.5, 1.5, 99.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(101.1)
    cum = h._unlabeled().cumulative()
    assert cum == [(0.5, 2), (1.0, 2), (2.0, 3), (math.inf, 4)]


def test_histogram_buckets_sorted_and_validated():
    r = Registry()
    h = r.histogram("hs", buckets=(2.0, 0.5, 1.0))
    assert h.buckets == (0.5, 1.0, 2.0)
    with pytest.raises(ValueError):
        r.histogram("hbad", buckets=())
    with pytest.raises(ValueError):
        r.histogram("hinf", buckets=(1.0, float("inf")))


def test_label_handling():
    r = Registry()
    c = r.counter("req_total", "doc", ["code", "method"])
    c.labels("200", "GET").inc()
    c.labels(method="GET", code="200").inc()  # same child, kwarg order free
    c.labels(code=404, method="GET").inc(2)   # values stringified
    series = {lv: ch.value for lv, ch in c.series()}
    assert series == {("200", "GET"): 2.0, ("404", "GET"): 2.0}
    with pytest.raises(ValueError):
        c.labels("200")  # wrong arity
    with pytest.raises(ValueError):
        c.labels(code="200", verb="GET")  # wrong names
    with pytest.raises(ValueError):
        c.labels("200", method="GET")  # positional + keyword mix
    with pytest.raises(ValueError):
        c.inc()  # labeled metric requires .labels()


def test_name_validation_and_reregistration():
    r = Registry()
    with pytest.raises(ValueError):
        r.counter("bad name")
    with pytest.raises(ValueError):
        r.counter("ok_total", labelnames=["bad-label"])
    c = r.counter("dup_total", "doc", ["a"])
    assert r.counter("dup_total", "other doc", ["a"]) is c  # get-or-create
    with pytest.raises(ValueError):
        r.gauge("dup_total")  # type mismatch
    with pytest.raises(ValueError):
        r.counter("dup_total", labelnames=["a", "b"])  # labelset mismatch


def test_reset_keeps_registrations():
    r = Registry()
    c = r.counter("keep_total", "doc", ["k"])
    g = r.gauge("keep_g")
    c.labels("x").inc(5)
    g.set(7)
    r.reset()
    assert r.get("keep_total") is c
    assert c.series() == []  # labeled children dropped
    assert g.value == 0.0    # unlabeled series re-zeroed
    c.labels("x").inc()      # and still usable
    assert c.labels("x").value == 1.0


def test_thread_safety_counter():
    r = Registry()
    c = r.counter("t_total")

    def worker():
        for _ in range(1000):
            c.inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000.0


# -- enable/disable gating --------------------------------------------------

def test_disabled_registry_records_nothing():
    r = Registry(enabled=False)
    c = r.counter("off_total", "doc", ["l"])
    child = c.labels("x")  # cached handle must also honor the switch
    h = r.histogram("off_seconds", buckets=(1.0,))
    g = r.gauge("off_g")
    for _ in range(100):
        child.inc()
        h.observe(0.5)
        g.set(3)
        g.inc()
    assert child.value == 0.0
    assert h.count == 0 and h.sum == 0.0
    assert g.value == 0.0
    r.enabled = True
    child.inc()  # same cached child resumes recording
    assert child.value == 1.0


def test_module_toggle_and_record_helpers(fresh):
    inst = telemetry.instruments
    telemetry.disable()
    inst.record_compile("B", "train", 1.0)
    inst.record_transfer("h2d", 128)
    inst.record_sync("waitall", 0.1)
    inst.record_collective("psum", 64, 0.01)
    inst.record_fallback("B")
    inst.observe_step(0.5, examples=32)
    assert inst.jit_compile_total.series() == []
    assert inst.step_total.value == 0.0
    telemetry.enable()
    inst.record_compile("B", "train", 1.0)
    assert inst.jit_compile_total.labels("B", "train").value == 1.0


def test_nbytes_of():
    import numpy as onp
    nbytes_of = telemetry.instruments.nbytes_of
    assert nbytes_of(onp.zeros((4, 4), dtype=onp.float32)) == 64
    assert nbytes_of(object()) == 0


def test_mfu_and_examples_gauges(fresh):
    inst = telemetry.instruments
    inst.set_flop_budget(1e12, peak=2e12)
    inst.observe_step(None)          # first step: counted, not timed
    inst.observe_step(0.25, examples=64)
    assert inst.step_total.value == 2.0
    assert inst.step_time_seconds.count == 1
    assert inst.examples_per_second.value == pytest.approx(256.0)
    # 1e12 flops / 0.25 s / 2e12 peak = 2.0 (trivially >1 on fake budget)
    assert inst.mfu_ratio.value == pytest.approx(2.0)


# -- exporters --------------------------------------------------------------

def _golden_registry():
    r = Registry()
    c = r.counter("requests_total", "Total requests", ["code"])
    c.labels(code="200").inc()
    c.labels("404").inc(3)
    r.gauge("temp_celsius", "Temp").set(36.6)
    h = r.histogram("lat_seconds", "Latency", buckets=(0.5, 1.0))
    for v in (0.25, 0.5, 2.0):
        h.observe(v)
    return r


def test_prometheus_text_golden():
    golden = """\
# HELP requests_total Total requests
# TYPE requests_total counter
requests_total{code="200"} 1.0
requests_total{code="404"} 3.0
# HELP temp_celsius Temp
# TYPE temp_celsius gauge
temp_celsius 36.6
# HELP lat_seconds Latency
# TYPE lat_seconds histogram
lat_seconds_bucket{le="0.5"} 2
lat_seconds_bucket{le="1.0"} 2
lat_seconds_bucket{le="+Inf"} 3
lat_seconds_sum 2.75
lat_seconds_count 3
"""
    assert prometheus_text(_golden_registry()) == golden


def test_dump_structure_and_json_roundtrip():
    snap = dump(_golden_registry())
    snap = json.loads(json.dumps(snap))  # must be JSON-serializable
    assert snap["requests_total"]["type"] == "counter"
    assert {"labels": {"code": "404"}, "value": 3.0} \
        in snap["requests_total"]["samples"]
    hist = snap["lat_seconds"]["samples"][0]
    assert hist["count"] == 3
    assert hist["sum"] == pytest.approx(2.75)
    assert hist["buckets"] == {"0.5": 2, "1.0": 2, "+Inf": 3}


def test_label_escaping():
    r = Registry()
    r.counter("esc_total", 'say "hi"\nback\\slash', ["msg"]) \
        .labels('a"b\nc\\d').inc()
    text = prometheus_text(r)
    assert '# HELP esc_total say "hi"\\nback\\\\slash' in text
    assert 'esc_total{msg="a\\"b\\nc\\\\d"} 1.0' in text


_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$')


def test_exposition_format_validity(fresh):
    """Every line of the live registry's exposition output must parse:
    comments declare HELP/TYPE, samples match the format grammar, and
    every sample belongs to a declared metric family."""
    inst = telemetry.instruments
    inst.record_compile("Net", "train", 0.2)
    inst.record_transfer("h2d", 1024)
    inst.record_collective("psum", 256, 0.001)
    inst.observe_step(None)
    inst.observe_step(0.01, examples=8)
    text = prometheus_text()
    assert text.endswith("\n")
    types = {}
    for line in text.splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            assert typ in ("counter", "gauge", "histogram", "untyped")
            assert name not in types, f"duplicate TYPE for {name}"
            types[name] = typ
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        name = m.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in types or (base in types
                                 and types[base] == "histogram"), \
            f"sample {name} has no TYPE declaration"


def test_histogram_buckets_cumulative_in_exposition(fresh):
    inst = telemetry.instruments
    for s in (0.002, 0.02, 0.2, 2.0):
        inst.observe_step(s)
    text = prometheus_text()
    cums = [int(m.group(1)) for m in re.finditer(
        r'^step_time_seconds_bucket\{le="[^"]+"\} (\d+)$', text,
        re.MULTILINE)]
    assert cums == sorted(cums) and cums[-1] == 4  # +Inf == count


def test_write_prometheus(tmp_path):
    p = telemetry.write_prometheus(str(tmp_path / "metrics.prom"),
                                   _golden_registry())
    assert "requests_total" in open(p).read()


# -- chrome-trace bridge ----------------------------------------------------

def test_chrome_bridge_counter_events(tmp_path, fresh):
    r = Registry()
    r.counter("bridge_total", "doc", ["k"]).labels("x").inc(5)
    r.histogram("bridge_seconds", buckets=(1.0,)).observe(0.5)
    # earlier profiler tests may have left profile_all on; pin a clean
    # stopped state so the not-recording gate is actually exercised
    profiler.set_config(profile_all=False,
                        filename=str(tmp_path / "bridge.json"))
    profiler.set_state("stop")
    assert telemetry.emit_chrome_counters(r) == 0  # profiler not running
    profiler.set_config(profile_all=True,
                        filename=str(tmp_path / "bridge.json"))
    profiler.set_state("run")
    try:
        assert telemetry.emit_chrome_counters(r) == 3  # counter + hist x2
        profiler.dump()
    finally:
        # don't leak a recording profiler into later tests (it flips
        # their own not-recording gates)
        profiler.set_config(profile_all=False)
        profiler.set_state("stop")
    events = json.load(open(tmp_path / "bridge.json"))["traceEvents"]
    counters = {e["name"]: e["args"]["value"] for e in events
                if e.get("ph") == "C"}
    assert counters['bridge_total{k="x"}'] == 5.0
    assert counters["bridge_seconds_count"] == 1.0
    assert counters["bridge_seconds_sum"] == 0.5


# -- end to end: the framework's own instruments ----------------------------

def test_e2e_hybrid_training_loop_metrics(fresh):
    net = nn.Dense(1, in_units=2, use_bias=False)
    net.initialize(init=mx.initializer.Constant(1.0))
    net.hybridize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = np.array([[1.0, 2.0]])
    for _ in range(3):
        with autograd.record():
            y = net(x).sum()
        y.backward()
        tr.step(1)
    y.asnumpy()
    engine.waitall()

    snap = telemetry.dump()
    compiles = snap["jit_compile_total"]["samples"]
    assert {"labels": {"block": "Dense", "variant": "train"}, "value": 1.0} \
        in compiles, compiles  # one cache miss, then steady-state
    hist = snap["jit_compile_seconds"]["samples"][0]
    assert hist["count"] == 1 and hist["sum"] > 0

    assert snap["step_total"]["samples"][0]["value"] == 3.0
    step_hist = snap["step_time_seconds"]["samples"][0]
    assert step_hist["count"] == 2  # first step counted, not timed
    assert snap["examples_per_second"]["samples"][0]["value"] > 0

    directions = {s["labels"]["direction"]: s["value"]
                  for s in snap["transfer_total"]["samples"]}
    assert directions.get("h2d", 0) >= 1  # np.array(x)
    assert directions.get("d2h", 0) >= 1  # y.asnumpy()
    sites = {s["labels"]["site"]: s["value"]
             for s in snap["sync_total"]["samples"]}
    assert sites.get("waitall", 0) >= 1

    # and the same state round-trips through the text exporter
    assert 'jit_compile_total{block="Dense",variant="train"} 1.0' \
        in telemetry.prometheus_text()


def test_e2e_fallback_counter(fresh):
    from mxnet_tpu import npx

    class Dyn(nn.HybridBlock):
        def forward(self, data, index):
            return npx.boolean_mask(data, index)  # dynamic output shape

    net = Dyn()
    net.hybridize()
    with pytest.warns(UserWarning, match="dynamic-output"):
        out = net(np.array([[1.0], [2.0], [3.0]]), np.array([1, 0, 1]))
    assert out.shape == (2, 1)
    samples = telemetry.dump()["hybridize_fallback_total"]["samples"]
    assert {"labels": {"block": "Dyn"}, "value": 1.0} in samples


def test_kvstore_collective_metrics(fresh):
    from mxnet_tpu import kvstore

    kv = kvstore.create("tpu_dist")
    vals = [np.ones((8,))]
    outs = [np.zeros((8,))]
    kv.pushpull(0, vals, out=outs)
    ops = {s["labels"]["op"]: s["value"]
           for s in telemetry.dump()["collective_total"]["samples"]}
    assert ops.get("pushpull", 0) >= 1
    byts = {s["labels"]["op"]: s["value"]
            for s in telemetry.dump()["collective_bytes_total"]["samples"]}
    assert byts.get("pushpull", 0) >= 32  # 8 x float32


# -- promparse: the strict exposition checker round-trip --------------------

def test_promparse_roundtrips_golden():
    """parse_text is the inverse of prometheus_text on the golden
    registry: families, types, label values, and cumulative histogram
    buckets all survive the round trip."""
    from mxnet_tpu.telemetry import promparse

    fams = promparse.parse_text(prometheus_text(_golden_registry()))
    assert fams["requests_total"]["type"] == "counter"
    assert fams["requests_total"]["help"] == "Total requests"
    assert promparse.sample_value(fams, "requests_total",
                                  {"code": "404"}) == 3.0
    assert promparse.sample_value(fams, "temp_celsius") == 36.6
    h = fams["lat_seconds"]
    assert h["type"] == "histogram"
    buckets = [(s["labels"]["le"], s["value"]) for s in h["samples"]
               if s["name"] == "lat_seconds_bucket"]
    assert buckets == [("0.5", 2.0), ("1.0", 2.0), ("+Inf", 3.0)]
    assert promparse.sample_value(fams, "lat_seconds_sum") == \
        pytest.approx(2.75)
    assert promparse.sample_value(fams, "lat_seconds_count") == 3.0


def test_promparse_roundtrips_escaped_labels():
    from mxnet_tpu.telemetry import promparse

    r = Registry()
    r.counter("esc2_total", 'help with "quotes"\nand\\more', ["msg"]) \
        .labels('a"b\nc\\d').inc()
    fams = promparse.parse_text(prometheus_text(r))
    assert fams["esc2_total"]["help"] == 'help with "quotes"\nand\\more'
    assert fams["esc2_total"]["samples"][0]["labels"]["msg"] == \
        'a"b\nc\\d'


def test_promparse_roundtrips_live_registry(fresh):
    """The FULL live registry — every instrumented family after real
    training — parses strictly, and parsed values match dump()."""
    net = nn.Dense(4)
    net.initialize()
    net.hybridize()
    net(np.ones((2, 8)))
    telemetry.instruments.observe_step(0.01, examples=8)

    from mxnet_tpu.telemetry import promparse

    fams = promparse.parse_text(prometheus_text())
    snap = dump()
    assert set(fams) == set(snap)
    assert promparse.sample_value(fams, "step_total") == \
        snap["step_total"]["samples"][0]["value"]
    assert promparse.sample_value(
        fams, "step_time_seconds_count") == \
        snap["step_time_seconds"]["samples"][0]["count"]


def test_promparse_rejects_malformed_text():
    from mxnet_tpu.telemetry import promparse

    ok = "# TYPE x_total counter\nx_total 1\n"
    promparse.parse_text(ok)
    bad = [
        "x_total 1\n",                                  # no TYPE
        "# TYPE x_total counter\nx_total one\n",        # bad value
        "# TYPE x_total counter\nx_total{le=0.5} 1\n",  # unquoted label
        "# TYPE x_total counter\nx_total 1\n"
        "# TYPE x_total counter\n",                     # TYPE after samples
        "# TYPE x_total counter\n# TYPE x_total gauge\nx_total 1\n",
        "# TYPE x_total widget\nx_total 1\n",           # unknown type
        '# TYPE x_total counter\nx_total{a="b} 1\n',    # unclosed quote
    ]
    for text in bad:
        with pytest.raises(promparse.ExpositionError):
            promparse.parse_text(text)


def test_promparse_content_type_constant():
    """The /metrics Content-Type advertises exposition v0.0.4 — what
    Prometheus' scraper negotiates for the text format."""
    from mxnet_tpu.telemetry import promparse

    assert promparse.CONTENT_TYPE == \
        "text/plain; version=0.0.4; charset=utf-8"


# -- what JAX compiles, counted inside the program (ISSUE 25) ----------------

def _xla_counts():
    from mxnet_tpu.telemetry import instruments as ti

    programs = {k[0]: c.value for k, c in ti.xla_programs_total.series()}
    seconds = {k[0]: c.value
               for k, c in ti.xla_compile_seconds_total.series()}
    return programs, seconds


def test_compile_listener_counts_a_fresh_jit_once(fresh):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.observability import flight

    x = jnp.arange(7.0)
    x.block_until_ready()           # its own programs compile here
    f = jax.jit(lambda v: jnp.tanh(v * 3.25 + 0.125).sum())
    p0, s0 = _xla_counts()
    n_events = sum(e["kind"] == "xla_compile" for e in flight.events())
    f(x)
    p1, s1 = _xla_counts()
    got = {k: p1.get(k, 0) - p0.get(k, 0) for k in ("built", "loaded")}
    # one program: built, or loaded where a cache already held it
    assert sorted(got.values()) == [0, 1]
    for stage in ("trace", "lower", "backend"):
        assert s1[stage] > s0.get(stage, 0.0)
    if got["loaded"]:
        assert s1["cache_load"] > s0.get("cache_load", 0.0)
    else:
        assert s1.get("cache_load", 0.0) == s0.get("cache_load", 0.0)
    events = [e for e in flight.events() if e["kind"] == "xla_compile"]
    assert len(events) == n_events + 1
    assert events[-1]["how"] == ("loaded" if got["loaded"] else "built")
    assert events[-1]["seconds"] > 0
    f(x)                            # the second call compiles nothing
    assert _xla_counts() == (p1, s1)


def test_compile_listener_is_installed_once():
    from jax._src import monitoring

    from mxnet_tpu.telemetry import instruments as ti

    ti.install_compile_listener()
    ti.install_compile_listener()
    assert monitoring._event_duration_secs_listeners.count(
        ti._on_xla_duration) == 1


def test_compile_listener_silent_when_disabled(fresh):
    import jax
    import jax.numpy as jnp

    x = jnp.arange(5.0)
    x.block_until_ready()
    before = _xla_counts()
    telemetry.disable()
    try:
        jax.jit(lambda v: (v * 1.75 - 2.0).sum())(x)
    finally:
        telemetry.enable()
    assert _xla_counts() == before
