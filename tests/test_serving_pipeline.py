"""Pipelined continuous-batching engine (ISSUE 15): assembler/completer
overlap, in-flight joining, priority classes + token buckets, replica
front door, deadline-aware drain, padded-row leak pinning
(mxnet_tpu/serving/; docs/serving.md, docs/performance.md).

Timing tests use serving.SimulatedBlock — a deterministic serial device
stream (sleep-based, GIL released) — so wall-clock deltas measure the
pipeline, not CPU contention (see serving/sim.py for why real XLA-on-CPU
can't do this on a small box). Margins are deliberately loose (≥2x)
for noisy CI hosts.
"""
import threading
import time

import numpy as onp
import pytest

from mxnet_tpu import serving
from mxnet_tpu.serving import (EngineStopped, Overloaded, RateLimited,
                               RequestScheduler, ServeClass,
                               SimulatedBlock, TokenBucket)
from mxnet_tpu.serving.engine import ServeRequest


def sim_engine(device_ms=20.0, host_ms=0.0, mode="pipelined",
               max_batch=4, **kw):
    blk = SimulatedBlock(device_ms=device_ms, host_ms=host_ms)
    kw.setdefault("max_wait_ms", 1.0)
    kw.setdefault("timeout_ms", 30_000.0)
    return serving.InferenceEngine(blk, name=kw.pop("name", "sim"),
                                   max_batch_size=max_batch, mode=mode,
                                   **kw)


def x_rows(rows, features=4, value=1.0):
    return onp.full((rows, features), value, onp.float32)


# --- tentpole: host assembly overlaps device compute ------------------------

def test_pipelined_overlaps_host_and_device():
    """N full batches: sync pays N*(host+device); pipelined hides host
    work under the previous batch's device time."""
    n, dev, host = 6, 30.0, 20.0

    def run(mode):
        eng = sim_engine(device_ms=dev, host_ms=host, mode=mode,
                         max_batch=4, name=f"ovl-{mode}")
        with eng:
            # full-bucket requests: each is its own micro-batch
            t0 = time.perf_counter()
            reqs = [eng.submit(x_rows(4, value=i)) for i in range(n)]
            for r in reqs:
                r.result()
            wall = time.perf_counter() - t0
            seen = eng.stats()["max_inflight_seen"]
        return wall, seen

    sync_wall, sync_seen = run("sync")
    # best of three: a wall-clock reading, beside five busy xdist workers
    pipe_wall, pipe_seen = min(run("pipelined") for _ in range(3))
    serialized = n * (dev + host) / 1e3
    assert sync_seen == 1
    assert pipe_seen >= 2  # the window actually ran ahead
    # the serialized baseline really pays the sum...
    assert sync_wall >= serialized * 0.9
    # ...and the pipeline is strictly under it (host time hidden)
    assert pipe_wall < serialized * 0.9
    assert pipe_wall < sync_wall


def test_inflight_joining_bounds_late_request_wait():
    """A request arriving while a batch is in flight is dispatched by
    the NEXT assembly — it never waits out the current round trip."""
    dev = 80.0
    eng = sim_engine(device_ms=dev, max_batch=4, name="join")
    with eng:
        first = eng.submit(x_rows(4))       # full bucket: dispatches alone
        time.sleep(0.015)                   # first is now in flight
        late = eng.submit(x_rows(1))
        t_submit = late.t_submit
        late.result()
        # dispatched well inside the first batch's device window — a
        # serialized engine would hold it for the full ~80ms round trip
        assert late.t_dispatch is not None
        assert (late.t_dispatch - t_submit) < dev / 1e3 / 2
    assert first.outcome == "ok"


def test_pipelined_default_and_sync_opt_in():
    eng = sim_engine(name="mode-default")
    assert eng.mode == "pipelined"
    assert sim_engine(mode="sync", name="mode-sync").mode == "sync"
    with pytest.raises(ValueError):
        sim_engine(mode="bogus", name="mode-bad")


# --- priority-class scheduler -----------------------------------------------

def _req(cls="interactive", rows=1, sig=("s",), deadline=None):
    return ServeRequest((), rows, sig, deadline, cls=cls)


def test_strict_priority_dequeue():
    s = RequestScheduler("sched-prio", max_queue=16)
    b1, b2, i1 = _req("batch"), _req("batch"), _req("interactive")
    s.offer(b1)
    s.offer(b2)
    s.offer(i1)
    # interactive head first despite arriving last; batch stays FIFO
    assert s.collect(1, 0.0) == [i1]
    assert s.collect(1, 0.0) == [b1]
    assert s.collect(1, 0.0) == [b2]


def test_batch_fill_is_signature_safe_and_priority_ordered():
    s = RequestScheduler("sched-fill", max_queue=16)
    head = _req("interactive", sig=("A",))
    ride = _req("batch", sig=("A",))
    other = _req("batch", sig=("B",))
    s.offer(head)
    s.offer(ride)
    s.offer(other)
    batch = s.collect(8, 0.0)
    # batch-class same-signature work rides along; the mismatched head
    # is never scanned past (FIFO preserved), so ("B",) waits its turn
    assert batch == [head, ride]
    assert s.collect(8, 0.0) == [other]


def test_token_bucket_rate_limits_per_class():
    classes = (ServeClass("interactive", 0, rate=1000.0, burst=2),
               ServeClass("batch", 10))
    s = RequestScheduler("sched-rate", classes=classes, max_queue=64)
    s.offer(_req("interactive"))
    s.offer(_req("interactive"))
    with pytest.raises(RateLimited):
        s.offer(_req("interactive"))
    s.offer(_req("batch"))  # other classes unaffected
    st = s.class_stats()
    assert st["interactive"]["shed_rate"] >= 1
    assert st["batch"]["shed_rate"] == 0
    # RateLimited IS an Overloaded: legacy shed handling still catches it
    assert issubclass(RateLimited, Overloaded)


def test_queue_bound_sheds_overloaded_with_reason():
    s = RequestScheduler("sched-bound", max_queue=2)
    s.offer(_req())
    s.offer(_req("batch"))
    with pytest.raises(Overloaded):
        s.offer(_req())
    assert s.class_stats()["interactive"]["shed_queue"] >= 1


def test_token_bucket_refills():
    tb = TokenBucket(rate=200.0, burst=1)
    assert tb.try_take()
    assert not tb.try_take()
    time.sleep(0.02)  # 200/s -> a token every 5ms
    assert tb.try_take()


def test_engine_strict_priority_under_backlog():
    """Queued before start: interactive requests dispatch before ALL
    batch-class ones, regardless of arrival order."""
    eng = sim_engine(device_ms=10.0, max_batch=1, name="prio-engine")
    batch = [eng.submit(x_rows(1), priority="batch") for _ in range(3)]
    inter = [eng.submit(x_rows(1)) for _ in range(2)]  # default class
    with eng:
        for r in batch + inter:
            r.result()
    assert max(r.t_dispatch for r in inter) < \
        min(r.t_dispatch for r in batch)


def test_engine_rate_limit_sheds_batch_not_interactive():
    classes = (ServeClass("interactive", 0),
               ServeClass("batch", 10, rate=100.0, burst=3))
    eng = sim_engine(device_ms=5.0, max_batch=8, classes=classes,
                     name="rate-engine")
    with eng:
        ok = shed = 0
        for _ in range(10):  # burst 3: most of these shed
            try:
                eng.submit(x_rows(1), priority="batch")
                ok += 1
            except RateLimited:
                shed += 1
        assert shed >= 5 and ok >= 3
        assert eng.predict(x_rows(1)) is not None  # interactive sails
    st = eng.stats()["classes"]
    assert st["batch"]["shed_rate"] == shed
    assert st["interactive"]["shed_rate"] == 0


def test_unknown_priority_class_rejected():
    eng = sim_engine(name="prio-unknown")
    with pytest.raises(ValueError):
        eng.submit(x_rows(1), priority="vip")


# --- replica front door -----------------------------------------------------

def test_frontdoor_least_loaded_skips_unhealthy():
    engines = [sim_engine(device_ms=100.0, max_batch=4, name=f"fd/{i}")
               for i in range(3)]
    for e in engines:
        e.start()
    engines[2].stop(drain=False)  # unhealthy replica
    fd = serving.FrontDoor(engines, name="fd")
    reqs = [fd.submit(x_rows(1)) for _ in range(4)]
    st = fd.stats()
    # the stopped replica got nothing; the healthy pair shared the load
    assert st["replicas"]["fd/2"]["routed"] == 0
    assert st["replicas"]["fd/2"]["healthy"] is False
    assert st["replicas"]["fd/0"]["routed"] >= 1
    assert st["replicas"]["fd/1"]["routed"] >= 1
    assert st["replicas"]["fd/0"]["routed"] + \
        st["replicas"]["fd/1"]["routed"] == 4
    for r in reqs:
        r.result()
    for e in engines[:2]:
        e.stop()
    with pytest.raises(EngineStopped):
        fd.submit(x_rows(1))  # no healthy replica left


def test_frontdoor_fails_over_on_shed_then_overloads():
    engines = [sim_engine(device_ms=200.0, max_batch=1, max_queue=1,
                          name=f"fds/{i}") for i in range(2)]
    # permissive health check so the SHED failover path is what's tested
    # (the default admission_state check would drop full replicas first)
    fd = serving.FrontDoor(engines, name="fds",
                           health_check=lambda e: True)
    fd.submit(x_rows(1))  # fills replica 0's 1-deep queue
    fd.submit(x_rows(1))  # replica 0 sheds -> fails over to replica 1
    assert sorted(st["routed"] for st in fd.stats()["replicas"].values()) \
        == [1, 1]
    with pytest.raises(Overloaded):
        fd.submit(x_rows(1))  # every replica at bound
    for e in engines:
        e.stop(drain=False)


def test_registry_replica_sets():
    reg = serving.ModelRegistry()
    engines = [sim_engine(device_ms=5.0, name=f"m/{i}") for i in range(2)]
    fd = reg.register_replicas("m", engines)
    assert reg.names() == ["m/0", "m/1"]  # each replica health-checkable
    assert reg.frontdoor("m") is fd
    out = fd.predict(x_rows(2))
    assert out.asnumpy().shape == (2, 4)
    with pytest.raises(ValueError):
        reg.register_replicas("m", engines)
    reg.unregister_replicas("m")
    assert reg.names() == []
    with pytest.raises(KeyError):
        reg.frontdoor("m")


# --- deadline-aware bounded drain -------------------------------------------

def test_stop_drain_never_started_force_drops():
    eng = sim_engine(device_ms=50.0, name="drain-cold")
    r = eng.submit(x_rows(1))
    eng.stop(drain=True)  # nothing will ever serve it: drop NOW
    with pytest.raises(EngineStopped):
        r.result()
    assert eng.stats()["drain_dropped"] >= 1


def test_stop_drain_bounded_by_timeout():
    eng = sim_engine(device_ms=100.0, max_batch=1, name="drain-bound")
    with eng:
        reqs = [eng.submit(x_rows(1)) for _ in range(8)]  # ~800ms backlog
        t0 = time.perf_counter()
        eng.stop(drain=True, drain_timeout_ms=150.0)
        wall = time.perf_counter() - t0
    assert wall < 1.5  # bounded: nowhere near the 800ms backlog
    outcomes = set()
    for r in reqs:
        try:
            r.result()
            outcomes.add("ok")
        except EngineStopped:
            outcomes.add("dropped")
    assert "dropped" in outcomes  # the backlog was force-dropped...
    assert eng.stats()["drain_dropped"] >= 1  # ...and counted


def test_stop_drain_capped_by_latest_deadline():
    """Draining past the last queued deadline is pointless — stop()
    returns once everything left would have expired anyway."""
    eng = sim_engine(device_ms=200.0, max_batch=1, name="drain-dl")
    with eng:
        for _ in range(6):
            eng.submit(x_rows(1), timeout_ms=120.0)
        t0 = time.perf_counter()
        eng.stop(drain=True, drain_timeout_ms=30_000.0)
        wall = time.perf_counter() - t0
    assert wall < 5.0  # capped by the ~120ms deadline, not the 30s knob


# --- padded rows never leak (satellite: buckets.py pinning) ------------------

def test_pad_rows_never_leak_every_rung_and_edge():
    """Every ladder rung × every row count (including rows == bucket):
    the result is exactly the input rows — bucket padding is invisible."""
    eng = sim_engine(device_ms=1.0, max_batch=8, name="pad-leak",
                     max_wait_ms=0.0)
    ladder = eng.buckets
    assert ladder == (1, 2, 4, 8)
    with eng:
        for rung in ladder:
            lo = 1 if rung == 1 else ladder[ladder.index(rung) - 1] + 1
            for rows in range(lo, rung + 1):  # interior AND rows==bucket
                x = onp.arange(rows * 4, dtype=onp.float32).reshape(rows, 4)
                out = eng.predict(x).asnumpy()
                assert out.shape == (rows, 4), (rung, rows)
                assert (out == x).all(), (rung, rows)
    # the identity block saw PADDED batches throughout: leaks would show
    assert eng.stats()["requests"]["ok"] == 8


def test_assemble_then_slice_roundtrip_direct():
    """buckets-level pinning, no engine: pad + slice is lossless for
    every rung, including the exact-fit edge (no-copy path)."""
    ladder = serving.bucket_ladder(8)
    for rung in ladder:
        for rows in range(1, rung + 1):
            a = onp.arange(rows * 3, dtype=onp.float32).reshape(rows, 3)
            (out,) = serving.assemble_batch([(a,)], rung)
            assert out.shape == (rung, 3)
            assert (out[:rows] == a).all(), (rung, rows)
            if rows == rung:  # exact-fit edge: pad_rows is the identity
                assert serving.pad_rows(a, rung) is a


# --- zero-retrace invariant through the pipeline ----------------------------

def test_pipelined_engine_preserves_zero_retrace():
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn

    mx.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    net.hybridize()
    eng = serving.InferenceEngine(net, name="retrace-pipe",
                                  max_batch_size=4, max_wait_ms=1.0)
    assert eng.mode == "pipelined"
    eng.warmup(mx.np.zeros((1, 6)))
    with eng:
        for rows in (1, 2, 3, 4, 1, 3):
            out = eng.predict(onp.ones((rows, 6), onp.float32))
            assert out.asnumpy().shape == (rows, 3)
    assert eng.recompiles_since_warmup() == 0
    assert eng.stats()["recompiles_since_warmup"] == 0


# --- soak (tier-2) ----------------------------------------------------------

@pytest.mark.slow
def test_open_loop_soak_interactive_bounded_under_overload():
    """Sustained overload: interactive latency stays bounded while the
    batch class absorbs the shedding (strict priority end to end)."""
    # queue bound below the flooder population so overload actually sheds
    eng = sim_engine(device_ms=15.0, max_batch=4, max_queue=4,
                     name="soak", timeout_ms=2000.0)
    lat = {"interactive": [], "batch": []}
    shed = {"interactive": 0, "batch": 0}
    lock = threading.Lock()
    stop = threading.Event()

    def client(cls, gap_s):
        while not stop.is_set():
            t0 = time.perf_counter()
            try:
                eng.predict(x_rows(1), priority=cls)
                with lock:
                    lat[cls].append(time.perf_counter() - t0)
            except (Overloaded, serving.RequestTimeout):
                with lock:
                    shed[cls] += 1
            stop.wait(gap_s)

    def burst_flooder():
        # open-loop-ish: 6 outstanding per flooder, so the queue bound
        # is genuinely exceeded and the batch class sheds
        while not stop.is_set():
            reqs = []
            for _ in range(6):
                try:
                    reqs.append(eng.submit(x_rows(1), priority="batch"))
                except Overloaded:
                    with lock:
                        shed["batch"] += 1
            for r in reqs:
                try:
                    r.result()
                    with lock:
                        lat["batch"].append(
                            time.perf_counter() - r.t_submit)
                except Exception:
                    pass

    with eng:
        threads = [threading.Thread(target=burst_flooder)
                   for _ in range(4)]
        threads += [threading.Thread(target=client, args=("interactive",
                                                          0.02))
                    for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(4.0)
        stop.set()
        for t in threads:
            t.join()
    inter = sorted(lat["interactive"])
    assert len(inter) >= 10
    p95 = inter[int(0.95 * (len(inter) - 1))]
    # interactive p95 ~ a few batch round trips, not the queue backlog
    assert p95 < 0.5
    # the overload went somewhere: the batch class shed
    assert shed["batch"] > 0
    st = eng.stats()["classes"]
    assert st["interactive"]["priority"] < st["batch"]["priority"]
