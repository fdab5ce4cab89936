"""Fused multi-tensor update path (docs/performance.md):
numerical equivalence vs the legacy per-param loop, dispatch-count /
retrace budgets, donation semantics, stale-grad interaction, and the
bucketed flat allreduce."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, np as mnp, optimizer, telemetry
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.telemetry import instruments as ti

rs = onp.random.RandomState(7)


def _param_set(seed, n=8, dtype="float32"):
    r = onp.random.RandomState(seed)
    ws, gs = [], []
    for k in range(n):
        shape = (3 + k % 4, 5)
        ws.append(mnp.array(r.randn(*shape).astype("float32"),
                            dtype=dtype))
        gs.append(mnp.array(r.randn(*shape).astype("float32"),
                            dtype=dtype))
    return ws, gs


def _run(opt_name, opt_kwargs, fused, monkeypatch, dtype="float32",
         steps=3, n=8, multi_precision=False):
    """`steps` list-form updates; returns (weights, states) numpy."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1" if fused else "0")
    opt = optimizer.create(opt_name, **opt_kwargs)
    ws, gs = _param_set(11, n=n, dtype=dtype)
    states = [opt.create_state_multi_precision(i, w)
              for i, w in enumerate(ws)]
    for _ in range(steps):
        if multi_precision:
            opt.update_multi_precision(list(range(n)), ws, gs, states)
        else:
            opt.update(list(range(n)), ws, gs, states)
    return ([w.asnumpy().astype("float32") for w in ws],
            [onp.asarray(s[0].asnumpy()) if isinstance(s, tuple)
             and isinstance(s[0], NDArray) else None for s in states])


CONFIGS = [
    ("sgd", {"learning_rate": 0.05, "wd": 0.01}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9}),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
             "clip_gradient": 0.3}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01, "wd": 0.02}),
    ("adam", {"learning_rate": 0.01, "clip_gradient": 0.25}),
]


@pytest.mark.parametrize("name,kwargs", CONFIGS)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_bitwise_matches_legacy(name, kwargs, dtype, monkeypatch):
    """Fused bucketed updates must be BITWISE identical to the legacy
    per-param loop: same op order, same weak-scalar dtype promotion."""
    fused_w, _ = _run(name, kwargs, True, monkeypatch, dtype=dtype)
    legacy_w, _ = _run(name, kwargs, False, monkeypatch, dtype=dtype)
    for fw, lw in zip(fused_w, legacy_w):
        assert onp.array_equal(fw, lw)


@pytest.mark.parametrize("name", ["sgd", "adam", "nag"])
def test_fused_multi_precision_bitwise(name, monkeypatch):
    """bf16 weights + f32 master (multi_precision): fused must cast the
    grad to f32 FIRST (legacy update_multi_precision order), yielding
    bitwise-equal bf16 weights AND f32 masters."""
    kw = {"learning_rate": 0.05, "wd": 0.01, "multi_precision": True,
          "clip_gradient": 0.5}
    if name != "adam":
        kw["momentum"] = 0.9
    fused_w, fused_m = _run(name, kw, True, monkeypatch,
                            dtype="bfloat16", multi_precision=True)
    legacy_w, legacy_m = _run(name, kw, False, monkeypatch,
                              dtype="bfloat16", multi_precision=True)
    for fw, lw in zip(fused_w, legacy_w):
        assert onp.array_equal(fw, lw)
    for fm, lm in zip(fused_m, legacy_m):
        assert fm is not None and onp.array_equal(fm, lm)


def test_clip_global_norm_matches_reference(monkeypatch):
    """clip_global_norm scales the WHOLE gradient set by
    min(1, max_norm/||g||) before the rule."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    opt = optimizer.SGD(learning_rate=0.1, clip_global_norm=0.5)
    ws, gs = _param_set(3, n=4)
    w0 = [w.asnumpy() for w in ws]
    g0 = [g.asnumpy() for g in gs]
    states = [opt.create_state(i, w) for i, w in enumerate(ws)]
    opt.update(list(range(4)), ws, gs, states)
    total = onp.sqrt(sum(float((g.astype("float64") ** 2).sum())
                         for g in g0))
    scale = min(1.0, 0.5 / total)
    for w, wo, go in zip(ws, w0, g0):
        onp.testing.assert_allclose(
            w.asnumpy(), wo - 0.1 * (go * scale), rtol=1e-5)


def test_clip_global_norm_under_bound_is_identity(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    opt = optimizer.SGD(learning_rate=0.1, clip_global_norm=1e9)
    ws, gs = _param_set(4, n=3)
    w0 = [w.asnumpy() for w in ws]
    g0 = [g.asnumpy() for g in gs]
    states = [opt.create_state(i, w) for i, w in enumerate(ws)]
    opt.update(list(range(3)), ws, gs, states)
    for w, wo, go in zip(ws, w0, g0):
        onp.testing.assert_allclose(w.asnumpy(), wo - 0.1 * go,
                                    rtol=1e-6)


def _counter(path):
    return ti.update_dispatch_total.labels(path).value


def test_list_update_is_single_dispatch(monkeypatch):
    """Satellite: the list-input path must run ONE fused dispatch for a
    same-dtype param set, not recurse per element."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    telemetry.enable()
    try:
        opt = optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ws, gs = _param_set(5, n=12)
        states = [opt.create_state(i, w) for i, w in enumerate(ws)]
        opt.update(list(range(12)), ws, gs, states)  # warm the cache
        fused0, per0 = _counter("fused"), _counter("per_param")
        opt.update(list(range(12)), ws, gs, states)
        assert _counter("fused") - fused0 == 1
        assert _counter("per_param") - per0 == 0
    finally:
        telemetry.disable()


def test_env_opt_out_restores_per_param_loop(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "0")
    telemetry.enable()
    try:
        opt = optimizer.SGD(learning_rate=0.1)
        ws, gs = _param_set(6, n=5)
        states = [opt.create_state(i, w) for i, w in enumerate(ws)]
        fused0, per0 = _counter("fused"), _counter("per_param")
        opt.update(list(range(5)), ws, gs, states)
        assert _counter("fused") - fused0 == 0
        assert _counter("per_param") - per0 == 5
    finally:
        telemetry.disable()


def _fused_trace_count():
    return sum(child.value
               for labels, child in ti.jit_trace_total.series()
               if labels and labels[0] == "fused_update")


def test_trainer_5step_dispatch_and_retrace_budget(monkeypatch):
    """Acceptance: a 5-step loop over a ≥50-param model runs ≤3
    optimizer jit dispatches per step with ZERO retraces after step 1
    despite an LR schedule."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    params = []
    for k in range(55):
        p = gluon.Parameter(f"p{k}", shape=(2 + k % 3, 4))
        p.initialize()
        params.append(p)
    trainer = gluon.Trainer(params, "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})

    def backward():
        for p in params:
            g = p.grad()
            g._data = mnp.array(
                rs.randn(*p.shape).astype("float32"))._data
            g._version += 1

    telemetry.enable()
    try:
        per_step = []
        traces = []
        for step in range(5):
            trainer.set_learning_rate(0.1 / (step + 1))  # LR schedule
            backward()
            before = sum(_counter(p) for p in
                         ("fused", "fused_norm", "per_param", "sparse"))
            t_before = _fused_trace_count()
            trainer.step(1)
            after = sum(_counter(p) for p in
                        ("fused", "fused_norm", "per_param", "sparse"))
            t_after = _fused_trace_count()
            per_step.append(after - before)
            traces.append(t_after - t_before)
        assert all(d <= 3 for d in per_step), per_step
        assert all(t == 0 for t in traces[1:]), traces
    finally:
        telemetry.disable()


def test_donation_reuses_buffers(monkeypatch):
    """Weights/states are donated into the fused dispatch: the old
    buffers die (XLA reuses their memory) and the donated-bytes counter
    advances."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    monkeypatch.setenv("MXTPU_DONATE_UPDATE", "1")
    telemetry.enable()
    try:
        opt = optimizer.SGD(learning_rate=0.1, momentum=0.9)
        ws, gs = _param_set(8, n=4)
        states = [opt.create_state(i, w) for i, w in enumerate(ws)]
        old = [w._data for w in ws]
        before = ti.update_donated_bytes.value
        opt.update(list(range(4)), ws, gs, states)
        assert ti.update_donated_bytes.value > before
        assert all(o.is_deleted() for o in old)
        # the containers hold live results
        for w in ws:
            assert onp.isfinite(w.asnumpy()).all()
    finally:
        telemetry.disable()


def test_donation_guard_on_aliased_grad(monkeypatch):
    """A call whose grad IS the weight buffer (aliased test arrays) must
    fall back to the copying variant instead of tripping XLA's
    donated-buffer check."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    monkeypatch.setenv("MXTPU_DONATE_UPDATE", "1")
    opt = optimizer.SGD(learning_rate=0.1)
    w = mnp.array(rs.randn(4, 3).astype("float32"))
    g = NDArray(w._data)  # same underlying buffer
    w0 = w.asnumpy()
    opt.update(0, w, g, opt.create_state(0, w))
    onp.testing.assert_allclose(w.asnumpy(), w0 - 0.1 * w0, rtol=1e-6)
    # grad's buffer must still be alive (it was never donated)
    assert not g._data.is_deleted()


def test_donation_env_opt_out(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    monkeypatch.setenv("MXTPU_DONATE_UPDATE", "0")
    opt = optimizer.SGD(learning_rate=0.1)
    ws, gs = _param_set(9, n=3)
    old = [w._data for w in ws]
    states = [opt.create_state(i, w) for i, w in enumerate(ws)]
    opt.update(list(range(3)), ws, gs, states)
    assert not any(o.is_deleted() for o in old)


def test_sgld_falls_back_to_legacy(monkeypatch):
    """SGLD overrides update() (Langevin noise) — the fused router must
    leave it on its own path."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    opt = optimizer.SGLD(learning_rate=0.1)
    assert not opt._supports_fused()
    w = mnp.array(rs.randn(3, 2).astype("float32"))
    g = mnp.array(rs.randn(3, 2).astype("float32"))
    w0 = w.asnumpy()
    opt.update(0, w, g, None)
    assert not onp.array_equal(w.asnumpy(), w0)


def test_allreduce_skips_stale_grads(monkeypatch):
    """Satellite regression: with ignore_stale_grad=True, the bucketed
    allreduce must SKIP params whose grad buffer is stale — reducing one
    would bump its version, making update() mistake it for fresh."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    p0 = gluon.Parameter("p0", shape=(2, 2))
    p1 = gluon.Parameter("p1", shape=(2, 2))
    for p in (p0, p1):
        p.initialize()
    trainer = gluon.Trainer([p0, p1], "sgd", {"learning_rate": 0.1},
                            kvstore="tpu_dist")

    def set_grad(p, val):
        g = p.grad()
        g._data = mnp.full(p.shape, val)._data
        g._version += 1

    set_grad(p0, 1.0)
    set_grad(p1, 1.0)
    trainer.step(1)  # warm-up: both fresh, versions recorded
    w0_before = p0.data().asnumpy()
    w1_before = p1.data().asnumpy()
    stale_version = p1.grad()._version
    set_grad(p0, 2.0)  # only p0 gets a new gradient
    trainer.step(1, ignore_stale_grad=True)
    # p0 moved by -lr*g; p1 untouched — allreduce neither reduced its
    # stale buffer nor bumped its version
    onp.testing.assert_allclose(p0.data().asnumpy(), w0_before - 0.2,
                                rtol=1e-6)
    onp.testing.assert_allclose(p1.data().asnumpy(), w1_before)
    assert p1.grad()._version == stale_version


def test_pushpull_fused_multi_copy_reduce(monkeypatch):
    """tpu_dist list-form pushpull: dtype-homogeneous buckets reduce
    device copies in one flat dispatch, writing every copy back."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    kv = mx.kvstore.create("tpu_dist")
    a = [mnp.full((3,), 1.0), mnp.full((3,), 2.0)]
    b = [mnp.full((2, 2), 3.0), mnp.full((2, 2), 5.0)]
    outs = [[mnp.zeros((3,)), mnp.zeros((3,))],
            [mnp.zeros((2, 2)), mnp.zeros((2, 2))]]
    kv.pushpull([0, 1], [a, b], out=outs)
    for o in outs[0]:
        onp.testing.assert_allclose(o.asnumpy(), onp.full((3,), 3.0))
    for o in outs[1]:
        onp.testing.assert_allclose(o.asnumpy(), onp.full((2, 2), 8.0))


def test_pushpull_fused_respects_bucket_cap(monkeypatch):
    """Buffers above MXTPU_FUSED_BUCKET_MB split into multiple buckets;
    results stay correct."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    monkeypatch.setenv("MXTPU_FUSED_BUCKET_MB", "1")
    kv = mx.kvstore.create("tpu_dist")
    n = 300_000  # 1.2 MB per f32 tensor > 1 MB cap → one bucket each
    vals = [[mnp.full((n,), 1.0), mnp.full((n,), 2.0)] for _ in range(2)]
    outs = [[mnp.zeros((n,)), mnp.zeros((n,))] for _ in range(2)]
    kv.pushpull([0, 1], vals, out=outs)
    for pair in outs:
        for o in pair:
            assert float(o.asnumpy()[0]) == 3.0


@pytest.mark.parametrize("name,kwargs,mp", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01,
             "multi_precision": True}, True),
    ("adam", {"learning_rate": 0.01, "wd": 0.02}, False),
    ("sgd", {"learning_rate": 0.05, "clip_global_norm": 0.5}, False),
], ids=["sgd_masters", "adam", "clip_global_norm"])
def test_fused_operands_are_packed(name, kwargs, mp, monkeypatch):
    """A bucket's call carries no Python scalar and four host arrays
    (lrs, wds, update counts, hyper-parameters + scale) whatever the
    bucket's size; their values change from step to step on one trace."""
    import jax

    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    calls, fns = [], []
    fused_jitted = optimizer.Optimizer._fused_jitted

    def recording(self, n, use_mp, donate):
        fn = fused_jitted(self, n, use_mp, donate)
        fns.append(fn)

        def call(*args):
            calls.append(args)
            return fn(*args)
        return call

    monkeypatch.setattr(optimizer.Optimizer, "_fused_jitted", recording)
    n = 9
    opt = optimizer.create(name, **kwargs)
    ws, gs = _param_set(13, n=n, dtype="bfloat16" if mp else "float32")
    for k, w in enumerate(ws):
        opt.lr_mult[k], opt.wd_mult[k] = 1.0 + k, 0.5 * k
    states = [opt.create_state_multi_precision(i, w)
              for i, w in enumerate(ws)]
    for k in range(3):
        opt.set_learning_rate(0.05 / (k + 1))
        if mp:
            opt.update_multi_precision(list(range(n)), ws, gs, states)
        else:
            opt.update(list(range(n)), ws, gs, states)
    assert len(calls) == 3 and len({id(f) for f in fns}) == 1
    assert fns[0]._cache_size() == 1
    for k, args in enumerate(calls):
        leaves = jax.tree_util.tree_leaves(args)
        assert not [x for x in leaves if isinstance(x, (bool, int, float))]
        assert sum(isinstance(x, onp.ndarray) for x in leaves) == 4
        lrs, wds, ts, hvec = args[3:]
        assert [(v.dtype.name, v.shape) for v in (lrs, wds, ts)] == [
            ("float32", (n,)), ("float32", (n,)), ("int32", (n,))]
        assert (ts == k + 1).all()
        assert onp.array_equal(
            lrs, onp.float32([0.05 / (k + 1) * (1.0 + j)
                              for j in range(n)]))
        assert len(set(wds)) == (n if opt.wd else 1)
        assert hvec.dtype == onp.float32 \
            and hvec.shape == (len(opt._hyper()) + 2,)
    if opt.clip_global_norm is not None:
        assert 0.0 < calls[0][-1][-1] < 1.0  # the global-norm scale


def test_fused_compile_registry_records_bucket(monkeypatch):
    """diagnose.py reads fused-bucket composition from the compile
    registry — a fresh fused trace must land there under block
    'fused_update' with the composition-encoding variant."""
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "1")
    from mxnet_tpu import diagnostics

    opt = optimizer.NAG(learning_rate=0.02, momentum=0.9)
    ws, gs = _param_set(10, n=7)
    states = [opt.create_state(i, w) for i, w in enumerate(ws)]
    opt.update(list(range(7)), ws, gs, states)
    entries = [v for (b, v) in diagnostics.compile_registry()
               if b == "fused_update"]
    assert any("nag-n7-float32-mp0" == v for v in entries), entries
