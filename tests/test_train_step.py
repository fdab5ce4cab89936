"""Whole-step compiled training path (gluon.TrainStep; ISSUE 6,
docs/performance.md): bitwise equivalence vs the legacy three-phase
sequence (fp32, bf16 multi-precision, kvstore='tpu_dist', BN aux state,
dropout RNG), the one-dispatch/zero-retrace acceptance proof, donation,
fallback routing, shard_map data parallelism, checkpoint interaction,
and the DataLoader device-prefetch overlap."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import gluon, np as mnp, telemetry
from mxnet_tpu.telemetry import instruments as ti

BATCH, FEATS, OUT = 8, 12, 4


def _net_plain(dtype=None):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(OUT))
    net.initialize()
    if dtype:
        net.cast(dtype)
    net.hybridize()
    return net


def _net_bn_dropout(dtype=None):
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.Dropout(0.5),
            gluon.nn.Dense(OUT))
    net.initialize()
    if dtype:
        net.cast(dtype)
    net.hybridize()
    return net


def _data(steps, dtype="float32"):
    r = onp.random.RandomState(3)
    xs = [mnp.array(r.standard_normal((BATCH, FEATS)).astype("float32"),
                    dtype=dtype) for _ in range(steps)]
    ys = [mnp.array(r.standard_normal((BATCH, OUT)).astype("float32"),
                    dtype=dtype) for _ in range(steps)]
    return xs, ys


def _run_path(whole, build_net, opt, opt_kwargs, steps=5, dtype=None,
              kvstore=None, lr_schedule=False):
    """Run `steps` iterations on one path; returns dict of final state."""
    mx.seed(0)
    net = build_net(dtype)
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), opt, dict(opt_kwargs),
                            kvstore=kvstore)
    xs, ys = _data(steps, dtype=dtype or "float32")
    mx.seed(99)  # same next_key sequence in both paths
    losses = []
    if whole:
        step = gluon.TrainStep(net, loss_fn, trainer)
        for k in range(steps):
            if lr_schedule:
                trainer.set_learning_rate(0.05 / (k + 1))
            loss = step(xs[k], ys[k])
            losses.append(loss.asnumpy().astype("float32").copy())
        assert step.last_path == "whole_step", step.ineligible_reason()
    else:
        for k in range(steps):
            if lr_schedule:
                trainer.set_learning_rate(0.05 / (k + 1))
            with ag.record():
                loss = loss_fn(net(xs[k]), ys[k])
            loss.backward()
            trainer.step(BATCH)
            losses.append(loss.asnumpy().astype("float32").copy())
    state = {
        "losses": losses,
        "num_update": trainer._optimizer.num_update,
        "counts": dict(trainer._optimizer._index_update_count),
        "params": {n: p.data().asnumpy().copy()
                   for n, p in sorted(net.collect_params().items())},
        "states": [],
    }
    from mxnet_tpu.ndarray.ndarray import NDArray

    def dump(s, out):
        if isinstance(s, NDArray):
            out.append(s.asnumpy().copy())
        elif isinstance(s, tuple):
            for x in s:
                dump(x, out)
    for s in trainer._states:
        acc = []
        dump(s, acc)
        state["states"].append(acc)
    return state


def _assert_same(a, b):
    for la, lb in zip(a["losses"], b["losses"]):
        assert onp.array_equal(la, lb)
    assert a["num_update"] == b["num_update"]
    assert a["counts"] == b["counts"]
    assert set(a["params"]) == set(b["params"])
    for n in a["params"]:
        assert onp.array_equal(a["params"][n], b["params"][n]), n
    for sa, sb in zip(a["states"], b["states"]):
        assert len(sa) == len(sb)
        for xa, xb in zip(sa, sb):
            assert onp.array_equal(xa, xb)


# -- bitwise equivalence -----------------------------------------------------

@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01, "wd": 0.02}),
])
def test_wholestep_bitwise_matches_phased_fp32(opt, kw):
    whole = _run_path(True, _net_plain, opt, kw)
    phased = _run_path(False, _net_plain, opt, kw)
    _assert_same(whole, phased)


def test_wholestep_bitwise_with_lr_schedule():
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    whole = _run_path(True, _net_plain, "sgd", kw, lr_schedule=True)
    phased = _run_path(False, _net_plain, "sgd", kw, lr_schedule=True)
    _assert_same(whole, phased)


@pytest.mark.parametrize("opt", ["sgd", "adam"])
def test_wholestep_bitwise_bf16_multi_precision(opt):
    """bf16 weights + f32 masters: the in-trace fused update must follow
    the legacy multi-precision op order (cast grad to f32 FIRST) — bf16
    weights AND f32 masters/states bitwise-equal, including update
    counts driving Adam's t."""
    kw = {"learning_rate": 0.05, "multi_precision": True}
    if opt == "sgd":
        kw["momentum"] = 0.9
    whole = _run_path(True, _net_plain, opt, kw, dtype="bfloat16")
    phased = _run_path(False, _net_plain, opt, kw, dtype="bfloat16")
    _assert_same(whole, phased)


def test_wholestep_bitwise_kvstore_tpu_dist():
    """kvstore='tpu_dist' single worker: the in-trace allreduce slot is
    the identity the eager pushpull computes — bitwise parity holds."""
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    whole = _run_path(True, _net_plain, "sgd", kw, kvstore="tpu_dist")
    phased = _run_path(False, _net_plain, "sgd", kw, kvstore="tpu_dist")
    _assert_same(whole, phased)


def test_wholestep_bitwise_bn_dropout_aux_state():
    """BatchNorm running stats flow through the whole-step program's aux
    output; Dropout draws from the same folded-key scheme the CachedOp
    uses — both must match the phased path bitwise."""
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    whole = _run_path(True, _net_bn_dropout, "sgd", kw)
    phased = _run_path(False, _net_bn_dropout, "sgd", kw)
    _assert_same(whole, phased)


# -- acceptance: one dispatch, zero retrace ----------------------------------

def _whole_trace_count():
    return sum(child.value
               for labels, child in ti.jit_trace_total.series()
               if labels and labels[0] == "whole_step")


def test_wholestep_one_dispatch_zero_retrace():
    """Acceptance: with MXTPU_WHOLE_STEP=1, Trainer.step work for a dense
    model is ONE jit dispatch per step — no separate optimizer dispatch —
    and an LR schedule causes ZERO retraces after step 1."""
    mx.seed(0)
    net = _net_plain(None)
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    step = gluon.TrainStep(net, loss_fn, trainer)
    xs, ys = _data(5)
    telemetry.enable()
    try:
        per_step, upd_per_step, traces = [], [], []
        for k in range(5):
            trainer.set_learning_rate(0.1 / (k + 1))  # LR schedule
            d0 = ti.step_dispatch_total.labels("whole_step").value
            u0 = sum(child.value for _, child in
                     ti.update_dispatch_total.series())
            t0 = _whole_trace_count()
            step(xs[k], ys[k])
            per_step.append(
                ti.step_dispatch_total.labels("whole_step").value - d0)
            upd_per_step.append(
                sum(child.value for _, child in
                    ti.update_dispatch_total.series()) - u0)
            traces.append(_whole_trace_count() - t0)
        assert per_step == [1] * 5, per_step
        # the optimizer update is INSIDE the whole-step program — no
        # separate fused/per-param dispatch fires
        assert upd_per_step == [0] * 5, upd_per_step
        assert traces[0] == 1 and traces[1:] == [0] * 4, traces
        assert step.jit_trace_count() == 1
    finally:
        telemetry.disable()


def test_wholestep_donation_reuses_buffers(monkeypatch):
    """Params and optimizer state donate into the step dispatch: the old
    buffers die (in-place reuse) and the donated-bytes counter advances."""
    monkeypatch.setenv("MXTPU_DONATE_UPDATE", "1")
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(2)
    step(xs[0], ys[0])  # build + first dispatch
    telemetry.enable()
    try:
        old = [p.data()._data
               for p in net.collect_params().values()]
        before = ti.step_donated_bytes.value
        step(xs[1], ys[1])
        assert ti.step_donated_bytes.value > before
        assert all(o.is_deleted() for o in old)
        for p in net.collect_params().values():
            assert onp.isfinite(
                p.data().asnumpy().astype("float32")).all()
    finally:
        telemetry.disable()


# -- fallback routing --------------------------------------------------------

def _phased_count():
    return ti.step_dispatch_total.labels("phased").value


def test_env_opt_out_runs_phased(monkeypatch):
    monkeypatch.setenv("MXTPU_WHOLE_STEP", "0")
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(1)
    telemetry.enable()
    try:
        before = _phased_count()
        step(xs[0], ys[0])
        assert step.last_path == "phased"
        assert _phased_count() - before == 1
    finally:
        telemetry.disable()


def test_overriding_optimizer_falls_back_with_reason():
    """SGLD overrides update() (Langevin noise) — TrainStep must route it
    to the phased path and say why."""
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "sgld",
                            {"learning_rate": 0.01})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(1)
    step(xs[0], ys[0])
    assert step.last_path == "phased"
    assert "SGLD" in step.ineligible_reason()


def test_clip_global_norm_falls_back():
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": 0.1, "clip_global_norm": 1.0})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(1)
    step(xs[0], ys[0])
    assert step.last_path == "phased"
    assert "clip_global_norm" in step.ineligible_reason()


def test_fallback_trains_identically_to_manual_loop():
    """The phased fallback must BE the legacy sequence, not an
    approximation: same params after 3 steps as a hand-written loop."""
    kw = {"learning_rate": 0.05, "momentum": 0.9}
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "sgd", dict(kw))
    xs, ys = _data(3)
    mx.seed(99)
    import os
    os.environ["MXTPU_WHOLE_STEP"] = "0"
    try:
        step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
        for k in range(3):
            step(xs[k], ys[k])
    finally:
        os.environ.pop("MXTPU_WHOLE_STEP", None)
    ref = _run_path(False, _net_plain, "sgd", kw, steps=3)
    for n, p in sorted(net.collect_params().items()):
        assert onp.array_equal(p.data().asnumpy(), ref["params"][n]), n


# -- data-parallel mesh ------------------------------------------------------

def test_wholestep_mesh_matches_single_device():
    """shard_map whole step on the 8-device CPU mesh: batch sharded over
    'dp', grads psum'd in-program — must match the single-device whole
    step numerically (order of the cross-shard sum differs, so allclose
    not bitwise) and keep the one-dispatch property."""
    import jax

    from jax.sharding import Mesh

    devs = onp.array(jax.devices())
    if devs.size < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh = Mesh(devs[:8], ("dp",))

    def run(mesh_arg):
        mx.seed(0)
        net = _net_plain(None)
        # per-sample loss (batch dim kept) — required under a mesh
        loss_fn = gluon.loss.L2Loss()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9})
        xs, ys = _data(3)
        mx.seed(99)
        step = gluon.TrainStep(net, loss_fn, trainer, mesh=mesh_arg)
        losses = []
        for k in range(3):
            losses.append(step(xs[k], ys[k]).asnumpy().copy())
        assert step.last_path == "whole_step", step.ineligible_reason()
        return losses, {n: p.data().asnumpy().copy()
                        for n, p in sorted(net.collect_params().items())}

    losses_m, params_m = run(mesh)
    losses_s, params_s = run(None)
    for lm, ls in zip(losses_m, losses_s):
        onp.testing.assert_allclose(lm, ls, rtol=1e-5, atol=1e-6)
    for n in params_s:
        onp.testing.assert_allclose(params_m[n], params_s[n],
                                    rtol=1e-5, atol=1e-6)


# -- checkpoint interaction (ISSUE satellite 4) ------------------------------

def test_async_checkpoint_survives_donated_steps(tmp_path):
    """Donation must not corrupt a pending async snapshot: capture copies
    to host inline, so continuing to train (donating the very buffers the
    snapshot read) while the write is in flight must still commit the
    at-capture state, and restore must be bitwise."""
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(6)
    for k in range(3):
        step(xs[k], ys[k])
    assert step.last_path == "whole_step", step.ineligible_reason()
    mx.waitall()
    at_capture = {n: p.data().asnumpy().copy()
                  for n, p in sorted(net.collect_params().items())}
    mgr = mx.checkpoint.CheckpointManager(tmp_path, trainer,
                                          async_save=True)
    mgr.save(step=3)
    # keep training THROUGH the in-flight write: these steps donate the
    # param/state buffers the snapshot walked
    for k in range(3, 6):
        step(xs[k], ys[k])
    mgr.flush()
    after = {n: p.data().asnumpy().copy()
             for n, p in sorted(net.collect_params().items())}
    for n in at_capture:  # training really moved past the snapshot
        assert not onp.array_equal(after[n], at_capture[n])
    mgr.restore(step=3)
    for n, p in sorted(net.collect_params().items()):
        assert onp.array_equal(p.data().asnumpy(), at_capture[n]), n
    # and the restored trainer state steps cleanly on the whole path
    step(xs[0], ys[0])
    assert step.last_path == "whole_step"


def test_trainer_save_load_states_roundtrip_whole_path(tmp_path):
    """Trainer.save_states/load_states round-trips optimizer state
    produced by the donated whole-step path (the donated originals are
    dead; the containers must hold the live outputs)."""
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(3)
    for k in range(3):
        step(xs[k], ys[k])
    fname = str(tmp_path / "opt.states")
    trainer.save_states(fname)
    saved = [[a.asnumpy().copy() for a in _flat_nd(s)]
             for s in trainer._states]
    nu_at_save = trainer._optimizer.num_update
    for k in range(3):  # move on
        step(xs[k], ys[k])
    assert trainer._optimizer.num_update > nu_at_save
    trainer.load_states(fname)
    assert trainer._optimizer.num_update == nu_at_save
    for s, ref in zip(trainer._states, saved):
        got = [a.asnumpy() for a in _flat_nd(s)]
        assert len(got) == len(ref)
        for ga, ra in zip(got, ref):
            assert onp.array_equal(ga, ra)


def _flat_nd(s):
    from mxnet_tpu.ndarray.ndarray import NDArray

    out = []
    if isinstance(s, NDArray):
        out.append(s)
    elif isinstance(s, tuple):
        for x in s:
            out.extend(_flat_nd(x))
    return out


# -- DataLoader device prefetch ----------------------------------------------

def _toy_dataset(n=24):
    r = onp.random.RandomState(5)
    return gluon.data.ArrayDataset(
        r.standard_normal((n, FEATS)).astype("float32"),
        r.standard_normal((n, OUT)).astype("float32"))


def test_device_prefetch_delivers_identical_batches():
    ds = _toy_dataset()
    plain = gluon.data.DataLoader(ds, batch_size=4)
    pre = gluon.data.DataLoader(ds, batch_size=4, device_prefetch=2)
    got_plain = [(x.asnumpy(), y.asnumpy()) for x, y in plain]
    got_pre = [(x.asnumpy(), y.asnumpy()) for x, y in pre]
    assert len(got_plain) == len(got_pre) == 6
    for (xa, ya), (xb, yb) in zip(got_plain, got_pre):
        assert onp.array_equal(xa, xb)
        assert onp.array_equal(ya, yb)


def test_device_prefetch_overlaps_transfer_with_compute():
    """Double-buffering proof: when the consumer holds batch i, batch
    i+1's device_put has ALREADY been issued (prefetch counter is ahead
    of consumption) and the transfer spans carry the data category so
    the step table shows them beside compute."""
    from mxnet_tpu.diagnostics import spans as _spans

    ds = _toy_dataset()
    loader = gluon.data.DataLoader(ds, batch_size=4, device_prefetch=1)
    telemetry.enable()
    # spans are module-global and an earlier test may have left them
    # disabled (e.g. test_serving's finally) — enable for this test
    spans_were_enabled = _spans.enabled()
    _spans.enable()
    try:
        base = ti.data_prefetch_total.value
        it = iter(loader)
        next(it)
        # holding batch 0 only, batches 0..2 are already transferred —
        # batch 1's h2d ran during/before our "step", not on demand
        assert ti.data_prefetch_total.value - base >= 2
        assert ti.data_prefetch_depth.value >= 1
        consumed = 1
        for _ in it:
            consumed += 1
        assert consumed == 6
        assert ti.data_prefetch_total.value - base == 6
        names = [r["name"] for r in _spans.records()
                 if r["name"] == "device_prefetch"]
        cats = {r["cat"] for r in _spans.records()
                if r["name"] == "device_prefetch"}
        assert names and cats == {"data"}
    finally:
        telemetry.disable()
        if not spans_were_enabled:
            _spans.disable()


@pytest.mark.parametrize("depth,prefetched", [(None, 0), (0, 0), (2, 6)])
def test_device_prefetch_is_the_argument_alone(monkeypatch, depth,
                                               prefetched):
    """The argument is the one way to set the depth: the variable that
    used to be its default is not read."""
    monkeypatch.setenv("MXTPU_DEVICE_PREFETCH", "2")
    ds = _toy_dataset()
    loader = gluon.data.DataLoader(ds, batch_size=4, device_prefetch=depth)
    telemetry.enable()
    try:
        base = ti.data_prefetch_total.value
        batches = list(loader)
        assert len(batches) == 6
        assert ti.data_prefetch_total.value - base == prefetched
    finally:
        telemetry.disable()


def test_wholestep_with_prefetched_loader_trains():
    """End-to-end: device-prefetched batches feed the one-dispatch step;
    losses stay finite and the path stays whole_step."""
    mx.seed(0)
    net = _net_plain(None)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    loader = gluon.data.DataLoader(_toy_dataset(), batch_size=4,
                                   device_prefetch=1)
    for x, y in loader:
        loss = step(x, y)
        assert onp.isfinite(loss.asnumpy().astype("float32")).all()
    assert step.last_path == "whole_step", step.ineligible_reason()


# -- names inside the program (ISSUE 25) -------------------------------------

def _conv_bn_dense_step():
    mx.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(4, 3, padding=1), gluon.nn.BatchNorm(),
            gluon.nn.Activation("relu"), gluon.nn.GlobalAvgPool2D(),
            gluon.nn.Dense(OUT))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    step = gluon.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           trainer)
    r = onp.random.RandomState(5)
    x = mnp.array(r.standard_normal((4, 3, 8, 8)).astype("float32"))
    y = mnp.array(r.randint(0, OUT, (4,)))
    return step, trainer, x, y


@pytest.fixture
def scoped_step(monkeypatch):
    from mxnet_tpu import diagnostics
    from mxnet_tpu.diagnostics import introspect

    was = telemetry.enabled()
    diagnostics.reset()
    telemetry.enable()
    telemetry.reset()
    texts, parse = [], introspect.op_scopes
    monkeypatch.setattr(introspect, "op_scopes",
                        lambda text: (texts.append(text), parse(text))[1])
    step, trainer, x, y = _conv_bn_dense_step()
    for _ in range(5):
        step(x, y)
    entry = diagnostics.compile_registry()[("whole_step", step._variant)]
    yield step, trainer, entry, texts[-1]
    diagnostics.reset()
    telemetry.reset()
    if not was:
        telemetry.disable()


@pytest.mark.parametrize("scope", [
    "/jvp(forward)/", "/transpose(jvp(forward))/", "/jvp(loss)/",
    "/optimizer/", "/jvp(forward)/BatchNorm_1/",
    "/transpose(jvp(forward))/Conv2D_0/", "/jvp(forward)/Dense_4/"])
def test_whole_step_hlo_names_its_work(scoped_step, scope):
    entry = scoped_step[2]
    names = entry["op_scopes"].values()
    assert all(v.startswith("jit(whole_step)/") for v in names
               if v.startswith("jit("))
    assert any(scope in v for v in names), sorted(set(names))[:40]


def test_op_scopes_resolve_the_entry_instructions(scoped_step):
    """Every entry instruction that carries an op_name is in the map.
    (The CPU compiler's own copies, constants, layout transposes and its
    rewritten weight-gradient convolution carry none; what share of the
    DEVICE's time resolves is the chip's number,
    device_scope_coverage_pct.train.)"""
    import re

    entry, text = scoped_step[2], scoped_step[3]
    entry_block = text[text.index("\nENTRY "):]
    lines = entry_block[:entry_block.index("\n}")].splitlines()[1:]
    named = [(m.group(1), "op_name=" in ln) for ln in lines
             for m in [re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = ", ln)]
             if m and " parameter(" not in ln]
    assert len(named) > 20
    with_meta = [n for n, has in named if has]
    resolved = [n for n, _ in named if n in entry["op_scopes"]]
    assert sorted(resolved) == sorted(with_meta)
    assert len(resolved) >= 0.6 * len(named)
    # and none of a fusion's inner instructions leaked into the map
    bodies = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", text))
    assert bodies and not bodies & set(entry["op_scopes"])
    inner = re.search(
        r"\n%?fused_computation[\w.\-]* .*\{\n\s+%?([\w.\-]+) = ", text)
    assert inner and inner.group(1) not in entry["op_scopes"]


def test_scopes_add_no_retrace(scoped_step):
    step = scoped_step[0]
    assert step.jit_trace_count() == 1
    assert _whole_trace_count() == 1


def test_step_scalar_operands_gauge(scoped_step):
    """4 is sound whatever the number of trained parameters (it read
    3 * 6 + 2 here): the host-resident operands are the four arrays lr /
    wd / update counts / hyper-parameters travel in, each one transfer a
    step (test_wholestep_operands_are_packed: none is a Python scalar)."""
    step = scoped_step[0]
    assert len(step._train_items) == 6
    assert ti.step_scalar_operands.value == 4


# -- packed optimizer operands ------------------------------------------------

def _record_whole_step_calls(monkeypatch):
    """Every operand tuple the jitted whole-step program is called with."""
    calls = []
    jitted = gluon.TrainStep._jitted

    def recording(self, donate):
        fn = jitted(self, donate)

        def call(*args):
            calls.append(args)
            return fn(*args)
        return call

    monkeypatch.setattr(gluon.TrainStep, "_jitted", recording)
    return calls


def _mults(net):
    for k, p in enumerate(net.collect_params().values()):
        p.lr_mult, p.wd_mult = 1.0 + k, 0.5 * k


@pytest.mark.parametrize("opt,kw,dtype,prepare", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
             "multi_precision": True}, "bfloat16", None),
    ("adam", {"learning_rate": 0.01, "wd": 0.02}, None, None),
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9,
             "lr_scheduler": mx.lr_scheduler.FactorScheduler(1, 0.5)},
     None, None),
    ("sgd", {"learning_rate": 0.05, "wd": 0.01}, None, _mults),
], ids=["sgd_masters", "adam", "lr_scheduler", "lr_wd_mult"])
def test_wholestep_operands_are_packed(opt, kw, dtype, prepare, monkeypatch):
    """The jitted call's operands hold no Python scalar and at most four
    host arrays (lrs, wds, update counts, hyper-parameters), whose VALUES
    follow the optimizer's bookkeeping from step to step on one trace."""
    import jax

    calls = _record_whole_step_calls(monkeypatch)
    mx.seed(0)
    net = _net_plain(dtype)
    if prepare is not None:
        prepare(net)
    trainer = gluon.Trainer(net.collect_params(), opt, dict(kw))
    step = gluon.TrainStep(net, gluon.loss.L2Loss(), trainer)
    xs, ys = _data(3, dtype=dtype or "float32")
    for x, y in zip(xs, ys):
        step(x, y)
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1 and len(calls) == 3
    o = trainer._optimizer
    n = len(step._train_items)
    for k, args in enumerate(calls):
        leaves = jax.tree_util.tree_leaves(args)
        assert not [x for x in leaves if isinstance(x, (bool, int, float))]
        host = [x for x in leaves if isinstance(x, onp.ndarray)]
        assert len(host) <= 4
        assert all(isinstance(x, jax.Array) for x in leaves
                   if not isinstance(x, onp.ndarray))
        lrs, wds, ts, hyper = args[4:8]
        assert [(v.dtype.name, v.shape) for v in (lrs, wds, ts)] == [
            ("float32", (n,)), ("float32", (n,)), ("int32", (n,))]
        assert (ts == k + 1).all()
        assert hyper.dtype == onp.float32 \
            and hyper.shape == (len(o._hyper()) + 2,)
    # the last call carried what the optimizer resolves now
    lrs, wds, _ts, hyper = calls[-1][4:8]
    idx = step._train_index
    assert onp.array_equal(lrs, onp.float32([o._get_lr(i) for i in idx]))
    assert onp.array_equal(wds, onp.float32([o._get_wd(i) for i in idx]))
    assert onp.array_equal(hyper, o._packed_hyper(sorted(o._hyper())))
    if "lr_scheduler" in kw:
        assert calls[0][4][0] > calls[1][4][0] > calls[2][4][0]
    if prepare is not None:
        assert len(set(lrs)) == n and len(set(wds)) == n


@pytest.mark.parametrize("opt,kw", [
    ("sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 0.01}),
    ("adam", {"learning_rate": 0.01, "wd": 0.02}),
])
def test_wholestep_bf16_without_masters_stays_bf16(opt, kw, monkeypatch):
    """The weak-type guard: an element of a float32 operand vector must
    reach the rule as a WEAK scalar, or `w - lr * g` on a bf16 weight
    without a master promotes to float32. Weights and state stay bf16 and
    match the per-parameter Optimizer.update loop bitwise."""
    whole = _run_path(True, _net_plain, opt, kw, dtype="bfloat16")
    monkeypatch.setenv("MXTPU_FUSED_UPDATE", "0")
    loop = _run_path(False, _net_plain, opt, kw, dtype="bfloat16")
    _assert_same(whole, loop)
    assert all(str(v.dtype) == "bfloat16" for v in whole["params"].values())
    assert all(str(x.dtype) == "bfloat16"
               for st in whole["states"] for x in st)
    assert any(st for st in whole["states"])


def test_named_scope_leaves_program_identity_alone():
    """Scopes are metadata: a body traced under them has the equations of
    the body traced plain, the name stack apart."""
    import jax
    import jax.numpy as jnp

    def body(x, w):
        return jnp.tanh(x @ w).sum()

    def scoped(x, w):
        with jax.named_scope("BottleneckV1_3"):
            with jax.named_scope("BatchNorm_bn2"):
                return body(x, w)

    args = (jnp.ones((4, 8)), jnp.ones((8, 2)))
    plain_j, scoped_j = jax.make_jaxpr(body)(*args), \
        jax.make_jaxpr(scoped)(*args)
    assert "BatchNorm_bn2" in str(
        scoped_j.jaxpr.eqns[0].source_info.name_stack)
    assert str(plain_j) == str(scoped_j)
