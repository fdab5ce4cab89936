"""Pallas bandwidth kernels (mxnet_tpu/kernels; docs/kernels.md):
interpret-mode forward+grad parity for all three kernels, the
MXTPU_KERNELS=0 kill switch (bitwise program identity, zero extra
traces), byte-model acceptance (>=30% external-HBM reduction on the
audited regions, asserted against recorded jaxprs), auto-mode declines,
fallback outcomes + flight-recorder events, and composition with
whole-step donation, cross-CachedOp dedup, and remat."""
import sys

import numpy as onp
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import env, gluon, np as mnp, passes, telemetry
from mxnet_tpu.kernels import dispatch as kdispatch
from mxnet_tpu.kernels import norm as knorm
from mxnet_tpu.kernels import opt as kopt
from mxnet_tpu.observability import flight
from mxnet_tpu.ops import nn
from mxnet_tpu.optimizer.optimizer import SGD, Adam, Optimizer
from mxnet_tpu.passes import memory as pmem
from mxnet_tpu.telemetry import instruments as ti


def _force(monkeypatch, mode="force"):
    monkeypatch.setenv("MXTPU_KERNELS", mode)
    monkeypatch.setenv("MXTPU_KERNELS_INTERPRET", "1")


def _off(monkeypatch):
    monkeypatch.delenv("MXTPU_KERNELS", raising=False)
    monkeypatch.delenv("MXTPU_KERNELS_INTERPRET", raising=False)


def _bn_operands(m=32, c=128, dtype=jnp.float32, seed=0):
    r = onp.random.RandomState(seed)
    x = jnp.asarray(r.standard_normal((m, c)) * 2.0 + 1.5, dtype)
    gamma = jnp.asarray(r.uniform(0.5, 1.5, c), jnp.float32)
    beta = jnp.asarray(r.standard_normal(c), jnp.float32)
    shift = jnp.asarray(r.standard_normal(c) * 0.1 + 1.5, jnp.float32)
    return x, gamma, beta, shift


def _trace_count(block="whole_step"):
    return sum(c.value for labels, c in ti.jit_trace_total.series()
               if labels[0] == block)


def _dispatch_count(kernel, outcome):
    return sum(c.value for labels, c in ti.kernel_dispatch_total.series()
               if labels == (kernel, outcome))


# -- mode resolution ---------------------------------------------------------

def test_invalid_kernels_mode_raises(monkeypatch):
    monkeypatch.setenv("MXTPU_KERNELS", "bogus")
    with pytest.raises(ValueError):
        kdispatch.mode()


def test_env_vars_registered_and_documented():
    for name in ("MXTPU_KERNELS", "MXTPU_KERNELS_INTERPRET",
                 "MXTPU_BN_COMPUTE"):
        assert name in env.all_vars()
        assert f"`{name}`" in env.doc()
    import os
    doc_path = os.path.join(os.path.dirname(__file__), "..", "docs",
                            "env_vars.md")
    text = open(doc_path).read()
    for name in ("MXTPU_KERNELS", "MXTPU_KERNELS_INTERPRET",
                 "MXTPU_BN_COMPUTE"):
        assert f"`{name}`" in text  # docs regenerated from the registry


# -- BN forward/backward parity (interpret mode) -----------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bn_forward_parity(monkeypatch, dtype):
    x, gamma, beta, shift = _bn_operands(dtype=dtype)
    _off(monkeypatch)
    ref = nn._bn_train(x, gamma, beta, shift, 1e-5, 1)
    _force(monkeypatch)
    got = knorm.bn_train(x, gamma, beta, shift, 1e-5, 1)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for r, g in zip(ref, got):
        assert g.dtype == r.dtype
        onp.testing.assert_allclose(onp.asarray(r, onp.float32),
                                    onp.asarray(g, onp.float32),
                                    rtol=tol, atol=tol)


def test_bn_grad_parity(monkeypatch):
    x, gamma, beta, shift = _bn_operands(m=64, c=128)
    r = onp.random.RandomState(1)
    w_out = jnp.asarray(r.standard_normal(x.shape), jnp.float32)
    w_mean = jnp.asarray(r.standard_normal(x.shape[-1]), jnp.float32)
    w_var = jnp.asarray(r.standard_normal(x.shape[-1]), jnp.float32)

    def loss(fn, x, gamma, beta):
        out, mean, var = fn(x, gamma, beta, shift, 1e-5, 1)
        # mean/var terms exercise the dmean/dvar cotangent path too
        return ((out * w_out).sum() + (mean * w_mean).sum()
                + (var * w_var).sum())

    _off(monkeypatch)
    ref = jax.grad(lambda *a: loss(nn._bn_train, *a),
                   argnums=(0, 1, 2))(x, gamma, beta)
    _force(monkeypatch)
    got = jax.grad(lambda *a: loss(knorm.bn_train, *a),
                   argnums=(0, 1, 2))(x, gamma, beta)
    for rg, gg in zip(ref, got):
        onp.testing.assert_allclose(onp.asarray(rg), onp.asarray(gg),
                                    rtol=1e-4, atol=1e-4)


def test_bn_compute_bf16_parity(monkeypatch):
    # the MXTPU_BN_COMPUTE knob applies to XLA path and kernel alike:
    # bf16 elementwise stays close to the f32-elementwise reference
    x, gamma, beta, shift = _bn_operands(dtype=jnp.bfloat16)
    _off(monkeypatch)
    monkeypatch.setenv("MXTPU_BN_COMPUTE", "f32")
    assert nn._bn_ew_dtype(x) == jnp.float32
    ref = nn._bn_train(x, gamma, beta, shift, 1e-5, 1)
    monkeypatch.setenv("MXTPU_BN_COMPUTE", "bf16")
    assert nn._bn_ew_dtype(x) == jnp.bfloat16
    xla16 = nn._bn_train(x, gamma, beta, shift, 1e-5, 1)
    _force(monkeypatch)
    k16 = knorm.bn_train(x, gamma, beta, shift, 1e-5, 1)
    for r, a, b in zip(ref, xla16, k16):
        onp.testing.assert_allclose(onp.asarray(r, onp.float32),
                                    onp.asarray(a, onp.float32),
                                    rtol=5e-2, atol=5e-2)
        onp.testing.assert_allclose(onp.asarray(a, onp.float32),
                                    onp.asarray(b, onp.float32),
                                    rtol=5e-2, atol=5e-2)


# -- optimizer-ladder parity (interpret mode) --------------------------------

def _opt_operands(size=2048, mp=True, n_state=1, seed=3):
    r = onp.random.RandomState(seed)
    wdt = jnp.bfloat16 if mp else jnp.float32
    master = jnp.asarray(r.standard_normal(size), jnp.float32)
    w = master.astype(wdt)
    g = jnp.asarray(r.standard_normal(size), wdt)
    # non-negative states: Adam's v is a running mean of g² — negative
    # values would NaN under sqrt in BOTH paths
    states = tuple(jnp.asarray(onp.abs(r.standard_normal(size)) * 0.01,
                               jnp.float32)
                   for _ in range(n_state))
    inner = states[0] if n_state == 1 else states
    st = (master, inner) if mp else inner
    return w, st, g


@pytest.mark.parametrize("cls,n_state,hyper", [
    (SGD, 1, {"rescale_grad": 1.0 / 8, "momentum": 0.9}),
    (Adam, 2, {"rescale_grad": 1.0, "beta1": 0.9, "beta2": 0.999,
               "eps": 1e-8}),
])
@pytest.mark.parametrize("mp", [True, False])
def test_opt_ladder_parity(monkeypatch, cls, n_state, hyper, mp):
    w, st, g = _opt_operands(mp=mp, n_state=n_state)
    args = (0.125, 1e-4, 3, 1.0, dict(hyper))   # lr, wd, t, scale, hyper
    _off(monkeypatch)
    ref = Optimizer._fused_param_step(cls, 0.5, False, mp, w, st, g, *args)
    _force(monkeypatch)
    got = kopt.param_step(cls, 0.5, False, mp, w, st, g, *args)
    ref_l = jax.tree_util.tree_leaves(ref)
    got_l = jax.tree_util.tree_leaves(got)
    assert len(ref_l) == len(got_l)
    for rl, gl in zip(ref_l, got_l):
        assert gl.dtype == rl.dtype and gl.shape == rl.shape
        # a ~1-ulp f32 difference (fused-program FMA contraction) can
        # round across a bf16 boundary at the final cast; Adam's
        # sqrt/divide amplifies it a few ulps further in f32
        tol = 1e-4 if rl.dtype == jnp.float32 else 1e-2
        onp.testing.assert_allclose(onp.asarray(rl, onp.float32),
                                    onp.asarray(gl, onp.float32),
                                    rtol=tol, atol=tol)


def test_opt_ladder_stateless_and_global_norm(monkeypatch):
    w, st, g = _opt_operands(mp=True, n_state=1)
    st = (st[0], None)                     # stateless SGD (momentum=0)
    hyper = {"rescale_grad": 1.0}
    _off(monkeypatch)
    ref = Optimizer._fused_param_step(SGD, None, True, True, w, st, g,
                                      0.1, 0.0, 1, 0.25, hyper)
    _force(monkeypatch)
    got = kopt.param_step(SGD, None, True, True, w, st, g,
                          0.1, 0.0, 1, 0.25, hyper)
    for rl, gl in zip(jax.tree_util.tree_leaves(ref),
                      jax.tree_util.tree_leaves(got)):
        onp.testing.assert_allclose(onp.asarray(rl, onp.float32),
                                    onp.asarray(gl, onp.float32),
                                    rtol=2e-6, atol=2e-6)


def test_opt_ladder_fallbacks(monkeypatch):
    telemetry.enable()
    _force(monkeypatch)
    hyper = {"rescale_grad": 1.0, "momentum": 0.9}
    # tiny tensor: unsupported_shape, result identical to the XLA body
    w, st, g = _opt_operands(size=64, mp=True)
    before = _dispatch_count("opt_sgd", "unsupported_shape")
    got = kopt.param_step(SGD, None, False, True, w, st, g,
                          0.1, 0.0, 1, 1.0, hyper)
    assert _dispatch_count("opt_sgd", "unsupported_shape") == before + 1
    ref = Optimizer._fused_param_step(SGD, None, False, True, w, st, g,
                                      0.1, 0.0, 1, 1.0, hyper)
    for rl, gl in zip(jax.tree_util.tree_leaves(ref),
                      jax.tree_util.tree_leaves(got)):
        onp.testing.assert_array_equal(onp.asarray(rl), onp.asarray(gl))
    # disallowed rule class: unsupported_rule
    class Weird(SGD):
        pass
    w, st, g = _opt_operands(mp=True)
    before = _dispatch_count("opt_weird", "unsupported_rule")
    kopt.param_step(Weird, None, False, True, w, st, g,
                    0.1, 0.0, 1, 1.0, hyper)
    assert _dispatch_count("opt_weird", "unsupported_rule") == before + 1


def test_auto_declines_non_mp_by_byte_model(monkeypatch):
    # no widening root in the pure-f32 chain: the model predicts zero
    # savings and auto keeps the XLA path (outcome no_savings)
    telemetry.enable()
    _force(monkeypatch, mode="auto")
    w, st, g = _opt_operands(size=1 << 17, mp=False)
    before = _dispatch_count("opt_sgd", "no_savings")
    kopt.param_step(SGD, None, False, False, w, st, g,
                    0.1, 0.0, 1, 1.0, {"rescale_grad": 1.0, "momentum": 0.9})
    assert _dispatch_count("opt_sgd", "no_savings") == before + 1
    # the same size WITH mp has the widening root: auto accepts
    w, st, g = _opt_operands(size=1 << 17, mp=True)
    before = _dispatch_count("opt_sgd", "kernel")
    saved0 = ti.kernel_bytes_saved.value
    kopt.param_step(SGD, None, False, True, w, st, g,
                    0.1, 0.0, 1, 1.0, {"rescale_grad": 1.0, "momentum": 0.9})
    assert _dispatch_count("opt_sgd", "kernel") == before + 1
    assert ti.kernel_bytes_saved.value > saved0


def test_bn_fallback_hits_flight_recorder(monkeypatch):
    _force(monkeypatch)
    flight.reset()
    x, gamma, beta, shift = _bn_operands(c=100)   # C % 128 != 0
    out = knorm.bn_train(x, gamma, beta, shift, 1e-5, 1)
    ref = nn._bn_train(x, gamma, beta, shift, 1e-5, 1)
    for r, g in zip(ref, out):
        onp.testing.assert_array_equal(onp.asarray(r), onp.asarray(g))
    evs = [e for e in flight.events() if e["kind"] == "kernel_fallback"]
    assert any(e["kernel"] == "bn_fwd"
               and e["reason"] == "unsupported_shape" for e in evs)


# -- kill switch: bitwise program identity, zero extra traces ----------------

def test_kill_switch_program_is_bitwise_and_kernels_unimported(monkeypatch):
    x, gamma, beta, shift = _bn_operands()

    def capture():
        return jax.make_jaxpr(
            lambda *a: nn.batch_norm(*a, jnp.ones_like(shift),
                                     training=True, axis=-1))(
            x, gamma, beta, shift)

    from mxnet_tpu.passes.dedup import structural_key

    _off(monkeypatch)
    for m in [m for m in sys.modules
              if m.startswith("mxnet_tpu.kernels")]:
        sys.modules.pop(m)
    unset = capture()
    # the off path never imports the kernel modules
    assert "mxnet_tpu.kernels.norm" not in sys.modules
    assert "mxnet_tpu.kernels.opt" not in sys.modules
    assert "pallas_call" not in str(unset)
    monkeypatch.setenv("MXTPU_KERNELS", "0")
    zero = capture()
    # '0' and unset capture the SAME program (structural identity is
    # exact modulo the per-trace thunk addresses str() would show)
    k_unset, k_zero = structural_key(unset), structural_key(zero)
    assert k_unset is not None and k_unset == k_zero
    _force(monkeypatch)
    forced = capture()
    assert "pallas_call" in str(forced)
    assert structural_key(forced) != k_unset


def _train_bn_net(steps=3, opt_kwargs=None):
    mx.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(128, activation="relu"),
            gluon.nn.BatchNorm(),
            gluon.nn.Dense(4))
    net.initialize()
    net.cast("bfloat16")
    net.hybridize()
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        dict({"learning_rate": 0.05, "momentum": 0.9,
              "multi_precision": True}, **(opt_kwargs or {})))
    r = onp.random.RandomState(7)
    xs = [mnp.array(r.standard_normal((8, 128)).astype("float32"),
                    dtype="bfloat16") for _ in range(steps)]
    ys = [mnp.array(r.standard_normal((8, 4)).astype("float32"),
                    dtype="bfloat16") for _ in range(steps)]
    mx.seed(99)
    step = gluon.TrainStep(net, loss_fn, trainer)
    losses = []
    for k in range(steps):
        losses.append(step(xs[k], ys[k]).asnumpy().astype("float32").copy())
    assert step.last_path == "whole_step", step.ineligible_reason()
    params = {n: p.data().asnumpy().copy()
              for n, p in sorted(net.collect_params().items())}
    return losses, params


def test_kill_switch_whole_step_bitwise_and_trace_parity(monkeypatch):
    telemetry.enable()
    _off(monkeypatch)
    t0 = _trace_count()
    unset_losses, unset_params = _train_bn_net()
    unset_traces = _trace_count() - t0
    monkeypatch.setenv("MXTPU_KERNELS", "0")
    t0 = _trace_count()
    zero_losses, zero_params = _train_bn_net()
    zero_traces = _trace_count() - t0
    assert zero_traces == unset_traces   # zero EXTRA traces under '0'
    for a, b in zip(unset_losses, zero_losses):
        onp.testing.assert_array_equal(a, b)
    for n in unset_params:
        onp.testing.assert_array_equal(unset_params[n], zero_params[n]), n


# -- whole-step composition: donation + zero retrace -------------------------

def test_kernels_whole_step_zero_retrace_and_donation(monkeypatch):
    telemetry.enable()
    _force(monkeypatch)
    t0 = _trace_count()
    d0 = ti.step_donated_bytes.value
    losses, params = _train_bn_net(steps=3)
    assert _trace_count() - t0 == 1      # ONE trace for all 3 steps
    assert ti.step_donated_bytes.value > d0   # donated whole-step path
    for l in losses:
        assert onp.isfinite(l).all()
    # and it actually trained vs the off path's step-0 weights
    assert all(onp.isfinite(v).all() for v in params.values())


def test_kernels_whole_step_close_to_off_path(monkeypatch):
    _off(monkeypatch)
    off_losses, _off_params = _train_bn_net(steps=3)
    _force(monkeypatch)
    k_losses, _k_params = _train_bn_net(steps=3)
    for a, b in zip(off_losses, k_losses):
        onp.testing.assert_allclose(a.astype(onp.float32),
                                    b.astype(onp.float32),
                                    rtol=5e-2, atol=5e-2)


# -- composition: dedup ------------------------------------------------------

def _bn_block(hidden=128, seed=0):
    mx.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden), gluon.nn.BatchNorm())
    net.initialize()
    net.hybridize()
    return net


def test_kernels_compose_with_dedup(monkeypatch):
    telemetry.enable()
    _force(monkeypatch)
    monkeypatch.setenv("MXTPU_GRAPH_DEDUP", "1")
    passes.reset_executable_cache()
    x = mnp.array(onp.random.RandomState(5)
                  .standard_normal((8, 128)).astype("float32"))
    a, b = _bn_block(seed=21), _bn_block(seed=22)
    before = _trace_count("HybridSequential")
    hits0 = sum(c.value for _l, c in ti.graph_dedup_hits_total.series())
    with ag.record():
        ya = a(x)
    assert _trace_count("HybridSequential") - before == 1
    with ag.record():
        yb = b(x)
    # pallas_call equations tokenize structurally: identical kernel-
    # bearing programs share ONE executable, zero extra traces
    assert _trace_count("HybridSequential") - before == 1
    hits1 = sum(c.value for _l, c in ti.graph_dedup_hits_total.series())
    assert hits1 - hits0 >= 1
    assert onp.isfinite(ya.asnumpy()).all()
    assert not onp.array_equal(ya.asnumpy(), yb.asnumpy())  # own weights


def test_kernels_dedup_different_configs_do_not_share(monkeypatch):
    telemetry.enable()
    _force(monkeypatch)
    monkeypatch.setenv("MXTPU_GRAPH_DEDUP", "1")
    passes.reset_executable_cache()
    r = onp.random.RandomState(6)
    x = mnp.array(r.standard_normal((8, 128)).astype("float32"))
    a = _bn_block(hidden=128, seed=31)
    b = _bn_block(hidden=256, seed=32)   # different C: different kernel
    before = _trace_count("HybridSequential")
    with ag.record():
        a(x)
        b(x)
    assert _trace_count("HybridSequential") - before == 2
    assert passes.executable_cache_info()["hits"] == 0


# -- composition: remat ------------------------------------------------------

def test_kernels_compose_with_remat(monkeypatch):
    _force(monkeypatch)
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "none")
    base_losses, base_params = _train_bn_net(steps=2)
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "full")
    remat_losses, remat_params = _train_bn_net(steps=2)
    for a, b in zip(base_losses, remat_losses):
        onp.testing.assert_allclose(a.astype(onp.float32),
                                    b.astype(onp.float32),
                                    rtol=1e-5, atol=1e-5)
    for n in base_params:
        onp.testing.assert_allclose(
            base_params[n].astype(onp.float32),
            remat_params[n].astype(onp.float32), rtol=1e-4, atol=1e-4)


# -- KernelPass --------------------------------------------------------------

def test_kernel_pass_injected_and_audits(monkeypatch):
    from mxnet_tpu.passes.kernel_pass import KernelPass, audit_jaxpr
    from mxnet_tpu.passes.manager import resolve_passes, PassContext

    _force(monkeypatch)
    ctx = PassContext(kind="block", label="t", training=True)
    resolved = resolve_passes(ctx)
    assert any(p.name == "kernels" for p in resolved)
    _off(monkeypatch)
    resolved = resolve_passes(ctx)
    assert not any(p.name == "kernels" for p in resolved)

    _force(monkeypatch)
    x, gamma, beta, shift = _bn_operands()
    closed = jax.make_jaxpr(
        lambda *a: knorm.bn_train(*a, 1e-5, 1))(x, gamma, beta, shift)
    note = audit_jaxpr(closed)
    assert note["pallas_calls"] >= 1
    assert note["external_bytes_total"] >= 0
    kp = KernelPass()
    out = kp.run(closed, ctx)
    assert out is closed                       # audit-only, never edits
    assert ctx.notes["kernels"]["pallas_calls"] >= 1


# -- byte-model acceptance: >=30% on the audited regions ---------------------

def _estimator_total(closed):
    return sum(r["external_bytes"]
               for r in pmem.estimate_region_bytes(closed))


@pytest.mark.parametrize("dtype,compute", [
    (jnp.float32, "f32"), (jnp.bfloat16, "bf16")])
def test_byte_model_predicts_30pct_bn(monkeypatch, dtype, compute):
    _off(monkeypatch)
    monkeypatch.setenv("MXTPU_BN_COMPUTE", compute)
    x, gamma, beta, shift = _bn_operands(m=2048, c=512, dtype=dtype)

    def loss(x, gamma, beta):
        out, mean, var = nn._bn_train(x, gamma, beta, shift, 1e-5, 1)
        return (out.astype(jnp.float32).sum() + mean.sum() + var.sum())

    fwd = jax.make_jaxpr(
        lambda *a: nn._bn_train(*a, 1e-5, 1))(x, gamma, beta, shift)
    bwd = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, gamma, beta)
    xla_recorded = _estimator_total(fwd) + _estimator_total(bwd)
    ew = nn._bn_ew_dtype(x)
    xla_model, kernel_bytes = pmem.norm_region_bytes(x.shape, x.dtype, ew)
    # acceptance: >=30% external-byte reduction vs the RECORDED XLA
    # program (the audited regions), and the analytic pair must clear
    # the auto-accept threshold so `auto` actually adopts the kernel
    assert (xla_recorded - kernel_bytes) / xla_recorded >= 0.30
    ok, reason, saved = kdispatch.auto_accepts(xla_model, kernel_bytes)
    assert ok and reason == "kernel" and saved > 0


def test_byte_model_predicts_30pct_optimizer_mp(monkeypatch):
    _off(monkeypatch)
    size = 1 << 20
    w, st, g = _opt_operands(size=size, mp=True)
    hyper = {"rescale_grad": 1.0 / 8, "momentum": 0.9}

    closed = jax.make_jaxpr(
        lambda w, st, g: Optimizer._fused_param_step(
            SGD, None, False, True, w, st, g, 0.1, 1e-4, 2, 1.0, hyper)
    )(w, st, g)
    xla_recorded = _estimator_total(closed)
    xla_model, kernel_bytes = pmem.optimizer_region_bytes(
        size, w.dtype, 1, True)
    assert (xla_recorded - kernel_bytes) / xla_recorded >= 0.30
    ok, reason, saved = kdispatch.auto_accepts(xla_model, kernel_bytes)
    assert ok and reason == "kernel" and saved > 0
    # non-mp: no widening root, model must predict ZERO savings
    xla_f32, k_f32 = pmem.optimizer_region_bytes(size, jnp.float32, 1,
                                                 False)
    assert xla_f32 == k_f32


# -- a stable name on every Pallas call (ISSUE 25) ----------------------------

def _pallas_names(closed):
    """Names of the pallas_call equations of a jaxpr, nested ones too."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    walk(inner)
                elif hasattr(v, "eqns"):
                    walk(v)
    walk(closed.jaxpr)
    return out


def _flash_names(grad):
    from mxnet_tpu.ops.pallas_attention import flash_attention

    q = jnp.ones((1, 2, 128, 64), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
    return _pallas_names(jax.make_jaxpr(fn)(q, q, q))


def _bn_names(grad, monkeypatch):
    _force(monkeypatch)
    x, gamma, beta, shift = _bn_operands(m=64, c=128)

    def f(x, gamma, beta):
        return knorm.bn_train(x, gamma, beta, shift, 1e-5, 1)[0].sum()

    fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
    return _pallas_names(jax.make_jaxpr(fn)(x, gamma, beta))


def _opt_names(monkeypatch):
    _force(monkeypatch)
    w, st, g = _opt_operands(mp=True, n_state=1)
    return _pallas_names(jax.make_jaxpr(
        lambda w, st, g: kopt.param_step(
            SGD, 0.5, False, True, w, st, g, 0.125, 1e-4, 3, 1.0,
            {"rescale_grad": 1.0 / 8, "momentum": 0.9}))(w, st, g))


@pytest.mark.parametrize("which,expect", [
    ("flash_fwd", ["flash_attention_fwd"]),
    ("flash_bwd", ["flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"]),
    ("bn_fwd", ["batchnorm_train_fwd"]),
    ("bn_bwd", ["batchnorm_train_fwd", "batchnorm_train_bwd"]),
    ("opt", ["optimizer_update_ladder"]),
])
def test_pallas_calls_carry_stable_names(monkeypatch, which, expect):
    names = {"flash_fwd": lambda: _flash_names(False),
             "flash_bwd": lambda: _flash_names(True),
             "bn_fwd": lambda: _bn_names(False, monkeypatch),
             "bn_bwd": lambda: _bn_names(True, monkeypatch),
             "opt": lambda: _opt_names(monkeypatch)}[which]()
    assert sorted(set(names)) == sorted(expect)


def test_rtc_kernel_is_named_after_itself(monkeypatch):
    from jax.experimental import pallas as pl

    from mxnet_tpu import rtc

    seen = []
    real = pl.pallas_call

    def spy(body, **kw):
        seen.append(kw.get("name"))
        return real(body, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    kern = rtc.PallasModule(double).get_kernel("double")
    out = kern.launch([mnp.ones((8, 128))], (8, 128), "float32")
    assert seen == ["double"]
    onp.testing.assert_allclose(out.asnumpy(), 2.0)
