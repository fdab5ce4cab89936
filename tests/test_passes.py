"""Graph-pass pipeline (mxnet_tpu/passes; docs/passes.md): seam
identity under the kill switch, pipeline-AMP vs legacy amp_rewrite,
remat policy parity + peak reduction, cross-CachedOp dedup zero-retrace
proof, pass-ordering determinism, export-through-pipeline."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, passes
from mxnet_tpu.telemetry import instruments as ti


def _mlp(seed=0, hidden=16, out=4):
    mx.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, activation="relu"),
            gluon.nn.Dense(out))
    net.initialize()
    net.hybridize()
    return net


def _deep_mlp(seed=0, depth=8, width=64):
    mx.seed(seed)
    net = gluon.nn.HybridSequential()
    for _ in range(depth):
        net.add(gluon.nn.Dense(width, activation="tanh"))
    net.initialize()
    net.hybridize()
    return net


def _x(shape=(4, 8), seed=0):
    return mx.np.array(np.random.RandomState(seed).rand(*shape)
                       .astype("f"))


class _CustomGradNet(gluon.HybridBlock):
    """Dense → BatchNorm → Dense → make_loss: training-mode BatchNorm
    and make_loss both differentiate through custom_vjp rules (the
    hand-written closed-form BN bwd; make_loss's constant-grad bwd that
    IGNORES the upstream cotangent), so any rewrite that silently
    replaces a custom rule with autodiff-of-primal fails parity here."""

    def __init__(self):
        super().__init__()
        self.d1 = gluon.nn.Dense(32, activation="tanh")
        self.bn = gluon.nn.BatchNorm(axis=-1)
        self.d2 = gluon.nn.Dense(8)

    def forward(self, x):
        from mxnet_tpu import nd

        h = self.bn(self.d1(x))
        return nd.make_loss(self.d2(h), grad_scale=3.0)


def _custom_grad_net(seed=0):
    mx.seed(seed)
    net = _CustomGradNet()
    net.initialize()
    net.hybridize()
    return net


def _loss_and_grads(net, x):
    with autograd.record():
        out = net(x)
        loss = (out * out).sum()
    loss.backward()
    grads = {n: p.grad().asnumpy().copy()
             for n, p in net.collect_params().items()
             if p.grad_req != "null"}  # BN moving stats have no grad
    return loss.asnumpy().copy(), grads


def _trace_count(block_cls="HybridSequential"):
    return sum(c.value for labels, c in ti.jit_trace_total.series()
               if labels[0] == block_cls)


# -- seam identity -----------------------------------------------------------

def test_identical_seeds_identical_nets():
    # precondition for every bitwise A/B test below
    x = _x()
    # deferred-shape params materialize (and consume RNG) at first
    # forward, so each net must be seeded AND materialized in turn
    a = _mlp(seed=11)
    a(x)
    b = _mlp(seed=11)
    b(x)
    for (na, pa), (nb, pb) in zip(sorted(a.collect_params().items()),
                                  sorted(b.collect_params().items())):
        assert na == nb
        np.testing.assert_array_equal(pa.data().asnumpy(),
                                      pb.data().asnumpy())


def test_kill_switch_is_bitwise_identity(monkeypatch):
    x = _x()
    ref = _mlp(seed=7)(x).asnumpy()  # plain fp32, no pipeline
    net = _mlp(seed=7)
    net.pass_pipeline().register(passes.AmpPass())
    monkeypatch.setenv("MXTPU_PASSES", "0")
    got = net(x).asnumpy()
    np.testing.assert_array_equal(ref, got)
    # re-enabled, the registered AMP pass changes the numerics
    monkeypatch.delenv("MXTPU_PASSES")
    net._jit_variants.clear()
    got2 = net(x).asnumpy()
    assert not np.array_equal(ref, got2)


def test_pipeline_build_bumps_trace_once(monkeypatch):
    mx.telemetry.enable()
    net = _mlp(seed=3)
    net.pass_pipeline().register(passes.AmpPass())
    x = _x()
    before = _trace_count()
    net(x)
    assert _trace_count() - before == 1  # pipeline build = one trace
    net(x)
    assert _trace_count() - before == 1  # cache hit: no retrace


# -- AMP pass ----------------------------------------------------------------

def test_pipeline_amp_matches_legacy_rewrite():
    import jax

    from mxnet_tpu.amp.graph_pass import AmpStats, amp_rewrite

    net = _mlp(seed=5)
    x = _x(seed=2)
    net(x)  # build + materialize params
    fn = net._make_cached_fn(False)
    pd = {n: p.data()._data for n, p in net._cached_param_list}
    key = jax.random.PRNGKey(0)
    closed = jax.make_jaxpr(fn)(pd, key, x._data)
    legacy_run = amp_rewrite(closed, jax.numpy.bfloat16, AmpStats())
    flat, _ = jax.tree_util.tree_flatten((pd, key, x._data))
    legacy_out = np.asarray(legacy_run(*flat)[0])

    net2 = _mlp(seed=5)
    amp.convert_hybrid_block(net2, graph_pass=True, example_inputs=(x,))
    got = net2(x).asnumpy()
    np.testing.assert_array_equal(legacy_out, got)


def test_convert_hybrid_block_graph_pass_shim():
    net = _mlp(seed=9)
    x = _x()
    out = amp.convert_hybrid_block(net, graph_pass=True,
                                   example_inputs=(x,))
    assert out is net
    assert net.pass_pipeline().get("amp") is not None
    assert net._amp_stats.lp16_ops >= 1
    y = net(x)
    assert y.dtype == np.float32  # outputs cast back (widest rule)
    # matches the convert_block_graph entry point bitwise
    from mxnet_tpu.amp import convert_block_graph

    net2 = _mlp(seed=9)
    convert_block_graph(net2, (x,))
    np.testing.assert_array_equal(y.asnumpy(), net2(x).asnumpy())


def test_named_pass_env_forces_amp(monkeypatch):
    x = _x()
    net_conv = _mlp(seed=13)
    amp.convert_hybrid_block(net_conv, graph_pass=True,
                             example_inputs=(x,))
    expected = net_conv(x).asnumpy()
    monkeypatch.setenv("MXTPU_PASSES", "amp")
    net = _mlp(seed=13)  # nothing registered; env forces the pass
    np.testing.assert_array_equal(expected, net(x).asnumpy())


@pytest.mark.parametrize("name", ["nonsuch", "kernels", "layout"])
def test_unknown_named_pass_raises(monkeypatch, name):
    # "kernels" and "layout" named passes that were deleted (PR 31)
    monkeypatch.setenv("MXTPU_PASSES", name)
    net = _mlp(seed=1)
    with pytest.raises(ValueError, match=name) as err:
        net(_x())
    assert "['amp', 'numerics', 'remat', 'sharding']" in str(err.value)


def test_amp_pass_composes_with_whole_step():
    mx.telemetry.enable()
    net = _mlp(seed=21)
    net.pass_pipeline().register(passes.AmpPass())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05})
    step = gluon.TrainStep(net, lambda out: (out * out).sum(axis=-1), tr)
    before = sum(c.value for labels, c in ti.pass_applied_total.series()
                 if labels[0] == "amp")
    x = _x((8, 8), seed=3)
    loss = step(x, batch_size=8)
    assert np.isfinite(loss.asnumpy()).all()
    after = sum(c.value for labels, c in ti.pass_applied_total.series()
                if labels[0] == "amp")
    assert after > before  # AMP rewrote the whole-step forward body


# -- remat pass --------------------------------------------------------------

@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_bitwise_parity(monkeypatch, policy):
    x = _x((16, 64), seed=4)
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "none")
    l0, g0 = _loss_and_grads(_deep_mlp(seed=17, depth=6), x)
    monkeypatch.setenv("MXTPU_REMAT_POLICY", policy)
    l1, g1 = _loss_and_grads(_deep_mlp(seed=17, depth=6), x)
    np.testing.assert_array_equal(l0, l1)
    assert set(g0) == set(g1)
    for n in g0:
        np.testing.assert_array_equal(g0[n], g1[n])


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_preserves_custom_vjp_rules(monkeypatch, policy):
    # make_loss's bwd returns grad_scale regardless of the upstream
    # cotangent, and BN's bwd is the closed-form kernel — if remat
    # segmentation inlined the primal bodies, autodiff-of-primal would
    # produce very different grads (identity-forward make_loss would
    # just pass the cotangent through)
    x = _x((16, 12), seed=14)
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "none")
    l0, g0 = _loss_and_grads(_custom_grad_net(seed=77), x)
    monkeypatch.setenv("MXTPU_REMAT_POLICY", policy)
    l1, g1 = _loss_and_grads(_custom_grad_net(seed=77), x)
    np.testing.assert_array_equal(l0, l1)
    assert set(g0) == set(g1)
    # Four of the six leaves are numerically zero here (BatchNorm's
    # output sums to zero over the batch and make_loss's cotangent is a
    # constant), so their bits are the rounding of sums whose terms are
    # as large as the largest leaf (d2.bias, 48): a recomputed forward
    # may round them differently.  A lost custom rule moves d2.bias and
    # bn.beta by their own size, far over a few ulps of that scale.
    scale = max(float(np.abs(g).max()) for g in g0.values())
    for n in g0:
        np.testing.assert_allclose(g1[n], g0[n], rtol=0, atol=2e-6 * scale)


def test_segmented_remat_keeps_custom_vjp_bwd():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.tensor import make_loss
    from mxnet_tpu.passes import remat

    def body(x):
        h = jnp.tanh(x * 2.0)
        return make_loss(h, grad_scale=3.0).sum()

    xb = jnp.linspace(-1.0, 1.0, 8, dtype=jnp.float32)
    closed, _ = passes.trace_closed(body, (xb,))
    seg = remat.segmented_remat(closed, "full", 2)

    def f_ref(v):
        return jax.core.eval_jaxpr(closed.jaxpr, closed.consts, v)[0]

    def f_seg(v):
        return jax.core.eval_jaxpr(seg.jaxpr, seg.consts, v)[0]

    g_ref = np.asarray(jax.grad(f_ref)(xb))
    g_seg = np.asarray(jax.grad(f_seg)(xb))
    np.testing.assert_array_equal(g_ref, g_seg)
    # and both ARE the custom bwd: 3.0 through tanh' * 2, not the
    # upstream-cotangent passthrough the identity primal would give
    expected = 3.0 * (1.0 - np.tanh(2.0 * np.asarray(xb)) ** 2) * 2.0
    np.testing.assert_allclose(g_ref, expected, rtol=1e-5, atol=1e-6)


def test_remat_applies_only_to_training(monkeypatch):
    monkeypatch.setenv("MXTPU_REMAT_POLICY", "full")
    net = _mlp(seed=2)
    x = _x()
    net(x)  # predict build: RematPass.applies is False
    ctx = passes.block_context(net, training=False)
    assert not any(p.name == "remat"
                   for p in passes.resolve_passes(ctx))
    ctx_t = passes.block_context(net, training=True)
    assert any(p.name == "remat" for p in passes.resolve_passes(ctx_t))


def test_segmented_remat_reduces_estimated_training_peak():
    import jax.numpy as jnp

    from mxnet_tpu.passes import memory, remat

    def deep(x, ws):
        h = x
        for w in ws:
            h = jnp.tanh(h @ w)
        return (h * h).sum(axis=-1)

    ws = [jnp.full((64, 64), 0.01, jnp.float32) for _ in range(16)]
    xb = jnp.ones((1024, 64), jnp.float32)
    closed, _ = passes.trace_closed(deep, (xb, ws))
    base = memory.estimate_training_peak_bytes(closed)
    seg = remat.segmented_remat(
        closed, "full", remat.default_segments(len(closed.jaxpr.eqns)))
    low = memory.estimate_training_peak_bytes(seg)
    assert low < base
    # and the rewrite is output-bitwise-identical
    import jax

    flat, _ = jax.tree_util.tree_flatten((xb, ws))
    o1 = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
    o2 = jax.core.eval_jaxpr(seg.jaxpr, seg.consts, *flat)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_auto_picks_policy_from_budget(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu.passes import memory, remat

    def deep(x, ws):
        h = x
        for w in ws:
            h = jnp.tanh(h @ w)
        return (h * h).sum(axis=-1)

    ws = [jnp.full((64, 64), 0.01, jnp.float32) for _ in range(16)]
    xb = jnp.ones((1024, 64), jnp.float32)
    closed, _ = passes.trace_closed(deep, (xb, ws))
    base = memory.estimate_training_peak_bytes(closed)

    ctx = passes.PassContext(label="t", kind="block", training=True)
    monkeypatch.setenv("MXTPU_REMAT_BUDGET_MB", str((base >> 20) + 16))
    assert remat.choose_policy(closed, ctx) == "none"  # fits already
    tight = remat.segmented_remat(closed, "full", 4)
    tight_mb = (memory.estimate_training_peak_bytes(tight) >> 20) + 1
    monkeypatch.setenv("MXTPU_REMAT_BUDGET_MB", str(tight_mb))
    assert remat.choose_policy(closed, ctx) in ("dots", "full")
    assert ctx.notes["remat_estimates"]["full"] < base


def test_remat_auto_reduces_reported_peak_bitwise(monkeypatch):
    """The acceptance path: remat on a deep model reduces the compile
    registry's reported peak while loss/grads stay bitwise-equal."""
    mx.telemetry.enable()
    from mxnet_tpu import diagnostics

    # liveness reporting is opt-in (costs a trace per compile); the
    # policy="none" leg needs it reported too for the comparison
    monkeypatch.setenv("MXTPU_DIAG_MEMORY", "1")
    x = _x((512, 64), seed=6)

    def run(policy):
        monkeypatch.setenv("MXTPU_REMAT_POLICY", policy)
        net = _deep_mlp(seed=23, depth=8)
        loss, grads = _loss_and_grads(net, x)
        entry = diagnostics.compile_registry().get(
            ("HybridSequential", "train"))
        assert entry is not None and entry.get("peak_live_bytes")
        return loss, grads, entry["peak_live_bytes"]

    l0, g0, p0 = run("none")
    l1, g1, p1 = run("full")
    assert p1 < p0, f"remat did not reduce reported peak: {p1} vs {p0}"
    np.testing.assert_array_equal(l0, l1)
    for n in g0:
        np.testing.assert_array_equal(g0[n], g1[n])
    # the remat_policy gauge recorded what was applied
    gauge = {labels[0]: g.value for labels, g in ti.remat_policy.series()}
    assert gauge.get("HybridSequential") == ti.REMAT_POLICY_CODES["full"]


# -- cross-CachedOp dedup ----------------------------------------------------

def test_dedup_two_identical_heads_share_one_executable(monkeypatch):
    mx.telemetry.enable()
    monkeypatch.setenv("MXTPU_GRAPH_DEDUP", "1")
    passes.reset_executable_cache()
    x = _x(seed=8)
    a, b = _mlp(seed=31), _mlp(seed=32)  # same structure, new weights
    before = _trace_count()
    hits0 = sum(c.value for _l, c in ti.graph_dedup_hits_total.series())
    ya = a(x).asnumpy()
    assert _trace_count() - before == 1
    yb = b(x).asnumpy()
    # the zero-retrace proof: b's build matched a's program
    assert _trace_count() - before == 1
    hits1 = sum(c.value for _l, c in ti.graph_dedup_hits_total.series())
    assert hits1 - hits0 >= 1
    info = passes.executable_cache_info()
    assert info["entries"] >= 1 and info["hits"] >= 1
    # shared executable, b's OWN weights: outputs differ from a's and
    # match the reference math
    assert not np.array_equal(ya, yb)
    params = {n: v.data().asnumpy() for n, v in b.collect_params().items()}
    ws = [params[n] for n in sorted(params) if n.endswith("weight")]
    bs = [params[n] for n in sorted(params) if n.endswith("bias")]
    h = np.maximum(x.asnumpy() @ ws[0].T + bs[0], 0.0)
    ref = h @ ws[1].T + bs[1]
    np.testing.assert_allclose(ref, yb, rtol=1e-5, atol=1e-5)


def test_dedup_different_structures_do_not_share(monkeypatch):
    mx.telemetry.enable()
    monkeypatch.setenv("MXTPU_GRAPH_DEDUP", "1")
    passes.reset_executable_cache()
    x = _x(seed=9)
    a = _mlp(seed=41, hidden=16)
    b = _mlp(seed=42, hidden=32)  # different widths: different key
    before = _trace_count()
    a(x)
    b(x)
    assert _trace_count() - before == 2  # both traced
    assert passes.executable_cache_info()["hits"] == 0


def test_dedup_grads_bitwise_vs_no_dedup(monkeypatch):
    x = _x(seed=10)
    l0, g0 = _loss_and_grads(_mlp(seed=51), x)
    monkeypatch.setenv("MXTPU_GRAPH_DEDUP", "1")
    passes.reset_executable_cache()
    # two identical heads; the SECOND (dedup hit) must still train
    # bitwise-identically to the no-dedup baseline
    _ = _mlp(seed=51)(x)
    net = _mlp(seed=51)
    l1, g1 = _loss_and_grads(net, x)
    np.testing.assert_array_equal(l0, l1)
    for n in g0:
        np.testing.assert_array_equal(g0[n], g1[n])
    assert passes.executable_cache_info()["hits"] >= 1


def test_dedup_key_distinguishes_custom_grad_rules():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.tensor import make_loss
    from mxnet_tpu.passes.dedup import structural_key

    # same library op, two traces: keys MATCH (the dedup win survives —
    # rule tokens are stable across traces of one custom_vjp op)
    k1 = structural_key(
        jax.make_jaxpr(lambda v: make_loss(v * 2.0))(jnp.ones(4)))
    k2 = structural_key(
        jax.make_jaxpr(lambda v: make_loss(v * 2.0))(jnp.ones(4)))
    assert k1 is not None and k1 == k2

    # identical primal graphs, DIFFERENT custom bwd rules: keys differ.
    # Sharing one executable would apply the first block's bwd to the
    # second block's training (train variants go through jax.vjp of the
    # compiled callable).
    @jax.custom_vjp
    def ident3(v):
        return v

    ident3.defvjp(lambda v: (v, v),
                  lambda r, g: (jnp.full_like(r, 3.0),))

    @jax.custom_vjp
    def ident9(v):
        return v

    ident9.defvjp(lambda v: (v, v),
                  lambda r, g: (jnp.full_like(r, 9.0),))

    k3 = structural_key(
        jax.make_jaxpr(lambda v: ident3(v * 2.0))(jnp.ones(4)))
    k9 = structural_key(
        jax.make_jaxpr(lambda v: ident9(v * 2.0))(jnp.ones(4)))
    assert k3 is not None and k9 is not None
    assert k3 != k9


def test_dedup_grads_bitwise_with_custom_ops(monkeypatch):
    # custom_vjp-bearing programs (BN train kernel, make_loss) still
    # dedup across identical blocks AND keep their custom gradients
    x = _x((16, 12), seed=15)
    l0, g0 = _loss_and_grads(_custom_grad_net(seed=88), x)
    monkeypatch.setenv("MXTPU_GRAPH_DEDUP", "1")
    passes.reset_executable_cache()
    # a full first training seeds the cache with the TRAIN variant
    _ = _loss_and_grads(_custom_grad_net(seed=88), x)
    l1, g1 = _loss_and_grads(_custom_grad_net(seed=88), x)
    np.testing.assert_array_equal(l0, l1)
    for n in g0:
        np.testing.assert_array_equal(g0[n], g1[n])
    assert passes.executable_cache_info()["hits"] >= 1


# -- ordering / manager ------------------------------------------------------

class _LogPass(passes.GraphPass):
    kinds = ("block",)

    def __init__(self, name, priority, log):
        self.name = name
        self.priority = priority
        self.log = log

    def run(self, closed, ctx):
        self.log.append(self.name)
        return closed


def test_pass_ordering_is_deterministic():
    import jax.numpy as jnp

    specs = [("b", 20), ("a", 20), ("z", 10)]
    for order in (specs, list(reversed(specs))):
        log = []
        pm = passes.PassManager([_LogPass(n, p, log) for n, p in order])
        assert [p.name for p in pm.passes()] == ["z", "a", "b"]
        ctx = passes.PassContext(label="t", kind="block")
        closed, _ = passes.trace_closed(lambda v: v + 1,
                                        (jnp.ones(3),))
        passes.run_passes(closed, pm.passes(), ctx)
        assert log == ["z", "a", "b"]


def test_manager_register_replaces_by_name():
    log = []
    pm = passes.PassManager()
    pm.register(_LogPass("p", 10, log))
    pm.register(_LogPass("p", 30, log))  # replaces, new priority
    assert len(pm) == 1
    assert pm.get("p").priority == 30
    assert pm.remove("p") and len(pm) == 0


def test_pass_telemetry_recorded():
    mx.telemetry.enable()
    net = _mlp(seed=61)
    net.pass_pipeline().register(passes.AmpPass())
    before = sum(c.value for labels, c in ti.pass_applied_total.series()
                 if labels[0] == "amp")
    net(_x())
    after = sum(c.value for labels, c in ti.pass_applied_total.series()
                if labels[0] == "amp")
    assert after == before + 1
    ms = [h for labels, h in ti.pass_rewrite_ms.series()
          if labels[0] == "amp"]
    assert ms and ms[0].count >= 1


# -- export / symbol seams ---------------------------------------------------

def test_export_routes_through_pipeline(tmp_path):
    x = _x(seed=12)
    raw = _mlp(seed=71)(x).asnumpy()
    net = _mlp(seed=71)
    amp.convert_hybrid_block(net, graph_pass=True, example_inputs=(x,))
    converted = net(x).asnumpy()
    assert not np.array_equal(raw, converted)
    sym_file, _par = net.export(str(tmp_path / "m"))
    blk = gluon.SymbolBlock.imports(sym_file, ["data"])
    roundtrip = blk(x).asnumpy()
    # the exported program is the CONVERTED one, not the raw fp32 graph
    np.testing.assert_array_equal(converted, roundtrip)
