"""Graph-pass pipeline (mxnet_tpu/passes; docs/passes.md): seam
identity under the kill switch, pipeline-AMP vs legacy amp_rewrite,
pass-ordering determinism, export-through-pipeline."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, gluon, passes
from mxnet_tpu.telemetry import instruments as ti


def _mlp(seed=0, hidden=16, out=4):
    mx.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(hidden, activation="relu"),
            gluon.nn.Dense(out))
    net.initialize()
    net.hybridize()
    return net


def _x(shape=(4, 8), seed=0):
    return mx.np.array(np.random.RandomState(seed).rand(*shape)
                       .astype("f"))


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
    # "kernels", "layout" (PR 31) and "remat" (PR 50) named passes that
    # were deleted
    monkeypatch.setenv("MXTPU_PASSES", name)
    net = _mlp(seed=1)
    with pytest.raises(ValueError, match=name) as err:
        net(_x())
    assert "['amp', 'numerics', 'sharding']" in str(err.value)


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
