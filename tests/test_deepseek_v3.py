"""A ``deepseek_v3`` decoder on the Gluon path, at a small size on the CPU:
the flash kernel at a key width that differs from the value width, latent
attention and the whole model against the benchmark's plain reference, the
expert layer's sigmoid router with a selection bias, a scaling factor and
a shared expert, and the whole step with its counters and scopes."""
import hashlib
import json
import os
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.contrib.nn import DroplessMoE, GatedMLP
from mxnet_tpu.gluon.model_zoo.deepseek_v3 import (MultiHeadLatentAttention,
                                                   deepseek_v3)
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.ops import pallas_mla_heads as mh
from mxnet_tpu.parallel import moe
from mxnet_tpu.telemetry import instruments as ti

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plain references, builders and weights."""
    sys.path.insert(0, BENCH)
    try:
        import weights as wmod
        from models import deepseek_v3 as model
        from models import sdar_moe as sdar_model
        from reference import deepseek_v3 as ref
        from reference import sdar_moe as sdar_ref
        yield ref, wmod, model, sdar_ref, sdar_model
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(autouse=True)
def _expert_counters_start_and_end_empty():
    """The layers' staged counters and gauges are the process's: what a
    test here stages, another file's test would fetch."""
    def clear():
        ti._staged_moe_load.clear()
        for g in (ti.moe_rows_routed_here, ti.moe_expert_load_max_over_mean,
                  ti.moe_buffer_rows, ti.moe_bias_moved_share):
            g.clear()
    clear()
    yield
    clear()


def _toy(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def toy(bench):
    ref, wmod, *_ = bench
    cfg = _toy("toy_deepseek_v3")
    weights = wmod.make_weights(ref.param_specs(cfg), 7, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    return cfg, weights, batch


@pytest.fixture(scope="module")
def wide(bench):
    """The toy configuration with two heads of the published widths (nope
    128, rope 64, v 128): what `npx.mla_heads`'s kernels tile."""
    ref, wmod, *_ = bench
    cfg = dict(_toy("toy_deepseek_v3"), num_attention_heads=2,
               num_key_value_heads=2, qk_nope_head_dim=128,
               qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128)
    weights = wmod.make_weights(ref.param_specs(cfg), 7, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    return cfg, weights, batch


@pytest.fixture(params=["composition", "kernels"])
def heads(request, toy, wide, monkeypatch):
    """(cfg, weights, batch, the share of `npx.mla_heads`'s sites on its
    kernels): the toy widths on the composition of XLA ops, as every CPU
    run takes it, and the published widths with the kernels forced,
    interpreted."""
    monkeypatch.setattr(ti, "_mla_heads_sites", [0, 0])
    if request.param == "kernels":
        monkeypatch.setattr(mh, "_kernel_mode", lambda: True)
    yield (wide if request.param == "kernels" else toy) + (
        float(request.param == "kernels"),)
    ti.mla_heads_kernel_share.clear()


def _net(bench, cfg, weights, remat=False, dtype="float32"):
    model = bench[2]
    return model.build(mx, dict(cfg, remat=remat, dtype=dtype), weights,
                       mx.cpu())


def _close(got, want, atol=2e-4, msg=""):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    onp.testing.assert_allclose(onp.asarray(got) / scale,
                                onp.asarray(want) / scale, atol=atol,
                                err_msg=msg)


# -- (a) the flash kernel: keys wider than values ---------------------------

def _qkv(s, heads, kv, dk, dv, seed=0):
    rs = onp.random.RandomState(seed)
    mk = lambda h, w: jnp.asarray(  # noqa: E731
        rs.randn(2, h, s, w).astype("f")) * 0.5
    return mk(heads, dk), mk(kv, dk), mk(kv, dv), mk(heads, dv)


@pytest.mark.parametrize("s,heads,kv,dk,dv,tile,mask", [
    (128, 2, 2, 192, 128, 64, {"causal": True}),    # the published widths
    (96, 4, 4, 24, 16, 32, {"causal": True}),
    (104, 4, 2, 24, 16, 32, {"causal": True}),      # grouped, padded
    (64, 2, 2, 16, 24, 32, {}),                     # values the wider
    (128, 4, 2, 24, 16, 32, {"block_diffusion": (4, 64)}),
    (96, 4, 4, 16, 16, 32, {"causal": True}),       # equal widths, as before
], ids=["192-128", "24-16", "grouped-padded", "16-24", "blockdiff",
        "equal"])
def test_the_kernel_at_two_widths_matches_the_reference(s, heads, kv, dk, dv,
                                                        tile, mask):
    q, k, v, w = _qkv(s, heads, kv, dk, dv)

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, interpret=True, block_q=tile,
                                  block_k=tile, **mask)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, **mask)

    out = kernel(q, k, v)
    assert out.shape == (2, heads, s, dv)
    onp.testing.assert_allclose(out, plain(q, k, v), rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        assert g.shape == r.shape
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)


def test_the_default_scale_is_the_keys_width():
    q, k, v, _ = _qkv(32, 2, 2, 24, 16)
    a = pa.flash_attention(q, k, v, interpret=True, block_q=32, block_k=32)
    b = pa.flash_attention(q, k, v, interpret=True, block_q=32, block_k=32,
                           scale=24 ** -0.5)
    onp.testing.assert_array_equal(a, b)


def test_what_the_kernel_cannot_tile_falls_back_loudly():
    """A width that is no multiple of 8, or blocks that do not divide: the
    reference's result, one count and one warning each; shapes that are
    no attention at all raise."""
    q, k, v, _ = _qkv(32, 2, 2, 12, 16)
    before = dict((key, c.value) for key, c in
                  ti.attention_kernel_fallback_total.series())
    with pytest.warns(RuntimeWarning, match="cannot be tiled .width."):
        out = pa.flash_attention(q, k, v, interpret=True, causal=True)
    onp.testing.assert_allclose(
        out, pa.attention_reference(q, k, v, causal=True), rtol=1e-6)
    q, k, v, _ = _qkv(48, 2, 2, 16, 16)
    with pytest.warns(RuntimeWarning, match="cannot be tiled .tile."):
        pa.flash_attention(q, k, v, interpret=True, block_q=32, block_k=20)
    after = dict((key, c.value) for key, c in
                 ti.attention_kernel_fallback_total.series())
    for reason in ("width", "tile"):
        assert after[(reason,)] == before.get((reason,), 0) + 1
    # off a TPU with no kernel asked for, the reference is the documented
    # path and says nothing
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pa.flash_attention(*_qkv(32, 2, 2, 12, 16)[:3])
    with pytest.raises(ValueError, match="share their last dimension"):
        pa.flash_attention(q, k[..., :8], v, interpret=True)
    with pytest.raises(ValueError, match="every other"):
        pa.flash_attention(q, k, v[:, :, :40], interpret=True)


def test_interleaved_rotary_is_the_pairwise_rotation_in_half_layout():
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 5, 3, 8).astype("f"))       # (b, s, h, w)
    pos = jnp.arange(5).reshape(5, 1)
    out = ops_nn.rotary_embedding(x, pos, theta=1e6, interleaved=True)
    # pair (2i, 2i + 1) at position p turns by p * theta ** (-2i / w) and
    # lands at (i, i + w / 2)
    for i in range(4):
        ang = onp.arange(5)[None, :, None] * 1e6 ** (-2 * i / 8)
        a, b = x[..., 2 * i], x[..., 2 * i + 1]
        onp.testing.assert_allclose(
            out[..., i], a * onp.cos(ang) - b * onp.sin(ang), atol=1e-5)
        onp.testing.assert_allclose(
            out[..., i + 4], b * onp.cos(ang) + a * onp.sin(ang), atol=1e-5)
    # a score depends on the relative position alone
    q = ops_nn.rotary_embedding(x[:, 3:4], jnp.asarray([[3]]), 1e6, True)
    k = ops_nn.rotary_embedding(x[:, 1:2], jnp.asarray([[1]]), 1e6, True)
    q2 = ops_nn.rotary_embedding(x[:, 3:4], jnp.asarray([[4]]), 1e6, True)
    k2 = ops_nn.rotary_embedding(x[:, 1:2], jnp.asarray([[2]]), 1e6, True)
    onp.testing.assert_allclose(jnp.sum(q * k, -1), jnp.sum(q2 * k2, -1),
                                atol=1e-5)


# -- (b) latent attention and the whole model against the reference ---------

def test_the_latent_attention_block_matches_the_reference(bench, heads):
    ref = bench[0]
    cfg, weights, _, share = heads
    prefix = "model.layers.1."
    block = MultiHeadLatentAttention(
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"])
    block.initialize()
    for name, p in block.collect_params().items():
        p.set_data(NDArray(weights[prefix + "self_attn." + name]))
    x = jnp.asarray(onp.random.RandomState(1).randn(
        2, cfg["seq"], cfg["hidden_size"]).astype("f"))
    pos = jnp.arange(cfg["seq"], dtype=jnp.int32)
    got = block(NDArray(x), NDArray(pos)).asnumpy()
    want = ref._mla(cfg, weights, prefix, x, pos, "float32")
    _close(got, want, atol=2e-5)
    assert ti.mla_heads_kernel_share.value == share
    # causal: a later token does not move an earlier output
    x2 = x.at[:, -1].add(1.0)
    again = block(NDArray(x2), NDArray(pos)).asnumpy()
    onp.testing.assert_allclose(again[:, :-1], got[:, :-1], atol=1e-6)
    assert onp.abs(again[:, -1] - got[:, -1]).max() > 1e-4


def _loss_and_grads(net, batch):
    fn, params = net.as_pure_function(training=True)
    train = {n: v for n, v in params.items()
             if not n.endswith(("running_load", "router_bias"))}
    frozen = {n: v for n, v in params.items() if n not in train}

    def total(tr):
        per, _ = fn({**tr, **frozen}, jax.random.PRNGKey(0), *batch)
        return jnp.sum(per), per

    (_, per), grads = jax.value_and_grad(total, has_aux=True)(train)
    return per, grads


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_plain_reference(bench, heads,
                                                           remat):
    ref = bench[0]
    cfg, weights, batch, share = heads
    per, grads = _loss_and_grads(_net(bench, cfg, weights, remat), batch)
    assert ti.mla_heads_kernel_share.value == share
    train = {n: w for n, w in weights.items() if ref.trainable(n)}
    frozen = {n: w for n, w in weights.items() if n not in train}

    def total(tr):
        per = ref.per_sample_loss(cfg, {**tr, **frozen}, batch)
        return jnp.sum(per), per

    (_, ref_per), ref_grads = jax.value_and_grad(total, has_aux=True)(train)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    assert set(grads) == set(ref_grads)
    assert not [n for n in grads if "router_bias" in n]
    for name, g in ref_grads.items():
        _close(grads[name], g, msg=name)


def test_the_layers_are_one_dense_then_sparse_and_amp_keeps_the_router(
        bench, toy):
    cfg, weights, batch = toy
    net = _net(bench, cfg, weights, dtype="bfloat16")
    layers = list(net.model.layers)
    assert isinstance(layers[0].mlp, GatedMLP)
    assert all(isinstance(l.mlp, DroplessMoE) for l in layers[1:])
    kinds = {n: str(p.data().dtype) for n, p in net.collect_params().items()}
    for name, kind in kinds.items():
        keeps = any(k in name for k in ("gamma", "router", "running_load"))
        assert kind == ("float32" if keeps else "bfloat16"), name
    assert kinds["model.layers.1.mlp.router_bias"] == "float32"
    bias = net.model.layers[1].mlp.router_bias
    assert bias.grad_req == "null"
    with pytest.raises(NotImplementedError):
        deepseek_v3(64, 32, 2, 4, 16, 16, 8, 16, 96, 16, 8, 2,
                    q_lora_rank=24)
    full = _net(bench, cfg, weights)(NDArray(batch[0])).asnumpy()
    low = net(NDArray(batch[0])).asnumpy()
    assert low.dtype == onp.float32 and onp.allclose(low, full, rtol=0.02)


# -- (c) the router's variants and the shared expert ------------------------

def _layer_weights(seed=0, n=64, d=16, f=12, experts=16, shared=20):
    rs = onp.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype("f")) * 0.3  # noqa: E731
    return mk(n, d) / 0.3, {
        "l.mlp.router": mk(experts, d) / 0.3,
        "l.mlp.router_bias": mk(experts) * 0.5,
        "l.mlp.gate_proj": mk(experts, d, f),
        "l.mlp.up_proj": mk(experts, d, f),
        "l.mlp.down_proj": mk(experts, f, d),
        "l.mlp.shared.gate_proj.weight": mk(shared, d),
        "l.mlp.shared.up_proj.weight": mk(shared, d),
        "l.mlp.shared.down_proj.weight": mk(d, shared)}


CUT = {"num_experts_per_tok": 4, "norm_topk_prob": True, "router_width": 16,
       "routed_scaling_factor": 2.448, "n_routed_experts": 16, "ep_rank": 0}


def _share(p, lo, hi):
    return {k: (v[lo:hi] if k.endswith(("gate_proj", "up_proj", "down_proj"))
                else v) for k, v in p.items()}


def _block(p, ep_size=1, ep_rank=0, shared=20):
    layer = DroplessMoE(16, 12, 16, 4, ep_size=ep_size, ep_rank=ep_rank,
                        scoring_func="sigmoid", selection_bias=True,
                        routed_scaling_factor=2.448, shared_units=shared)
    layer.initialize()
    held = 16 // ep_size
    part = _share(p, ep_rank * held, (ep_rank + 1) * held)
    for name, param in layer.collect_params().items():
        if name != "running_load":
            param.set_data(NDArray(part["l.mlp." + name]))
    return layer


def test_the_block_with_every_variant_matches_the_reference(bench):
    ref = bench[0]
    x, p = _layer_weights()
    want = ref._routed(CUT, p, "l.", x, "float32") \
        + ref._mlp(p, "l.mlp.shared.", x, "float32")
    layer = _block(p)
    onp.testing.assert_allclose(layer(NDArray(x)).asnumpy(), want,
                                atol=3e-5)
    assert layer.running_load.shape == (3,)
    assert DroplessMoE(16, 12, 16, 4).running_load.shape == (2,)
    assert DroplessMoE(16, 12, 16, 4).router_bias is None
    with pytest.raises(ValueError, match="scoring_func"):
        DroplessMoE(16, 12, 16, 4, scoring_func="tanh")


def test_the_bias_selects_and_never_weighs_and_has_no_gradient(bench):
    ref = bench[0]
    x, p = _layer_weights(1)
    logits = x @ p["l.mlp.router"].T
    bias = p["l.mlp.router_bias"]
    g0, e0 = moe.route_top_k(logits, 4, scoring="sigmoid", scale=2.448)
    g1, e1 = moe.route_top_k(logits, 4, scoring="sigmoid", bias=bias,
                             scale=2.448)
    # the choice follows score + bias ...
    want = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 4)[1]
    onp.testing.assert_array_equal(e1, want)
    moved = float(moe.bias_moved_share(logits, e1, "sigmoid"))
    assert 0.1 < moved < 0.9 and not onp.array_equal(e0, e1)
    assert float(moe.bias_moved_share(logits, e0, "sigmoid")) == 0.0
    # ... the gates are the plain scores of the chosen, normalised and
    # scaled: a bias moved by a constant moves nothing, and a gate never
    # holds the bias
    s = onp.take_along_axis(onp.asarray(jax.nn.sigmoid(logits)),
                            onp.asarray(e1), 1)
    onp.testing.assert_allclose(g1, 2.448 * s / s.sum(1, keepdims=True),
                                rtol=1e-6)
    onp.testing.assert_allclose(g1.sum(1), 2.448, rtol=1e-6)
    g2, e2 = moe.route_top_k(logits, 4, scoring="sigmoid", bias=bias + 7.0,
                             scale=2.448)
    onp.testing.assert_array_equal(e2, e1)
    onp.testing.assert_allclose(g2, g1, rtol=1e-6)
    rg, re_ = ref.route(CUT, p, "l.", x)
    onp.testing.assert_array_equal(re_, e1)
    onp.testing.assert_allclose(rg, g1, rtol=1e-6)
    # no gradient reaches the bias; the router's is the reference's
    args = (x, p["l.mlp.router"], p["l.mlp.gate_proj"], p["l.mlp.up_proj"],
            p["l.mlp.down_proj"], bias)

    def ours(*a):
        out, load = moe.dropless_moe(*a[:5], top_k=4, scoring="sigmoid",
                                     bias=a[5], scale=2.448)
        return jnp.sum(out ** 2)

    def theirs(*a):
        q = dict(p, **{"l.mlp.router": a[1], "l.mlp.gate_proj": a[2],
                       "l.mlp.up_proj": a[3], "l.mlp.down_proj": a[4],
                       "l.mlp.router_bias": a[5]})
        return jnp.sum(ref._routed(CUT, q, "l.", a[0], "float32") ** 2)

    got = jax.grad(ours, range(6))(*args)
    want = jax.grad(theirs, range(6))(*args)
    assert not jnp.any(got[5]) and not jnp.any(want[5])
    for g, w in zip(got[:5], want[:5]):
        _close(g, w, atol=2e-5)
    # softmax scoring is what it was: the largest of the scores themselves
    gs, es = moe.route_top_k(logits, 4)
    onp.testing.assert_array_equal(
        es, jax.lax.top_k(jax.nn.softmax(logits), 4)[1])
    onp.testing.assert_allclose(gs.sum(1), 1.0, rtol=1e-6)


def test_the_load_counts_what_the_bias_moved():
    x, p = _layer_weights(2)
    layer = _block(p, ep_size=4, ep_rank=1)
    with autograd.record():
        layer(NDArray(x))
    assert not ti.moe_bias_moved_share.series()     # nothing fetched yet
    out = ti.flush_moe_load()
    rows, ratio = out["DroplessMoE"]                # two numbers, as ever
    logits = x @ p["l.mlp.router"].T
    _, experts = moe.route_top_k(logits, 4, scoring="sigmoid",
                                 bias=p["l.mlp.router_bias"])
    assert rows == int(jnp.sum((experts >= 4) & (experts < 8)))
    moved = ti.moe_bias_moved_share.labels("DroplessMoE").value
    assert moved == pytest.approx(
        float(moe.bias_moved_share(logits, experts, "sigmoid")))
    assert 0.0 < moved < 1.0
    # a zero bias moves nothing, and the gauge says so
    layer.router_bias.set_data(NDArray(jnp.zeros(16)))
    with autograd.record():
        layer(NDArray(x))
    ti.flush_moe_load()
    assert ti.moe_bias_moved_share.labels("DroplessMoE").value == 0.0


# -- (d) the shares add up, the shared expert counted once ------------------

def test_the_eight_shares_add_up_to_the_uncut_reference_layer(bench):
    ref = bench[0]
    x, p = _layer_weights(3)
    shared = ref._mlp(p, "l.mlp.shared.", x, "float32")
    whole = ref._routed(CUT, p, "l.", x, "float32") + shared
    parts, rows = 0.0, 0.0
    for rank in range(8):
        layer = _block(p, ep_size=8, ep_rank=rank)
        with autograd.record():
            out = layer(NDArray(x)).asnumpy()
        want = ref._routed(dict(CUT, n_routed_experts=2, ep_rank=rank),
                           _share(p, 2 * rank, 2 * rank + 2), "l.", x,
                           "float32") + shared
        onp.testing.assert_allclose(out, want, atol=3e-5)
        # what every chip computes alike is counted once
        parts = parts + out - (shared if rank else 0.0)
        rows += ti.flush_moe_load()["DroplessMoE"][0]
    onp.testing.assert_allclose(parts, whole, atol=1e-4)
    assert rows == x.shape[0] * 4           # every assignment, once


# -- (e) SDAR's step is what it was ------------------------------------------

class _Lowered(Exception):
    pass


def _lowered_step(net, n_data, opt, batch):
    trainer = gluon.Trainer(
        net.collect_params(), opt["name"],
        {k: v for k, v in opt.items() if k != "name"}, kvstore="tpu_dist")
    step = gluon.TrainStep(net, None, trainer, n_data=n_data)
    jitted = step._jitted

    def intercept(donate):
        fn = jitted(donate)

        def lower_only(*a):
            raise _Lowered(fn.lower(*a).as_text())
        return lower_only

    step._jitted = intercept
    with pytest.raises(_Lowered) as caught:
        step(*[NDArray(a) for a in batch])
    return caught.value.args[0]


def test_sdars_toy_step_lowers_to_the_text_it_had_before_pr_38(bench):
    """The kernel's second width, the router's variants and the shared
    module are arguments at their defaults for SDAR: its whole step,
    lowered on the CPU at the toy size (never compiled or run), is byte
    for byte the text the parent commit lowered (441,310 characters)."""
    _, wmod, _, ref, model = bench
    cfg = _toy("toy_sdar_moe")
    weights = wmod.make_weights(ref.param_specs(cfg), 7, cfg["dtype"])
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    text = _lowered_step(model.build(mx, cfg, weights, mx.cpu()), 3,
                         cfg["optimizer"], batch)
    assert len(text) == 441310
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "9886f21840a2064dc60802a69796706e320688ab8d18231c50e1548bd0e5b166")


# -- the whole step, its counters and its scopes ----------------------------

def test_train_step_takes_it_whole_with_counters_and_scopes(bench, toy):
    from mxnet_tpu.diagnostics import introspect

    cfg, weights, batch = toy
    introspect.reset()
    net = _net(bench, cfg, weights, remat=True, dtype="bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    tokens = NDArray(batch[0])
    bias = net.model.layers[1].mlp.router_bias.data().asnumpy()
    before = net.lm_head.weight.data().asnumpy().astype("f")
    losses = [float(step(tokens).asnumpy().mean()) for _ in range(4)]
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1
    assert losses[-1] < losses[0]
    assert not onp.array_equal(
        before, net.lm_head.weight.data().asnumpy().astype("f"))
    # the bias is held fixed through the steps
    onp.testing.assert_array_equal(
        bias, net.model.layers[1].mlp.router_bias.data().asnumpy())
    assert onp.abs(bias).max() > 0
    load = ti.flush_moe_load()
    layers = ["model.layers.1.mlp", "model.layers.2.mlp"]
    assert sorted(load) == layers      # the sparse layers alone count rows
    tokens_k = 2 * cfg["seq"] * cfg["num_experts_per_tok"]
    for layer in layers:
        rows, ratio = load[layer]
        assert 0 < rows <= tokens_k
        assert 1.0 <= ratio <= cfg["n_routed_experts"]
        assert 0.0 < ti.moe_bias_moved_share.labels(layer).value < 1.0
    assert ti.step_scalar_operands.value == 4
    scopes = set()
    for (block, _), entry in introspect.compile_registry().items():
        if block == "whole_step":
            scopes.update(entry["op_scopes"].values())
    text = "\n".join(scopes)
    for name in ("/mla/mla.q/", "/mla/mla.kv_latent/", "/mla/mla.rope/",
                 "/mla/attention/", "/mla/mla.out/", "/moe.shared/",
                 "/moe.router/", "/moe.dispatch/", "/moe.experts/",
                 "/moe.combine/", "/lm_head/", "DeepseekV3DecoderLayer_1",
                 "GatedMLP_mlp", "/optimizer/"):
        assert name in text, name
    # a shared expert's scope holds no routed scope and the other way round
    assert not [s for s in scopes if "/moe.shared/" in s and any(
        r in s for r in ("/moe.router/", "/moe.experts/"))]
    ti.moe_bias_moved_share.clear()
