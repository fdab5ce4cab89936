"""An ``lfm2_moe`` decoder on the Gluon path, at a small size on the CPU:
the gated short convolution's mix as one op (against a grouped
convolution, a loop over positions and autodiff of its composition), the
stack of layers of two kinds and the whole model against the benchmark's
plain reference, the expert layer's share, the router's normaliser, the
tied head, and the whole step with its gauges and scopes."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, npx
from mxnet_tpu.gluon.contrib.nn import DroplessMoE, GatedMLP, GatedShortConv
from mxnet_tpu.gluon.model_zoo.decoder import GroupedQueryAttention
from mxnet_tpu.gluon.model_zoo.lfm2_moe import lfm2_moe
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import short_conv
from mxnet_tpu.parallel import moe
from mxnet_tpu.telemetry import instruments as ti

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plain reference, builder and weights."""
    sys.path.insert(0, BENCH)
    try:
        import weights as wmod
        from models import lfm2_moe as model
        from reference import lfm2_moe as ref
        yield ref, wmod, model
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


def _toy_cfg(**changes):
    with open(os.path.join(BENCH, "configs", "toy_lfm2_moe.json")) as f:
        return dict(json.load(f), **changes)


@pytest.fixture(scope="module")
def toy(bench):
    ref, wmod, _ = bench
    cfg = _toy_cfg()
    weights = wmod.make_weights(ref.param_specs(cfg), 7, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    return cfg, weights, batch


def _net(bench, cfg, weights, remat=False, dtype="float32"):
    return bench[2].build(mx, dict(cfg, remat=remat, dtype=dtype), weights,
                          mx.cpu())


def _close(got, want, atol=2e-4, msg=""):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    onp.testing.assert_allclose(onp.asarray(got) / scale,
                                onp.asarray(want) / scale, atol=atol,
                                err_msg=msg)


# -- (a) the mix, as one op ---------------------------------------------------

def _streams(b=2, s=12, d=8, taps=3, seed=0, dtype="float32"):
    rs = onp.random.RandomState(seed)
    bcx = jnp.asarray(rs.randn(b, s, 3 * d).astype("f")).astype(dtype)
    w = jnp.asarray(rs.randn(d, taps).astype("f"))
    return bcx, w


def _composition(bcx, w):
    """B, C, x~ and the taps through plain jnp, for autodiff."""
    b_, c_, x_ = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    taps, s = w.shape[1], bcx.shape[1]
    u = jnp.pad(b_ * x_, ((0, 0), (taps - 1, 0), (0, 0)))
    return c_ * sum(w[:, j] * u[:, j:j + s] for j in range(taps))


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_the_mix_is_a_grouped_causal_convolution_between_two_gates(taps):
    bcx, w = _streams(taps=taps, seed=taps)
    d = w.shape[0]
    b_, c_, x_ = onp.split(onp.asarray(bcx), 3, axis=-1)
    got = short_conv.gated_short_conv(bcx, w)
    assert got.shape == (2, 12, d) and got.dtype == bcx.dtype
    # PyTorch's Conv1d(D, D, L, groups=D, padding=L - 1), its first S outputs
    conv = jax.lax.conv_general_dilated(
        jnp.asarray(b_ * x_).transpose(0, 2, 1), w[:, None, :], (1,),
        [(taps - 1, taps - 1)], feature_group_count=d,
        precision=jax.lax.Precision.HIGHEST)[:, :, :12].transpose(0, 2, 1)
    onp.testing.assert_allclose(got, c_ * conv, rtol=1e-5, atol=1e-6)
    # and a loop over positions, tap L - 1 on the position itself
    u, want = b_ * x_, onp.zeros((2, 12, d), "f")
    for t in range(12):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                want[:, t] += onp.asarray(w)[:, j] * u[:, t - (taps - 1) + j]
    onp.testing.assert_allclose(got, c_ * want, rtol=1e-5, atol=1e-6)


def test_the_mix_is_causal_and_its_first_outputs_see_the_padding():
    bcx, w = _streams(seed=1)
    base = short_conv.gated_short_conv(bcx, w)
    for t in (0, 5, 11):
        moved = short_conv.gated_short_conv(bcx.at[:, t].add(1.0), w)
        delta = onp.abs(onp.asarray(moved - base)).max(axis=(0, 2))
        assert not delta[:t].any()                  # nothing before t
        assert delta[t:t + 3].all()                 # t and the two after
        assert not delta[t + 3:].any()
    # outputs 0 and 1 read zeros before the sequence: one and two taps
    b_, c_, x_ = onp.split(onp.asarray(bcx), 3, axis=-1)
    u, wn = b_ * x_, onp.asarray(w)
    onp.testing.assert_allclose(base[:, 0], c_[:, 0] * wn[:, 2] * u[:, 0],
                                rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(
        base[:, 1], c_[:, 1] * (wn[:, 2] * u[:, 1] + wn[:, 1] * u[:, 0]),
        rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_hand_written_vjp_is_the_compositions_gradient(dtype):
    bcx, w = _streams(s=16, seed=2, dtype=dtype)
    dy = jnp.asarray(onp.random.RandomState(3).randn(2, 16, 8).astype("f"))
    got = jax.grad(lambda a, b: jnp.sum(
        short_conv.gated_short_conv(a, b).astype(jnp.float32) * dy),
        (0, 1))(bcx, w)
    want = jax.grad(lambda a, b: jnp.sum(_composition(a, b) * dy),
                    (0, 1))(bcx, w)
    assert got[0].dtype == bcx.dtype and got[0].shape == bcx.shape
    assert got[1].dtype == jnp.float32 and got[1].shape == w.shape
    tol = 1e-5 if dtype == "float32" else 2e-2
    for g, r, name in zip(jnp.split(got[0].astype(jnp.float32), 3, -1)
                          + [got[1]],
                          jnp.split(want[0].astype(jnp.float32), 3, -1)
                          + [want[1]], ("dB", "dC", "dx", "dw")):
        _close(g, r, atol=tol, msg=name)


def test_the_op_is_registered_and_refuses_what_is_no_mix():
    from mxnet_tpu.ops import registry

    assert registry.get_op("gated_short_conv") is short_conv.gated_short_conv
    bcx, w = _streams()
    out = npx.gated_short_conv(NDArray(bcx), NDArray(w))
    onp.testing.assert_array_equal(out.asnumpy(),
                                   short_conv.gated_short_conv(bcx, w))
    with pytest.raises(ValueError, match="streams"):
        short_conv.gated_short_conv(bcx[..., :23], w)
    with pytest.raises(ValueError, match="taps"):
        short_conv.gated_short_conv(bcx, w[:4])


def test_the_block_is_projection_mix_projection_with_float32_taps(bench,
                                                                  toy):
    from mxnet_tpu import amp

    ref = bench[0]
    cfg, weights, _ = toy
    prefix = "model.layers.2."
    block = GatedShortConv(cfg["hidden_size"], kernel=cfg["conv_L_cache"])
    block.initialize()
    assert block.conv_taps.shape == (64, 3)
    for name, p in block.collect_params().items():
        p.set_data(NDArray(weights[prefix + "conv." + name]))
    x = jnp.asarray(onp.random.RandomState(1).randn(2, 32, 64).astype("f"))
    got = block(NDArray(x)).asnumpy()
    _close(got, ref.short_conv(weights, prefix, x, "float32"), atol=2e-5)
    amp.convert_hybrid_block(block, target_dtype="bfloat16")
    kinds = {n: str(p.data().dtype) for n, p in
             block.collect_params().items()}
    assert kinds == {"in_proj.weight": "bfloat16", "conv_taps": "float32",
                     "out_proj.weight": "bfloat16"}


# -- (b) the stack of two kinds of layer against the reference ---------------

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


def _ref_loss_and_grads(ref, cfg, weights, batch):
    train = {n: w for n, w in weights.items() if ref.trainable(n)}
    frozen = {n: w for n, w in weights.items() if n not in train}

    def total(tr):
        per = ref.per_sample_loss(cfg, {**tr, **frozen}, batch)
        return jnp.sum(per), per

    (_, per), grads = jax.value_and_grad(total, has_aux=True)(train)
    return per, grads


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_plain_reference(bench, toy,
                                                           remat):
    ref = bench[0]
    cfg, weights, batch = toy
    per, grads = _loss_and_grads(_net(bench, cfg, weights, remat), batch)
    ref_per, ref_grads = _ref_loss_and_grads(ref, cfg, weights, batch)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    assert set(grads) == set(ref_grads)
    assert not [n for n in grads if "router_bias" in n]
    assert "lm_head.weight" not in grads            # tied: one matrix
    for name, g in ref_grads.items():
        _close(grads[name], g, msg=name)


@pytest.mark.parametrize("kind,dense,operator,feed_forward", [
    ("conv", 1, GatedShortConv, GatedMLP),
    ("conv", 0, GatedShortConv, DroplessMoE),
    ("full_attention", 1, GroupedQueryAttention, GatedMLP),
    ("full_attention", 0, GroupedQueryAttention, DroplessMoE),
], ids=["conv-dense", "conv-moe", "attention-dense", "attention-moe"])
def test_layer_types_and_num_dense_layers_choose_each_half_by_itself(
        bench, kind, dense, operator, feed_forward):
    """One layer of each of the four kinds, alone, against the reference."""
    ref, wmod, _ = bench
    cfg = _toy_cfg(layer_types=[kind], num_hidden_layers=1,
                   num_dense_layers=dense)
    weights = wmod.make_weights(ref.param_specs(cfg), 11, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 11, 1)[0]
    net = _net(bench, cfg, weights)
    layer = net.model.layers[0]
    assert isinstance(getattr(layer, "conv", None)
                      or layer.self_attn, operator)
    assert isinstance(layer.feed_forward, feed_forward)
    assert layer.kind == ("conv" if kind == "conv" else "attention",
                          "dense" if dense else "moe")
    per, grads = _loss_and_grads(net, batch)
    ref_per, ref_grads = _ref_loss_and_grads(ref, cfg, weights, batch)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    for name, g in ref_grads.items():
        _close(grads[name], g, msg=name)


def test_the_factory_takes_config_jsons_keys_and_refuses_what_is_not_written():
    net = lfm2_moe(64, 64, ["conv", "conv", "full_attention", "conv"], 4, 2,
                   96, 24, 8, 2)
    kinds = [layer.kind for layer in net.model.layers]
    assert kinds == [("conv", "dense"), ("conv", "dense"),
                     ("attention", "moe"), ("conv", "moe")]
    attn = net.model.layers[2].self_attn
    assert (attn._heads, attn._kv_heads, attn._hd, attn._theta,
            attn._eps) == (4, 2, 16, 1e6, 1e-5)
    moe_ = net.model.layers[3].feed_forward
    assert moe_._normalize_eps == 1e-6 and moe_._scoring == "sigmoid"
    assert moe_.router_bias is not None and moe_.shared is None
    assert net.lm_head is None
    assert lfm2_moe(64, 64, ["conv"], 4, 2, 96, 24, 8, 2,
                    tie_word_embeddings=False).lm_head is not None
    with pytest.raises(NotImplementedError, match="conv_bias"):
        lfm2_moe(64, 64, ["conv"], 4, 2, 96, 24, 8, 2, conv_bias=True)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_moe(64, 64, ["conv", "sliding"], 4, 2, 96, 24, 8, 2)
    with pytest.raises(ValueError, match="num_hidden_layers"):
        lfm2_moe(64, 64, ["conv"], 4, 2, 96, 24, 8, 2, num_hidden_layers=2)


# -- (c) the expert layer: the share and the normaliser ----------------------

def _layer_weights(seed=0, n=48, d=16, f=12, experts=8):
    rs = onp.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype("f")) * 0.3  # noqa: E731
    return mk(n, d) / 0.3, {
        "l.feed_forward.router": mk(experts, d) / 0.3,
        "l.feed_forward.router_bias": mk(experts) * 0.5,
        "l.feed_forward.gate_proj": mk(experts, d, f),
        "l.feed_forward.up_proj": mk(experts, d, f),
        "l.feed_forward.down_proj": mk(experts, f, d)}


CUT = {"num_experts_per_tok": 2, "norm_topk_prob": True, "router_width": 8,
       "routed_scaling_factor": 1, "use_expert_bias": True,
       "num_experts": 8, "ep_rank": 0}


def _share(p, lo, hi):
    return {k: (v[lo:hi] if k.endswith(("gate_proj", "up_proj", "down_proj"))
                else v) for k, v in p.items()}


@pytest.mark.parametrize("ep_size", [1, 2, 4])
def test_the_shares_partial_results_add_up_to_the_uncut_reference_layer(
        bench, ep_size):
    ref = bench[0]
    x, p = _layer_weights(ep_size)
    whole = ref.routed(CUT, p, "l.", x, "float32")
    held = 8 // ep_size
    parts, rows = 0.0, 0.0
    for rank in range(ep_size):
        layer = DroplessMoE(16, 12, 8, 2, ep_size=ep_size, ep_rank=rank,
                            scoring_func="sigmoid", selection_bias=True,
                            normalize_eps=1e-6)
        layer.initialize()
        part = _share(p, rank * held, (rank + 1) * held)
        for name, param in layer.collect_params().items():
            if name != "running_load":
                param.set_data(NDArray(part["l.feed_forward." + name]))
        with autograd.record():
            out = layer(NDArray(x)).asnumpy()
        # the share as the configuration cuts it: the reference on the
        # held experts alone
        want = ref.routed(dict(CUT, num_experts=held, ep_rank=rank), part,
                          "l.", x, "float32")
        onp.testing.assert_allclose(out, want, atol=3e-5)
        parts = parts + out
        rows += ti.flush_moe_load()["DroplessMoE"][0]
    onp.testing.assert_allclose(parts, whole, atol=1e-4)
    assert rows == x.shape[0] * 2           # every assignment, once


def test_normalize_eps_is_added_to_the_chosen_gates_sum():
    logits = jnp.asarray(onp.random.RandomState(0).randn(6, 8).astype("f"))
    s = onp.asarray(jax.nn.sigmoid(logits), onp.float64)
    top = onp.sort(s, axis=1)[:, ::-1][:, :3]
    g, e = moe.route_top_k(logits, 3, scoring="sigmoid", normalize_eps=1e-6)
    onp.testing.assert_allclose(
        g, top / (top.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    onp.testing.assert_array_equal(e, onp.argsort(-s, axis=1)[:, :3])
    # an epsilon a gate can feel: the gates sum to total / (total + eps)
    g1, _ = moe.route_top_k(logits, 3, scoring="sigmoid", normalize_eps=0.5)
    onp.testing.assert_allclose(g1.sum(1), top.sum(1) / (top.sum(1) + 0.5),
                                rtol=1e-6)
    # the default is what it was: 1e-20 for a sigmoid (scores that all
    # vanish give zeros, not NaN), nothing for a softmax
    g0, _ = moe.route_top_k(logits, 3, scoring="sigmoid")
    onp.testing.assert_array_equal(
        g0, moe.route_top_k(logits, 3, scoring="sigmoid",
                            normalize_eps=1e-20)[0])
    assert onp.isfinite(moe.route_top_k(
        jnp.full((2, 8), -200.0), 3, scoring="sigmoid")[0]).all()
    gs, _ = moe.route_top_k(logits, 3)
    onp.testing.assert_array_equal(
        gs, moe.route_top_k(logits, 3, normalize_eps=0.0)[0])
    onp.testing.assert_allclose(gs.sum(1), 1.0, rtol=1e-6)
    # the layer hands it down
    x, p = _layer_weights(5)
    args = [p["l.feed_forward." + n] for n in
            ("router", "gate_proj", "up_proj", "down_proj")]
    a, _ = moe.dropless_moe(x, *args, top_k=2, scoring="sigmoid")
    b, _ = moe.dropless_moe(x, *args, top_k=2, scoring="sigmoid",
                            normalize_eps=0.5)
    assert float(jnp.abs(a - b).max()) > 1e-3


# -- (d) the tied head ---------------------------------------------------------

def test_a_tied_embedding_takes_the_sum_of_its_two_uses_gradients(bench,
                                                                  toy):
    ref, wmod, _ = bench
    cfg, weights, batch = toy
    _, tied = _loss_and_grads(_net(bench, cfg, weights), batch)
    # an untied copy on the same numbers: the head a matrix of its own
    loose = dict(weights, **{"lm_head.weight":
                             weights["model.embed_tokens.weight"]})
    per, untied = _loss_and_grads(
        _net(bench, dict(cfg, tie_word_embeddings=False), loose), batch)
    embed, head = (untied["model.embed_tokens.weight"],
                   untied["lm_head.weight"])
    assert float(jnp.abs(embed).max()) > 0 and float(jnp.abs(head).max()) > 0
    _close(tied["model.embed_tokens.weight"], embed + head, atol=1e-6)
    for name in tied:
        if name != "model.embed_tokens.weight":
            _close(tied[name], untied[name], atol=1e-6, msg=name)
    # and the untied model is the reference's untied model
    ref_per, ref_grads = _ref_loss_and_grads(
        ref, dict(cfg, tie_word_embeddings=False), loose, batch)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    _close(head, ref_grads["lm_head.weight"])


# -- (e) the whole step, its gauges and its scopes ---------------------------

def test_train_step_takes_it_whole_with_gauges_and_scopes(bench, toy):
    from mxnet_tpu.diagnostics import introspect

    cfg, weights, batch = toy
    introspect.reset()
    ti.short_conv_sites.set(0)
    ti.decoder_layers.clear()
    net = _net(bench, cfg, weights, remat=True, dtype="bfloat16")
    kinds = {n: str(p.data().dtype) for n, p in net.collect_params().items()}
    for name, kind in kinds.items():
        keeps = any(k in name for k in ("gamma", "router", "running_load",
                                        "conv_taps"))
        assert kind == ("float32" if keeps else "bfloat16"), name
    assert kinds["model.layers.1.feed_forward.router_bias"] == "float32"
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    tokens = NDArray(batch[0])
    bias = net.model.layers[1].feed_forward.router_bias.data().asnumpy()
    taps = net.model.layers[0].conv.conv_taps.data().asnumpy()
    losses = [float(step(tokens).asnumpy().mean()) for _ in range(4)]
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1
    assert losses[-1] < losses[0]
    # the bias is held fixed through the steps, the taps are trained
    onp.testing.assert_array_equal(
        bias, net.model.layers[1].feed_forward.router_bias.data().asnumpy())
    assert not onp.array_equal(
        taps, net.model.layers[0].conv.conv_taps.data().asnumpy())
    # the gauges of the traced stack
    assert ti.short_conv_sites.value == 3
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("conv", "dense"): 1, ("attention", "moe"): 1, ("conv", "moe"): 2}
    load = ti.flush_moe_load()
    assert sorted(load) == [f"model.layers.{i}.feed_forward"
                            for i in (1, 2, 3)]
    scopes = set()
    for (block, _), entry in introspect.compile_registry().items():
        if block == "whole_step":
            scopes.update(entry["op_scopes"].values())
    text = "\n".join(scopes)
    for name in ("/short_conv/short_conv.mix/", "/short_conv/Dense_in_proj",
                 "/short_conv/Dense_out_proj", "/attention/",
                 "/GroupedQueryAttention_self_attn/", "/moe.router/",
                 "/moe.experts/", "/lm_head/", "Lfm2MoeDecoderLayer_0",
                 "GatedMLP_feed_forward", "/optimizer/"):
        assert name in text, name
    # the mix runs forward (here the replay: the CPU's compiler folds the
    # first forward into a neighbour's fusion) and back under its scope,
    # and the attention layer holds none of it
    mix = [s for s in scopes if "/short_conv.mix/" in s]
    forward = [s for s in mix if "rematted_computation" in s
               or "transpose(" not in s]
    assert forward and len(forward) < len(mix)
    assert not [s for s in mix if "Lfm2MoeDecoderLayer_1" in s]
    ti.moe_bias_moved_share.clear()


def test_the_gauges_read_the_stack_traced_last(bench):
    """Two models in one process: the gauges are the last traced stack's,
    not a tally over both."""
    ti.decoder_layers.clear()
    for layer_types, want in (
            (["conv", "conv", "full_attention"], 2),
            (["full_attention", "conv"], 1)):
        net = lfm2_moe(64, 32, layer_types, 2, 1, 48, 16, 4, 2,
                       num_dense_layers=1)
        net.initialize()
        net(NDArray(jnp.zeros((1, 8), jnp.int32)))
        assert ti.short_conv_sites.value == want
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("attention", "dense"): 1, ("conv", "moe"): 1}
