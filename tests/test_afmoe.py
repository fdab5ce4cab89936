"""An ``afmoe`` decoder (Trinity-Mini's architecture) on the Gluon path, at
a small size on the CPU: the whole model against the benchmark's plain
reference (loss, every leaf's gradient, three Adam steps), each of its
mechanisms off and on — the window, the global layer that carries no
positions, the gate on attention's output, the muP scale, the router's
normaliser —, the expert layer's sixteen shares, the published count of
parameters, and the whole step with its gauges and scopes."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon.contrib.nn import DroplessMoE, GatedMLP
from mxnet_tpu.gluon.model_zoo.afmoe import afmoe
from mxnet_tpu.gluon.model_zoo.decoder import GroupedQueryAttention
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.telemetry import instruments as ti

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plain reference, builder, weights and counts."""
    sys.path.insert(0, BENCH)
    try:
        import weights as wmod
        from models import afmoe as model
        from reference import afmoe as ref
        from reference import train_ref_large
        yield ref, wmod, model, train_ref_large
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


def _cfg(name="toy_afmoe", **changes):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return dict(json.load(f), **changes)


def _drawn(bench, cfg, seed=7):
    ref, wmod = bench[:2]
    weights = wmod.make_weights(ref.param_specs(cfg), seed, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), seed, 1)[0]
    return weights, batch


@pytest.fixture(scope="module")
def toy(bench):
    cfg = _cfg()
    return (cfg,) + _drawn(bench, cfg)


def _net(bench, cfg, weights, remat=False, dtype="float32"):
    return bench[2].build(mx, dict(cfg, remat=remat, dtype=dtype), weights,
                          mx.cpu())


def _close(got, want, atol=2e-4, msg=""):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    onp.testing.assert_allclose(onp.asarray(got) / scale,
                                onp.asarray(want) / scale, atol=atol,
                                err_msg=msg)


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


def _matches_the_reference(bench, cfg, weights, batch, remat=False):
    per, grads = _loss_and_grads(_net(bench, cfg, weights, remat), batch)
    ref_per, ref_grads = _ref_loss_and_grads(bench[0], cfg, weights, batch)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    assert set(grads) == set(ref_grads)
    for name, g in ref_grads.items():
        _close(grads[name], g, msg=name)
    return onp.asarray(per)


# -- (a) the whole model against the reference -------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_plain_reference(bench, toy,
                                                           remat):
    cfg, weights, batch = toy
    _matches_the_reference(bench, cfg, weights, batch, remat)
    grads = _loss_and_grads(_net(bench, cfg, weights, remat), batch)[1]
    assert not [n for n in grads if "router_bias" in n]
    assert "lm_head.weight" in grads                # untied: its own matrix
    assert len([n for n in grads if n.endswith("gate_proj.weight")
                and "self_attn" in n]) == 5         # every layer is gated


def test_three_adam_steps_follow_the_plain_reference(bench, toy):
    """Float32 through gluon.TrainStep, three batches: each step's loss
    and every leaf's change after the third against
    reference/train_ref_large.py (the arithmetic the cell's `correct`
    compares)."""
    ref, wmod, _, train_ref = bench
    cfg, weights, _ = toy
    cfg = dict(cfg, optimizer=dict(cfg["optimizer"], learning_rate=1e-3,
                                   multi_precision=False))
    batches = wmod.make_batches(ref.input_specs(cfg, 2), 11, 3)
    net = _net(bench, cfg, weights)
    trainer = gluon.Trainer(net.collect_params(), "adam", {
        k: v for k, v in cfg["optimizer"].items() if k != "name"})
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    losses = [float(step(NDArray(b[0])).asnumpy().mean()) for b in batches]
    assert step.last_path == "whole_step", step.ineligible_reason()
    # fresh copies: the reference's update donates its leaves
    want_losses, _, want_dw = train_ref.train_steps(
        ref, cfg, lambda: {n: jnp.copy(w) for n, w in weights.items()},
        batches, 3)
    onp.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    params = net.collect_params()
    assert set(want_dw) == {n for n in params if ref.trainable(n)}
    for name, want in want_dw.items():
        got = float(jnp.linalg.norm(
            (params[name].data()._data - weights[name]).ravel()))
        assert got == pytest.approx(want, rel=2e-3), name


# -- (b) each mechanism, off and on -------------------------------------------

@pytest.mark.parametrize("key,off,on", [
    ("sliding_window", 1000, 16),
    ("layer_types", ["sliding_attention"] * 5,
     ["sliding_attention"] * 4 + ["full_attention"]),
    ("mup_enabled", False, True),
    ("route_norm", False, True),
], ids=["window", "unrotated-global-layer", "mup-scale", "router-normaliser"])
def test_a_mechanism_off_and_on_follows_the_reference_and_matters(
        bench, key, off, on):
    """Both settings of a key against the reference, and the two losses
    apart: the mechanism is live at the toy size (a window over the
    sequence is the causal mask; a stack of sliding layers alone rotates
    every layer)."""
    base = _cfg()
    weights, batch = _drawn(bench, base, seed=13)
    losses = [_matches_the_reference(bench, dict(base, **{key: value}),
                                     weights, batch) for value in (off, on)]
    assert onp.abs(losses[0] - losses[1]).min() > 1e-5


def _attention_block(bench, toy, **kwargs):
    cfg, weights, _ = toy
    block = GroupedQueryAttention(
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"], **kwargs)
    block.initialize()
    for name, p in block.collect_params().items():
        p.set_data(NDArray(weights["model.layers.1.self_attn." + name]))
    return block


def test_the_gate_multiplies_the_heads_output_before_the_output_projection(
        bench, toy):
    """Against the reference's layer, and by the formula: with W_g = 0 the
    sigmoid is a half everywhere, so the gated block is half the ungated
    one; the ungated block holds no fifth projection."""
    ref = bench[0]
    cfg, weights, _ = toy
    x = jnp.asarray(onp.random.RandomState(1).randn(2, 48, 64).astype("f"))
    pos = NDArray(jnp.arange(48, dtype=jnp.int32))
    gated = _attention_block(bench, toy, output_gate=True, window=16,
                             scope="attention.window")
    got = gated(NDArray(x), pos, causal=True).asnumpy()
    want = ref.attention(cfg, weights, "model.layers.1.", x, jnp.arange(48),
                         True, "float32")
    _close(got, want, atol=2e-5)
    plain = _attention_block(bench, toy, window=16)
    assert plain.gate_proj is None
    assert "gate_proj.weight" not in plain.collect_params()
    ungated = plain(NDArray(x), pos, causal=True).asnumpy()
    assert onp.abs(got - ungated).max() > 1e-3
    gated.gate_proj.weight.set_data(NDArray(jnp.zeros((64, 64))))
    _close(gated(NDArray(x), pos, causal=True).asnumpy(), 0.5 * ungated,
           atol=2e-6)


def test_a_global_layer_carries_no_positions_and_sees_every_earlier_key(
        bench, toy):
    """``rotary=False`` without a window against the reference's global
    layer; moving every position changes a sliding layer and not it."""
    ref = bench[0]
    cfg, weights, _ = toy
    x = jnp.asarray(onp.random.RandomState(2).randn(1, 48, 64).astype("f"))
    block = _attention_block(bench, toy, output_gate=True, rotary=False,
                             scope="attention.global")
    pos = NDArray(jnp.arange(48, dtype=jnp.int32))
    got = block(NDArray(x), pos, causal=True).asnumpy()
    want = ref.attention(cfg, weights, "model.layers.1.", x, jnp.arange(48),
                         False, "float32")
    _close(got, want, atol=2e-5)
    later = NDArray(jnp.arange(48, dtype=jnp.int32) * 3 + 5)
    onp.testing.assert_array_equal(
        block(NDArray(x), later, causal=True).asnumpy(), got)
    sliding = _attention_block(bench, toy, output_gate=True, window=16)
    a, b = (sliding(NDArray(x), p, causal=True).asnumpy()
            for p in (pos, later))
    assert onp.abs(a - b).max() > 1e-3
    # the window is live too: the last query's output moves with key 0
    # in the global layer and not in the sliding one
    moved = NDArray(x.at[:, 0].add(1.0))
    assert onp.abs(block(moved, pos, causal=True).asnumpy()[:, -1]
                   - got[:, -1]).max() > 1e-6
    onp.testing.assert_array_equal(
        sliding(moved, pos, causal=True).asnumpy()[:, 16:], a[:, 16:])


def test_the_factory_takes_config_jsons_keys_and_refuses_what_is_not_written():
    types = ["sliding_attention", "sliding_attention", "full_attention",
             "sliding_attention"]
    args = (64, 64, types, 4, 2, 16, 96, 24, 8, 2, 16)
    net = afmoe(*args, route_scale=2.826)
    assert [layer.kind for layer in net.model.layers] == [
        ("window", "dense"), ("window", "dense"), ("global", "moe"),
        ("window", "moe")]
    window, full = (net.model.layers[i].self_attn for i in (3, 2))
    assert (window._window, window._rotary, window._scope) == (
        16, True, "attention.window")
    assert (full._window, full._rotary, full._scope) == (
        None, False, "attention.global")
    assert (window._heads, window._kv_heads, window._hd, window._theta,
            window._eps) == (4, 2, 16, 1e4, 1e-5)
    assert window.gate_proj is not None and full.gate_proj is not None
    assert isinstance(net.model.layers[0].mlp, GatedMLP)
    moe_ = net.model.layers[3].mlp
    assert isinstance(moe_, DroplessMoE)
    assert (moe_._normalize_eps, moe_._scoring, moe_._scale) == (
        1e-20, "sigmoid", 2.826)
    assert moe_.router_bias is not None and moe_.shared is not None
    assert net.model._embed_scale == 8.0
    assert afmoe(*args, mup_enabled=False).model._embed_scale is None
    for key in ("n_group", "topk_group", "num_expert_groups",
                "num_limited_groups"):
        with pytest.raises(NotImplementedError, match="grouped"):
            afmoe(*args, **{key: 2})
    with pytest.raises(NotImplementedError, match="rope_scaling"):
        afmoe(*args, rope_scaling={"type": "yarn"})
    with pytest.raises(NotImplementedError, match="score_func"):
        afmoe(*args, score_func="softmax")
    with pytest.raises(NotImplementedError, match="tie_word_embeddings"):
        afmoe(*args, tie_word_embeddings=True)
    with pytest.raises(ValueError, match="layer_types"):
        afmoe(64, 64, ["conv"], *args[3:])
    with pytest.raises(ValueError, match="num_hidden_layers"):
        afmoe(*args, num_hidden_layers=2)


# -- (c) the expert layer's shares and the published count --------------------

def _layer_weights(seed=0, n=48, d=16, f=12, experts=16):
    rs = onp.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype("f")) * 0.3  # noqa: E731
    p = {"l.mlp.router": mk(experts, d) / 0.3,
         "l.mlp.router_bias": mk(experts) * 0.5,
         "l.mlp.gate_proj": mk(experts, d, f),
         "l.mlp.up_proj": mk(experts, d, f),
         "l.mlp.down_proj": mk(experts, f, d)}
    for m, shape in (("gate", (f, d)), ("up", (f, d)), ("down", (d, f))):
        p[f"l.mlp.shared.{m}_proj.weight"] = mk(*shape)
    return mk(n, d) / 0.3, p


CUT = {"num_experts_per_tok": 4, "route_norm": True, "router_width": 16,
       "route_scale": 2.826, "num_experts": 16, "ep_rank": 0}
ROUTED = ("mlp.gate_proj", "mlp.up_proj", "mlp.down_proj")


def test_the_16_shares_routed_parts_and_the_shared_expert_once_add_up(bench):
    """Sixteen chips, one of sixteen experts each (the cell's deployment:
    ``ep_size`` 16): what every share's layer gives, less the shared
    expert that every share computes alike, summed over the shares, plus
    the shared expert ONCE, is the uncut reference's layer."""
    ref = bench[0]
    x, p = _layer_weights(3)
    shared = ref._mlp(p, "l.mlp.shared.", x, "float32")
    whole = ref.routed(CUT, p, "l.", x, "float32") + shared
    parts, rows = 0.0, 0.0
    for rank in range(16):
        layer = DroplessMoE(16, 12, 16, 4, ep_size=16, ep_rank=rank,
                            scoring_func="sigmoid", selection_bias=True,
                            routed_scaling_factor=2.826, normalize_eps=1e-20,
                            shared_units=12)
        layer.initialize()
        part = {k: (v[rank:rank + 1] if k.endswith(ROUTED) else v)
                for k, v in p.items()}
        for name, param in layer.collect_params().items():
            if name != "running_load":
                param.set_data(NDArray(part["l.mlp." + name]))
        with autograd.record():
            out = layer(NDArray(x)).asnumpy()
        # the share as the configuration cuts it: the reference on the
        # held expert alone, beside the whole shared expert
        want = ref.routed(dict(CUT, num_experts=1, ep_rank=rank), part,
                          "l.", x, "float32") + shared
        onp.testing.assert_allclose(out, want, atol=3e-5)
        parts = parts + (out - onp.asarray(shared))
        rows += ti.flush_moe_load()["DroplessMoE"][0]
    onp.testing.assert_allclose(parts + onp.asarray(shared), whole,
                                atol=2e-4)
    assert rows == x.shape[0] * 4           # every assignment, once


def test_the_parameters_add_up_to_the_issues_count(bench):
    """The cell's configuration at the published widths: 504,147,712
    parameters (the router's bias among them, the layers' counters not),
    as ISSUE 51 counts them layer by layer."""
    ref = bench[0]
    cfg = _cfg("trinity_mini_26b_a3b_ep16")
    sizes = {name: int(onp.prod(shape))
             for name, shape, *_ in ref.param_specs(cfg)
             if not name.endswith("running_load")}
    assert sum(sizes.values()) == 504_147_712

    def layer(i):
        return sum(n for name, n in sizes.items()
                   if name.startswith(f"model.layers.{i}."))

    assert layer(0) == 65_020_160
    assert [layer(i) for i in range(1, 5)] == [84_156_800] * 4
    attention = sum(n for name, n in sizes.items()
                    if name.startswith("model.layers.1.self_attn."))
    assert attention == 27_263_232
    assert sizes["model.embed_tokens.weight"] == sizes["lm_head.weight"] \
        == 51_249_152
    assert cfg["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    # every width as published
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["router_width"],
            cfg["num_experts_per_tok"], cfg["num_shared_experts"],
            cfg["route_scale"], cfg["rms_norm_eps"], cfg["rope_theta"]) == (
        2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 1, 2.826, 1e-5, 10000)
    # and the zoo block holds the reference's leaves, shape for shape
    toy = _cfg()
    weights = _drawn(bench, toy)[0]
    params = _net(bench, toy, weights).collect_params()
    assert {n: tuple(p.shape) for n, p in params.items()} == {
        n: tuple(shape) for n, shape, *_ in ref.param_specs(toy)}


# -- (d) the whole step, its gauges and its scopes ---------------------------

def test_train_step_takes_it_whole_with_gauges_and_scopes(bench, toy,
                                                          monkeypatch):
    from mxnet_tpu.diagnostics import introspect

    cfg, weights, batch = toy
    introspect.reset()
    ti.decoder_layers.clear()
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    net = _net(bench, cfg, weights, remat=True, dtype="bfloat16")
    kinds = {n: str(p.data().dtype) for n, p in net.collect_params().items()}
    for name, kind in kinds.items():
        keeps = any(k in name for k in ("gamma", "router", "running_load"))
        assert kind == ("float32" if keeps else "bfloat16"), name
    assert kinds["model.layers.1.mlp.router_bias"] == "float32"
    assert kinds["model.layers.1.self_attn.gate_proj.weight"] == "bfloat16"
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    tokens = NDArray(batch[0])
    bias = net.model.layers[1].mlp.router_bias.data().asnumpy()
    losses = [float(step(tokens).asnumpy().mean()) for _ in range(4)]
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1
    assert losses[-1] < losses[0]
    onp.testing.assert_array_equal(
        bias, net.model.layers[1].mlp.router_bias.data().asnumpy())
    # the gauges of the traced stack: five layers, ten q/k preparations
    # (off a TPU all on the composition)
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("window", "dense"): 1, ("window", "moe"): 3, ("global", "moe"): 1}
    assert ti._qk_prep_sites[1] % 10 == 0 and ti._qk_prep_sites[1] >= 10
    assert ti.qk_prep_kernel_share.value == 0.0
    assert sorted(ti.flush_moe_load()) == [f"model.layers.{i}.mlp"
                                           for i in (1, 2, 3, 4)]
    scopes = set()
    for (block, _), entry in introspect.compile_registry().items():
        if block == "whole_step":
            scopes.update(entry["op_scopes"].values())
    text = "\n".join(scopes)
    for name in ("/attention/attention.window/",
                 "/attention/attention.global/", "/attention.gate/",
                 "/GroupedQueryAttention_self_attn/", "/moe.router/",
                 "/moe.experts/", "/moe.shared/", "/lm_head/",
                 "AfmoeDecoderLayer_0", "AfmoeDecoderLayer_4",
                 "/optimizer/"):
        assert name in text, name
    # a layer's flash call lies under ITS kind's scope alone: the four
    # sliding layers hold no global call, the fifth no windowed one
    for s in scopes:
        if "/attention.global/" in s:
            assert "AfmoeDecoderLayer_4" in s, s
        if "/attention.window/" in s:
            assert "AfmoeDecoderLayer_4" not in s, s
    ti.moe_bias_moved_share.clear()
    ti.qk_prep_kernel_share.clear()
