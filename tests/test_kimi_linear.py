"""A ``kimi_linear`` decoder (Kimi-Linear's architecture) on the Gluon
path, at a small size on the CPU: the whole model against the benchmark's
plain reference — which runs the delta rule one position at a time —
(loss, every leaf's gradient, three Adam steps), the layers' kinds from the
two published lists, the delta layer by its formula, the latent layer that
carries no positions, the factory's refusals, the expert layer's
thirty-two shares, the published count of parameters, and the whole step
with its gauges and scopes."""
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
from mxnet_tpu.gluon.model_zoo.deepseek_v3 import MultiHeadLatentAttention
from mxnet_tpu.gluon.model_zoo.kimi_linear import (KimiDeltaAttention,
                                                   kimi_linear)
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
        from models import kimi_linear as model
        from reference import kimi_linear as ref
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


def _cfg(name="toy_kimi_linear", **changes):
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
    return onp.asarray(per), grads


# -- (a) the whole model against the reference -------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_plain_reference(bench, toy,
                                                           remat):
    cfg, weights, batch = toy
    _, grads = _matches_the_reference(bench, cfg, weights, batch, remat)
    assert not [n for n in grads if "router_bias" in n]
    assert "lm_head.weight" in grads                # untied: its own matrix
    # the delta layers' own leaves are trained: decay, bias, taps, beta
    for leaf in ("A_log", "dt_bias", "q_conv_taps", "k_conv_taps",
                 "v_conv_taps", "b_proj.weight", "o_norm.gamma"):
        names = [n for n in grads if n.endswith("self_attn." + leaf)]
        assert len(names) == 4, leaf
        for n in names:
            assert float(jnp.max(jnp.abs(grads[n]))) > 0.0, n


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


# -- (b) the layers' kinds, and each mixer by its formula ---------------------

LIN = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
       "num_heads": 2, "head_dim": 16, "short_conv_kernel_size": 4}
ARGS = dict(vocab_size=64, hidden_size=32, linear_attn_config=LIN,
            num_attention_heads=2, kv_lora_rank=16, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, intermediate_size=64,
            moe_intermediate_size=16, num_experts=8, num_experts_per_token=2,
            routed_scaling_factor=2.446)


@pytest.mark.parametrize("held,kinds", [
    (None, [("kda", "dense")] + [("kda", "moe")] * 2 + [("mla", "moe")]
     + [("kda", "moe")] * 3 + [("mla", "moe")]),
    ([1, 5, 6, 7, 8], [("kda", "dense")] + [("kda", "moe")] * 3
     + [("mla", "moe")]),
    ([4, 8], [("mla", "moe")] * 2),
], ids=["whole", "the-cells-cut", "latent-alone"])
def test_the_layers_kinds_come_from_the_two_one_indexed_lists(held, kinds):
    net = kimi_linear(**ARGS, num_hidden_layers=8 if held is None else None,
                      layers=held)
    assert [layer.kind for layer in net.model.layers] == kinds
    for layer in net.model.layers:
        mixer, ffn = layer.kind
        assert isinstance(layer.self_attn, KimiDeltaAttention
                          if mixer == "kda" else MultiHeadLatentAttention)
        assert isinstance(layer.mlp, GatedMLP if ffn == "dense"
                          else DroplessMoE)
        if mixer == "mla":
            assert layer.self_attn._rotary is False


def test_the_factory_takes_config_jsons_keys_and_refuses_what_is_not_written():
    net = kimi_linear(**ARGS, layers=[1, 5, 8], ep_size=2, ep_rank=1)
    kda = net.model.layers[0].self_attn
    assert (kda._heads, kda._hd, kda._eps) == (2, 16, 1e-5)
    assert kda.q_conv_taps.shape == (32, 4) and kda.A_log.shape == (2,)
    assert kda.f_a_proj.weight.shape == (16, 32)
    assert kda.f_b_proj.weight.shape == (32, 16)
    moe_ = net.model.layers[1].mlp
    assert (moe_._scoring, moe_._scale, moe_._top_k, moe_._first) == (
        "sigmoid", 2.446, 2, 4)
    assert moe_.router_bias is not None and moe_.shared is not None
    for key, value, match in [
            ("q_lora_rank", 64, "q_lora_rank"),
            ("num_expert_group", 2, "num_expert_group"),
            ("topk_group", 2, "topk_group"),
            ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
            ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"),
            ("tie_word_embeddings", True, "tie_word_embeddings"),
            ("mla_use_nope", False, "mla_use_nope"),
            ("moe_router_activation_func", "softmax", "activation")]:
        with pytest.raises(NotImplementedError, match=match):
            kimi_linear(**ARGS, layers=[1], **{key: value})
    with pytest.raises(ValueError, match="exactly one"):
        kimi_linear(**ARGS, layers=[1, 9])          # in neither list
    with pytest.raises(ValueError, match="exactly one"):
        kimi_linear(**dict(ARGS, linear_attn_config=dict(
            LIN, full_attn_layers=[1, 4, 8])), layers=[1])  # in both
    with pytest.raises(ValueError, match="num_hidden_layers"):
        kimi_linear(**ARGS, layers=[1, 5], num_hidden_layers=3)
    with pytest.raises(ValueError, match="dense layers"):
        kimi_linear(**ARGS, layers=[5, 1])
    with pytest.raises(ValueError, match="num_hidden_layers or layers"):
        kimi_linear(**ARGS)


def _mixer_weights(bench, toy, block, layer):
    _, weights, _ = toy
    block.initialize()
    for name, p in block.collect_params().items():
        p.set_data(NDArray(weights[f"model.layers.{layer}.self_attn." + name]))
    return block


def test_the_delta_layer_is_the_references_recurrence(bench, toy):
    """`KimiDeltaAttention` alone against the reference's layer, which
    walks the positions one by one; a later token never reaches an
    earlier output."""
    ref = bench[0]
    cfg, weights, _ = toy
    x = jnp.asarray(onp.random.RandomState(1).randn(2, 32, 32).astype("f"))
    block = _mixer_weights(bench, toy, KimiDeltaAttention(32, 2, 16), 1)
    got = block(NDArray(x)).asnumpy()
    want = ref._kda(cfg, weights, "model.layers.1.", x, "float32")
    _close(got, want, atol=2e-5)
    moved = block(NDArray(x.at[:, 20].add(1.0))).asnumpy()
    onp.testing.assert_array_equal(moved[:, :20], got[:, :20])
    assert onp.abs(moved[:, 20:] - got[:, 20:]).max() > 1e-4


def test_the_latent_layer_carries_no_positions(bench, toy):
    """``rotary=False`` against the reference's layer; moving every
    position changes a rotating layer and not it."""
    ref = bench[0]
    cfg, weights, _ = toy
    x = jnp.asarray(onp.random.RandomState(2).randn(1, 32, 32).astype("f"))
    make = lambda rotary: _mixer_weights(          # noqa: E731
        bench, toy, MultiHeadLatentAttention(
            32, 2, 16, 16, 8, 16, epsilon=1e-5, rotary=rotary), 4)
    block = make(False)
    pos = NDArray(jnp.arange(32, dtype=jnp.int32))
    later = NDArray(jnp.arange(32, dtype=jnp.int32) * 3 + 5)
    got = block(NDArray(x), pos).asnumpy()
    _close(got, ref._mla(cfg, weights, "model.layers.4.", x, "float32"),
           atol=2e-5)
    onp.testing.assert_array_equal(block(NDArray(x), later).asnumpy(), got)
    onp.testing.assert_array_equal(block(NDArray(x), None).asnumpy(), got)
    rotating = make(True)
    a, b = (rotating(NDArray(x), p).asnumpy() for p in (pos, later))
    assert onp.abs(a - b).max() > 1e-4
    assert onp.abs(a - got).max() > 1e-4


# -- (c) the expert layer's shares and the published count --------------------

def _layer_weights(seed=0, n=48, d=16, f=12, experts=32):
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


CUT = {"num_experts_per_token": 8, "moe_renormalize": True,
       "router_width": 32, "routed_scaling_factor": 2.446,
       "num_experts": 32, "ep_rank": 0}
ROUTED = ("mlp.gate_proj", "mlp.up_proj", "mlp.down_proj")


def test_the_32_shares_routed_parts_and_the_shared_expert_once_add_up(bench):
    """Thirty-two chips, one of thirty-two experts each (the cell's
    deployment: ``ep_size`` 32, top-8): what every share's layer gives,
    less the shared expert that every share computes alike, summed over
    the shares, plus the shared expert ONCE, is the uncut reference's
    layer."""
    ref = bench[0]
    x, p = _layer_weights(3)
    shared = ref._mlp(p, "l.mlp.shared.", x, "float32")
    whole = ref._routed(CUT, p, "l.", x, "float32") + shared
    parts, rows = 0.0, 0.0
    for rank in range(32):
        layer = DroplessMoE(16, 12, 32, 8, ep_size=32, ep_rank=rank,
                            scoring_func="sigmoid", selection_bias=True,
                            routed_scaling_factor=2.446, shared_units=12)
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
        want = ref._routed(dict(CUT, num_experts=1, ep_rank=rank), part,
                           "l.", x, "float32") + shared
        onp.testing.assert_allclose(out, want, atol=3e-5)
        parts = parts + (out - onp.asarray(shared))
        rows += ti.flush_moe_load()["DroplessMoE"][0]
    onp.testing.assert_allclose(parts + onp.asarray(shared), whole,
                                atol=2e-4)
    assert rows == x.shape[0] * 8           # every assignment, once


def test_the_parameters_add_up_to_the_issues_count(bench):
    """The cell's configuration at the published widths: 602,434,432
    parameters (the router's bias among them, the layers' counters not),
    as ISSUE 54 counts them layer by layer — by shapes, nothing
    allocated."""
    ref = bench[0]
    cfg = _cfg("kimi_linear_48b_a3b_ep32")
    sizes = {name: int(onp.prod(shape))
             for name, shape, *_ in ref.param_specs(cfg)
             if not name.endswith("running_load")}
    assert sum(sizes.values()) == 602_434_432

    def under(prefix):
        return sum(n for name, n in sizes.items() if name.startswith(prefix))

    assert [under(f"model.layers.{i}.self_attn.") for i in range(5)] == [
        39_514_272] * 4 + [29_114_880]
    assert under("model.layers.0.mlp.") == 63_700_992
    assert [under(f"model.layers.{i}.mlp.") for i in range(1, 5)] == [
        64_291_072] * 4
    assert sizes["model.embed_tokens.weight"] == sizes["lm_head.weight"] \
        == 47_185_920
    assert cfg["layers_held"] == [1, 5, 6, 7, 8]
    # every width as published
    lin = cfg["linear_attn_config"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["router_width"], cfg["num_experts_per_token"],
            cfg["num_shared_experts"], cfg["routed_scaling_factor"],
            cfg["rms_norm_eps"], lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (
        2304, 32, 512, 128, 64, 128, 9216, 1024, 256, 8, 1, 2.446, 1e-5,
        32, 128, 4)
    assert lin["kda_layers"] == [n for n in range(1, 27) if n % 4]
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
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
    monkeypatch.setattr(ti, "_mla_heads_sites", [0, 0])
    monkeypatch.setattr(ti, "_kda_scan_calls",
                        {"kernel": 0, "composition": 0})
    net = _net(bench, cfg, weights, remat=True, dtype="bfloat16")
    kinds = {n: str(p.data().dtype) for n, p in net.collect_params().items()}
    for name, kind in kinds.items():
        keeps = any(k in name for k in ("gamma", "router", "running_load",
                                        "conv_taps", "A_log", "dt_bias"))
        assert kind == ("float32" if keeps else "bfloat16"), name
    assert kinds["model.layers.0.self_attn.A_log"] == "float32"
    assert kinds["model.layers.0.self_attn.f_b_proj.weight"] == "bfloat16"
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    tokens = NDArray(batch[0])
    losses = [float(step(tokens).asnumpy().mean()) for _ in range(4)]
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1
    assert losses[-1] < losses[0]
    # the gauges of the traced stack: four delta layers and a latent one
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("kda", "dense"): 1, ("kda", "moe"): 3, ("mla", "moe"): 1}
    assert ti._kda_scan_calls["kernel"] == 0        # off a TPU
    assert ti._kda_scan_calls["composition"] >= 4 \
        and ti._kda_scan_calls["composition"] % 4 == 0
    assert ti.kda_scan_chunks.value == 2 * 2 * 1    # 32 tokens: one chunk
    assert ti.kda_scan_kept_bytes.value == 2 * 2 * 16 * 16 * 4
    assert ti.mla_heads_kernel_share.value == 0.0
    assert sorted(ti.flush_moe_load()) == [f"model.layers.{i}.mlp"
                                           for i in (1, 2, 3, 4)]
    scopes = set()
    for (block, _), entry in introspect.compile_registry().items():
        if block == "whole_step":
            scopes.update(entry["op_scopes"].values())
    text = "\n".join(scopes)
    for name in ("/kda/kda.proj/", "/kda/kda.conv/", "/kda/kda.gate/",
                 "/kda/kda.scan/", "/kda/kda.out/", "/short_conv.taps/",
                 "/mla/mla.q/", "/mla/mla.kv_latent/", "/mla/mla.heads/",
                 "/mla/attention/", "/mla/mla.out/", "/moe.router/",
                 "/moe.experts/", "/moe.shared/", "/lm_head/",
                 "KimiLinearDecoderLayer_0", "KimiLinearDecoderLayer_4",
                 "/optimizer/"):
        assert name in text, name
    assert "/mla.rope/" not in text                 # nothing rotates
    # a mixer's scopes lie in ITS kind's layers alone
    for s in scopes:
        if "/kda/" in s:
            assert "KimiLinearDecoderLayer_4" not in s, s
        if "/mla/" in s:
            assert "KimiLinearDecoderLayer_4" in s, s
    ti.moe_bias_moved_share.clear()
    ti.mla_heads_kernel_share.clear()
