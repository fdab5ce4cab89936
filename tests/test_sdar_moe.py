"""SDAR (model type ``sdar_moe``) on the Gluon path, at a small size on the
CPU: the model against the benchmark's plain reference, the flash kernel
with grouped heads under the block-diffusion mask, the expert layer that
holds a share, and the whole step with its counters and scopes."""
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import amp, gluon
from mxnet_tpu.gluon.contrib.nn import DroplessMoE
from mxnet_tpu.gluon.model_zoo.sdar import sdar_moe
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import pallas_attention as pa
from mxnet_tpu.parallel import moe

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's plain reference, toy configuration and weights."""
    sys.path.insert(0, BENCH)
    try:
        import weights as wmod
        from reference import sdar_moe as ref
        yield ref, wmod
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def toy(bench):
    ref, wmod = bench
    with open(os.path.join(BENCH, "configs", "toy_sdar_moe.json")) as f:
        cfg = json.load(f)
    weights = wmod.make_weights(ref.param_specs(cfg), 7, "float32")
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    return cfg, weights, batch


def _net(cfg, weights, remat=False, dtype="float32"):
    net = sdar_moe(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        expert_units=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"], ep_size=cfg["ep_size"],
        ep_rank=cfg["ep_rank"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"], remat=remat)
    net.initialize()
    for name, p in net.collect_params().items():
        p.set_data(NDArray(jnp.copy(weights[name])))
    if dtype != "float32":
        amp.convert_hybrid_block(net, target_dtype=dtype)
    return net


def _loss_and_grads(net, batch):
    fn, params = net.as_pure_function(training=True)
    train = {n: v for n, v in params.items() if "running_load" not in n}
    frozen = {n: v for n, v in params.items() if n not in train}

    def total(tr):
        per, _ = fn({**tr, **frozen}, jax.random.PRNGKey(0), *batch)
        return jnp.sum(per), per

    (_, per), grads = jax.value_and_grad(total, has_aux=True)(train)
    return per, grads


def _assert_matches_the_reference(ref, cfg, weights, batch, per, grads):
    """Per-sample losses and every gradient against the plain reference's."""
    train = {n: w for n, w in weights.items() if ref.trainable(n)}
    frozen = {n: w for n, w in weights.items() if n not in train}

    def total(tr):
        per = ref.per_sample_loss(cfg, {**tr, **frozen}, batch)
        return jnp.sum(per), per

    (_, ref_per), ref_grads = jax.value_and_grad(total, has_aux=True)(train)
    onp.testing.assert_allclose(per, ref_per, rtol=2e-5)
    assert set(grads) == set(ref_grads)
    for name, g in ref_grads.items():
        scale = float(jnp.max(jnp.abs(g))) or 1.0
        onp.testing.assert_allclose(
            onp.asarray(grads[name]) / scale, onp.asarray(g) / scale,
            atol=2e-4, err_msg=name)


# -- the model against the plain reference ----------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_loss_and_every_gradient_match_the_plain_reference(bench, toy,
                                                           remat):
    ref, _ = bench
    cfg, weights, batch = toy
    per, grads = _loss_and_grads(_net(cfg, weights, remat), batch)
    _assert_matches_the_reference(ref, cfg, weights, batch, per, grads)


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_with_the_preparation_kernels_it_matches_the_plain_reference(
        bench, toy, remat, monkeypatch):
    """Heads of 128, so that `npx.rms_norm_rotary` takes its kernels
    (interpreted here): the loss and every gradient, the norms' scales
    among them, against the reference, with and without the per-layer
    checkpoint that replays the forward kernel."""
    from mxnet_tpu.ops import pallas_qk_prep as qp
    from mxnet_tpu.telemetry import instruments as ti

    ref, wmod = bench
    cfg = dict(toy[0], head_dim=128)
    weights = wmod.make_weights(ref.param_specs(cfg), 7, "float32")
    batch = toy[2]
    monkeypatch.setattr(qp, "_kernel_mode", lambda: True)
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    per, grads = _loss_and_grads(_net(cfg, weights, remat), batch)
    # queries and keys of both layers, every site on the kernels
    assert ti._qk_prep_sites == [4, 4]
    assert ti.qk_prep_kernel_share.value == 1.0
    _assert_matches_the_reference(ref, cfg, weights, batch, per, grads)


def test_eager_hybrid_and_remat_give_one_loss(toy):
    cfg, weights, batch = toy
    nd = [NDArray(a) for a in batch]
    eager = _net(cfg, weights)(*nd).asnumpy()
    for remat in (False, True):
        net = _net(cfg, weights, remat)
        net.hybridize()
        onp.testing.assert_allclose(net(*nd).asnumpy(), eager, rtol=1e-6)


def test_the_noise_masks_what_u_and_t_say(toy):
    """A position reads the mask token iff u < t of its block, and an
    unmasked sequence has no loss."""
    cfg, weights, batch = toy
    x0, u, t = batch
    net = _net(cfg, weights)
    none = net(NDArray(x0), NDArray(jnp.ones_like(u)), NDArray(t)).asnumpy()
    assert onp.all(none == 0.0)
    some = net(NDArray(x0), NDArray(u), NDArray(t)).asnumpy()
    assert onp.all(some > 0.0) and some.shape == (x0.shape[0],)
    with pytest.raises(ValueError):
        net(NDArray(x0), NDArray(u), NDArray(t[:, :-1]))


def test_amp_keeps_norms_router_counters_and_noise_in_float32(toy):
    cfg, weights, batch = toy
    net = _net(cfg, weights, dtype="bfloat16")
    kinds = {n: str(p.data().dtype) for n, p in net.collect_params().items()}
    for name, kind in kinds.items():
        keeps = any(k in name for k in ("gamma", "router", "running_load"))
        assert kind == ("float32" if keeps else "bfloat16"), name
    # u and t are compared as they come in, not rounded to bfloat16
    x0, u, t = batch
    near = jnp.repeat(t, cfg["block_length"], axis=1) * (1 - 2.0 ** -12)
    full = _net(cfg, weights)
    a = net(NDArray(x0), NDArray(near), NDArray(t)).asnumpy()
    b = full(NDArray(x0), NDArray(near), NDArray(t)).asnumpy()
    assert onp.all(a > 0) and onp.allclose(a, b, rtol=0.05)


# -- the flash kernel: grouped heads, block-diffusion mask -------------------

def _qkv(half, heads=4, kv=2, width=16, seed=0):
    rs = onp.random.RandomState(seed)
    mk = lambda h: jnp.asarray(  # noqa: E731
        rs.randn(2, h, 2 * half, width).astype("f")) * 0.5
    return mk(heads), mk(kv), mk(kv), mk(heads)


@pytest.mark.parametrize("half,blen,tile", [
    (64, 4, 32),        # a tile multiple
    (52, 4, 32),        # padded: 104 positions on tiles of 32
    (20, 4, 16),        # padded, under three tiles
    (64, 8, 128),       # one tile holds everything
    (128, 4, 64),
])
def test_grouped_block_diffusion_kernel_matches_the_reference(half, blen,
                                                              tile):
    q, k, v, w = _qkv(half)
    mask = (blen, half)

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, interpret=True, block_q=tile,
                                  block_k=tile, block_diffusion=mask)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, block_diffusion=mask)

    onp.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        assert g.shape == r.shape
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_without_the_mask(causal):
    q, k, v, w = _qkv(48)

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, causal=causal, interpret=True,
                                  block_q=32, block_k=32)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, causal=causal)

    onp.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_the_masks_codes_say_what_the_equations_say(bench):
    """`_mask_codes` against the plain reference's statement of the mask,
    and a query always keeps its own block."""
    ref, _ = bench
    half, blen = 24, 4
    keep = pa._keep(*pa._mask_codes(False, (blen, half), 2 * half))
    pos = jnp.arange(2 * half)
    assert onp.array_equal(keep, ref.allowed(pos, pos, half, blen))
    assert keep[onp.arange(2 * half), onp.arange(2 * half)].all()
    assert not keep[half:, :half].any()                 # clean -> noisy
    with pytest.raises(ValueError):
        pa._mask_codes(True, (blen, half), 2 * half)
    with pytest.raises(ValueError):
        pa._mask_codes(False, (blen, half), 2 * half + 8)


@pytest.mark.parametrize("by_key", [False, True])
@pytest.mark.parametrize("half,blen,tile,live", [
    (4096, 4, 512, 80), (4096, 4, 128, 1088), (64, 4, 32, None)])
def test_the_tile_schedule_visits_every_live_tile_and_no_more(
        half, blen, tile, live, by_key):
    n = 2 * half // tile
    codes = pa._mask_codes(False, (blen, half), 2 * half)
    classes = pa._classes(codes, 2 * half, tile, tile)
    sched = pa._span_schedule(classes.T if by_key else classes, 1, by_key)
    qi, kj = sched >> 20, (sched >> 10) & 0x3FF
    keep = onp.asarray(pa._keep(*codes)).reshape(n, tile, n, tile)
    truth = keep.any(axis=(1, 3))
    visited = onp.zeros((n, n), bool)
    visited[qi, kj] = True
    assert onp.array_equal(visited, truth)
    assert live is None or len(sched) == live
    major = kj if by_key else qi
    first, last = (sched & 2) != 0, (sched & 1) != 0
    assert onp.array_equal(first[1:], major[1:] != major[:-1]) and first[0]
    assert onp.array_equal(last[:-1], major[1:] != major[:-1]) and last[-1]
    assert onp.all(onp.diff(major) >= 0)


def test_rotary_embedding_is_rotate_half_at_the_given_positions():
    rs = onp.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 3, 6, 8).astype("f"))
    pos = jnp.asarray([0, 1, 2, 0, 1, 2])
    out = ops_nn.rotary_embedding(x, pos, theta=1e6)
    onp.testing.assert_allclose(out[:, :, 0], x[:, :, 0], rtol=1e-6)
    onp.testing.assert_allclose(out[:, :, :3], ops_nn.rotary_embedding(
        x[:, :, :3], pos[:3], theta=1e6), rtol=1e-6)
    # one pair (i, i + 4) at position p turns by p * theta ** (-i / 4)
    i, p = 1, 2
    ang = p * 1e6 ** (-i / 4)
    want = x[0, 0, p, i] * onp.cos(ang) - x[0, 0, p, i + 4] * onp.sin(ang)
    onp.testing.assert_allclose(out[0, 0, p, i], want, rtol=1e-5)
    # norms are kept, and bfloat16 comes back as bfloat16
    onp.testing.assert_allclose(jnp.linalg.norm(out, axis=-1),
                                jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert ops_nn.rotary_embedding(x.astype(jnp.bfloat16), pos).dtype \
        == jnp.bfloat16


# -- the expert layer that holds a share ------------------------------------

def _layer_weights(seed=0, n=64, d=16, f=12, experts=16):
    rs = onp.random.RandomState(seed)
    mk = lambda *s: jnp.asarray(rs.randn(*s).astype("f")) * 0.3  # noqa: E731
    return mk(n, d) / 0.3, {
        "l.mlp.router": mk(experts, d) / 0.3,
        "l.mlp.gate_proj": mk(experts, d, f),
        "l.mlp.up_proj": mk(experts, d, f),
        "l.mlp.down_proj": mk(experts, f, d)}


def test_the_eight_shares_add_up_to_the_uncut_reference_layer(bench):
    ref, _ = bench
    x, p = _layer_weights()
    cut = {"num_experts_per_tok": 4, "norm_topk_prob": True,
           "router_width": 16}
    whole = ref._moe(dict(cut, num_experts=16, ep_rank=0), p, "l.", x,
                     "float32")
    parts, rows = 0.0, 0.0
    for rank in range(8):
        lo = 2 * rank
        out, load = moe.dropless_moe(
            x, p["l.mlp.router"], p["l.mlp.gate_proj"][lo:lo + 2],
            p["l.mlp.up_proj"][lo:lo + 2], p["l.mlp.down_proj"][lo:lo + 2],
            top_k=4, first_expert=lo)
        share = ref._moe(dict(cut, num_experts=2, ep_rank=rank),
                         {k: (v if "router" in k else v[lo:lo + 2])
                          for k, v in p.items()}, "l.", x, "float32")
        onp.testing.assert_allclose(out, share, atol=2e-5)
        parts, rows = parts + out, rows + float(load[0])
    onp.testing.assert_allclose(parts, whole, atol=5e-5)
    assert rows == x.shape[0] * 4           # every assignment, once


def _dense(first, top_k):
    """The layer's formula, expert by expert over every token."""
    def dense(x, r, wg, wu, wd):
        g, idx = moe.route_top_k(x @ r.T, top_k)
        out = 0.0
        for e in range(wg.shape[0]):
            ge = jnp.sum(jnp.where(idx == first + e, g, 0.0), -1)[:, None]
            out = out + ge * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        return out
    return dense


def test_a_shares_gradients_match_the_dense_formula():
    x, p = _layer_weights(1)
    args = (x, p["l.mlp.router"], p["l.mlp.gate_proj"][4:8],
            p["l.mlp.up_proj"][4:8], p["l.mlp.down_proj"][4:8])

    def dense(*a):
        return jnp.sum(_dense(4, 4)(*a) ** 2)

    def sparse(*a):
        return jnp.sum(moe.dropless_moe(*a, top_k=4, first_expert=4)[0] ** 2)

    for got, want in zip(jax.grad(sparse, range(5))(*args),
                         jax.grad(dense, range(5))(*args)):
        onp.testing.assert_allclose(got, want, rtol=2e-4,
                                    atol=1e-5 * float(jnp.abs(want).max()))


def test_routing_drops_nothing_when_every_token_picks_one_expert():
    x, p = _layer_weights(2)
    x = jnp.abs(x)
    router = jnp.zeros_like(p["l.mlp.router"]).at[5].set(100.0)
    out, load = moe.dropless_moe(
        x, router, p["l.mlp.gate_proj"][4:8], p["l.mlp.up_proj"][4:8],
        p["l.mlp.down_proj"][4:8], top_k=1, first_expert=4)
    assert load.tolist() == [x.shape[0], 4.0]   # all rows on 1 of 4 held
    want = (jax.nn.silu(x @ p["l.mlp.gate_proj"][5])
            * (x @ p["l.mlp.up_proj"][5])) @ p["l.mlp.down_proj"][5]
    onp.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(out).min(axis=1).max()) > 0    # no row left empty
    # and a chip that holds none of the chosen experts adds nothing
    out, load = moe.dropless_moe(
        x, router, p["l.mlp.gate_proj"][8:12], p["l.mlp.up_proj"][8:12],
        p["l.mlp.down_proj"][8:12], top_k=1, first_expert=8)
    assert load[0] == 0 and not jnp.any(out)


def _routed_layer(both, one, held=slice(4, 8), n=64, seed=3):
    """Weights under which the first ``both`` tokens pick two held experts,
    the next ``one`` tokens one held expert (the first) and one held
    elsewhere, and every other token none: 2 * both + one rows routed
    here, gates and values still the seed's."""
    x, p = _layer_weights(seed, n=n)
    router = p["l.mlp.router"] / 6                 # noise, std ~0.2
    x = x.at[:, :3].set(0.0).at[:, 0].set(1.0)
    x = x.at[:both, 1].set(1.0).at[both:both + one, 2].set(1.0)
    here = jnp.zeros(router.shape[0], bool).at[held].set(True)
    router = router.at[:, 0].set(jnp.where(here, 0.0, 3.0))
    router = router.at[:, 1].set(jnp.where(here, 6.0, 0.0))
    router = router.at[:, 2].set(0.0).at[held.start, 2].set(12.0)
    return (x, router, p["l.mlp.gate_proj"][held], p["l.mlp.up_proj"][held],
            p["l.mlp.down_proj"][held])


@pytest.mark.parametrize("both,one,held,rung", [
    (0, 0, slice(4, 8), 48),        # no row routed here: the first rung
    (24, 0, slice(4, 8), 48),       # exactly a rung
    (24, 1, slice(4, 8), 96),       # a rung + 1: the next one
    (45, 7, slice(4, 8), 128),      # past the last rung under N * k
    (64, 0, slice(4, 8), 128),      # every row routed here: N * k rows
    (24, 5, slice(0, 16), 128),     # ep_size=1: one rung, all 16 held
], ids=["none", "a-rung", "a-rung-plus-1", "over-half", "every-row",
        "ep_size-1"])
def test_every_rung_matches_the_dense_formula(both, one, held, rung):
    args = _routed_layer(both, one, held)
    first, count = held.start, held.stop - held.start
    dense = _dense(first, 2)

    def sparse(*a):
        return moe.dropless_moe(*a, top_k=2, first_expert=first)

    out, load = sparse(*args)
    rows = 2 * 64 if count == 16 else 2 * both + one
    assert load[0] == rows
    rungs = moe.buffer_rungs(2 * 64, 16 // count)
    assert rungs == ((48, 96, 128) if count == 4 else (128,))
    assert rungs[int(moe.rung_index(rungs, load[0]))] == rung
    onp.testing.assert_allclose(out, dense(*args), rtol=1e-5, atol=1e-6)
    w = _layer_weights(9)[0]        # a cotangent that is not the output's
    for got, want in zip(
            jax.grad(lambda *a: jnp.sum(sparse(*a)[0] * w), range(5))(*args),
            jax.grad(lambda *a: jnp.sum(dense(*a) * w), range(5))(*args)):
        onp.testing.assert_allclose(
            got, want, rtol=2e-4,
            atol=1e-5 * max(float(jnp.abs(want).max()), 1e-3))


def _arrays_of(jaxpr):
    """Every array an equation of ``jaxpr`` makes, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _arrays_of(sub)


def _conds(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _conds(sub)


def test_only_the_last_rung_holds_a_buffer_of_every_assignment():
    """Counts, not times: at a share of 8 the layer's forward and backward
    are one conditional each, and no branch but the last makes an array of
    N * k rows (the gates and their gradient, (N, k) and N * k numbers,
    are what a rung may hold of that length); a layer that holds every
    expert has no conditional at all."""
    x, p = _layer_weights(4)
    n, k = x.shape[0], 4
    rungs = moe.buffer_rungs(n * k, 8)
    assert rungs == (48, 96, 256)
    assert rungs[int(moe.rung_index(rungs, n * k))] == n * k
    assert int(moe.rung_index(rungs, 0)) == 0
    assert [int(moe.rung_index(rungs, r)) for r in (48, 49, 97)] == [0, 1, 2]
    assert moe.buffer_rungs(n * k, 1) == (n * k,)
    assert moe.buffer_rungs(n * k, 2) == (192, 256)
    assert len(moe.buffer_rungs(1 << 20, 128)) == 3     # never more

    def loss(held, *a):
        return jnp.sum(moe.dropless_moe(
            a[0], a[1], *(w[:held] for w in a[2:]), top_k=k)[0] ** 2)

    args = (x, p["l.mlp.router"], p["l.mlp.gate_proj"], p["l.mlp.up_proj"],
            p["l.mlp.down_proj"])
    share = jax.make_jaxpr(jax.grad(lambda *a: loss(2, *a), range(5)))(*args)
    conds = list(_conds(share.jaxpr))
    assert len(conds) == 2                          # forward, backward
    for cond in conds:
        *lower, top = cond.params["branches"]
        assert len(lower) == 2
        for rung, branch in zip(rungs, lower):
            shapes = {a.shape for a in _arrays_of(branch.jaxpr)
                      if getattr(a, "ndim", 0) > 1}
            assert shapes and not any(s[0] == n * k for s in shapes), shapes
            assert any(s[0] == rung for s in shapes)
        assert any(a.shape[:1] == (n * k,) and a.ndim > 1
                   for a in _arrays_of(top.jaxpr))
    whole = jax.make_jaxpr(jax.grad(lambda *a: loss(16, *a), range(5)))(*args)
    assert not list(_conds(whole.jaxpr))


def test_the_flush_sets_the_buffers_rows_from_the_fetched_count():
    from mxnet_tpu import autograd
    from mxnet_tpu.telemetry import instruments as ti

    ti.flush_moe_load()             # whatever an earlier test staged
    ti.moe_buffer_rows.clear()
    x, router, wg, wu, wd = _routed_layer(16, 1)
    layer = DroplessMoE(16, 12, 16, 2, ep_size=4, ep_rank=1)
    layer.initialize()
    for param, value in ((layer.router, router), (layer.gate_proj, wg),
                         (layer.up_proj, wu), (layer.down_proj, wd)):
        param.set_data(NDArray(value))
    with autograd.record():
        layer(NDArray(x))
    assert not ti.moe_buffer_rows.series()      # the step fetched nothing
    assert ti.flush_moe_load()["DroplessMoE"][0] == 33
    assert ti.moe_rows_routed_here.labels("DroplessMoE").value == 33
    assert ti.moe_buffer_rows.labels("DroplessMoE").value == 48
    # and the benchmark's reader divides the one by the other
    spec = importlib.util.spec_from_file_location("reader", os.path.join(
        BENCH, "layer_metrics", "moe_buffer_rows_over_routed.train.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.read({}, {}) == pytest.approx(48 / 33)
    ti.moe_buffer_rows.clear()
    assert reader.read({}, {}) is None          # a program without it


def test_the_block_is_told_its_share():
    layer = DroplessMoE(16, 12, 16, 4, ep_size=8, ep_rank=3)
    assert layer.gate_proj.shape == (2, 16, 12)
    assert layer.down_proj.shape == (2, 12, 16)
    assert layer.router.shape == (16, 16)
    assert "experts 6..7 of 16" in repr(layer)
    with pytest.raises(ValueError):
        DroplessMoE(16, 12, 16, 4, ep_size=3)
    with pytest.raises(ValueError):
        DroplessMoE(16, 12, 16, 4, ep_size=8, ep_rank=8)


# -- the whole step, its counters and its scopes ----------------------------

def test_train_step_takes_it_whole_with_counters_and_scopes(toy):
    from mxnet_tpu.diagnostics import introspect
    from mxnet_tpu.telemetry import instruments as ti

    cfg, weights, batch = toy
    introspect.reset()
    net = _net(cfg, weights, remat=True, dtype="bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3, "multi_precision": True})
    step = gluon.TrainStep(net, None, trainer, n_data=3)
    nd = [NDArray(a) for a in batch]
    before = net.lm_head.weight.data().asnumpy().astype("f")
    losses = [float(step(*nd).asnumpy().mean()) for _ in range(4)]
    assert step.last_path == "whole_step", step.ineligible_reason()
    assert step.jit_trace_count() == 1
    assert losses[-1] < losses[0]
    assert not onp.array_equal(
        before, net.lm_head.weight.data().asnumpy().astype("f"))
    # the counters: produced by the step, fetched only when asked
    assert not [k for k, _ in ti.moe_buffer_rows.series()
                if k[0].startswith("model.layers")]     # nothing fetched yet
    load = ti.flush_moe_load()
    layers = [f"model.layers.{i}.mlp"
              for i in range(cfg["num_hidden_layers"])]
    tokens = 2 * 2 * cfg["seq"] * cfg["num_experts_per_tok"]
    for layer in layers:
        rows, ratio = load[layer]
        assert 0 < rows <= tokens and 1.0 <= ratio <= cfg["num_experts"]
        assert ti.moe_rows_routed_here.labels(layer).value == rows
        assert ti.moe_expert_load_max_over_mean.labels(layer).value == ratio
        # the buffer's length that step took: the first rung to hold them
        rungs = moe.buffer_rungs(tokens, cfg["ep_size"])
        assert rungs == (tokens * 3 // 4, tokens)
        assert ti.moe_buffer_rows.labels(layer).value == min(
            r for r in rungs if r >= rows)
    assert ti.step_scalar_operands.value == 4
    # the scopes the compile registry resolves
    scopes = set()
    for (block, _), entry in introspect.compile_registry().items():
        if block == "whole_step":
            scopes.update(entry["op_scopes"].values())
    text = "\n".join(scopes)
    for name in ("/attention/", "/moe.router/", "/moe.dispatch/",
                 "/moe.experts/", "/moe.combine/", "/lm_head/",
                 "SDARDecoderLayer_1", "/optimizer/"):
        assert name in text, name
