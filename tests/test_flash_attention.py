"""Pallas flash-attention kernel: numerics vs the jnp oracle (kernel runs
in interpret mode on CPU — same code path the TPU compiles), gradients,
causal masking, and the BERT integration.

TPU design: ops/pallas_attention.py — VMEM-resident q blocks, streamed
k/v blocks, online softmax in scratch; per pallas_guide.md."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_attention import (attention_reference,
                                            flash_attention)


def _forget_plans():
    """Plans and their jitted kernels are memoised by signature: a test
    that changes what a plan is built from starts and ends without them."""
    from mxnet_tpu.ops import pallas_attention as pa

    pa._plan.cache_clear()
    pa._shared.cache_clear()


@pytest.fixture(params=["fused", "two_kernels"])
def backward_path(request, monkeypatch):
    """Both memory plans of the backward at one shape (ISSUE 44): the plan
    takes the one fused kernel where a head's keys, values and their
    float32 gradients fit the core's fast memory, and the dQ and dK/dV
    kernels where they do not — reached here by a core with none.  Every
    plan the test builds must have taken the path it names."""
    from mxnet_tpu.ops import pallas_attention as pa

    built, init = [], pa._Plan.__init__
    monkeypatch.setattr(
        pa._Plan, "__init__",
        lambda self, *a: (init(self, *a), built.append(self))[0])
    if request.param == "two_kernels":
        monkeypatch.setattr(pa, "_vmem_capacity", lambda: 0)
    _forget_plans()
    yield request.param
    _forget_plans()
    assert built and {plan.fused for plan in built} == {
        request.param == "fused"}


def _qkv(b=2, h=3, s=256, d=64, seed=0):
    rs = onp.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.rand(b, h, s, d).astype("f") - 0.5)  # noqa: E731
    return mk(), mk(), mk()


class TestFlashKernel:
    def test_matches_reference(self):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_causal(self):
        q, k, v = _qkv(s=128)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = attention_reference(q, k, v, causal=True)
        onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
        # last row attends to everything; first row only to itself
        first_ref = attention_reference(q[:, :, :1], k[:, :, :1],
                                        v[:, :, :1])
        onp.testing.assert_allclose(out[:, :, :1], first_ref, rtol=1e-4,
                                    atol=1e-5)

    def test_multiblock_streaming(self):
        # S spans several k blocks: online-softmax accumulation across
        # inner grid steps
        q, k, v = _qkv(s=512, d=32)
        out = flash_attention(q, k, v, block_q=128, block_k=128,
                              interpret=True)
        ref = attention_reference(q, k, v)
        onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_gradients(self, backward_path):
        q, k, v = _qkv(s=128, d=32)

        def loss_flash(q_, k_, v_):
            return (flash_attention(q_, k_, v_, interpret=True) ** 2).sum()

        def loss_ref(q_, k_, v_):
            return (attention_reference(q_, k_, v_) ** 2).sum()

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_bf16(self):
        q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(s=128, d=64))
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        assert out.dtype == jnp.bfloat16
        onp.testing.assert_allclose(out.astype("f"), ref.astype("f"),
                                    rtol=5e-2, atol=5e-2)

    def test_ragged_length_tile_padded(self):
        # non-multiple S is padded to a tile boundary; the kernel masks
        # the padded keys via its static valid_len
        q, k, v = _qkv(s=100, d=16)
        out = flash_attention(q, k, v, interpret=True)
        ref = attention_reference(q, k, v)
        onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    def test_ragged_length_causal_grads(self, backward_path):
        # padded keys must be invisible to the backward kernels too
        q, k, v = _qkv(s=52, d=16)

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True, interpret=True) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            onp.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


class TestBertIntegration:
    def test_bert_same_output_with_and_without_flash(self, monkeypatch):
        from mxnet_tpu.gluon.model_zoo.bert import bert_12_768_12

        mx.seed(0)
        net = bert_12_768_12(vocab_size=100, num_layers=2, units=32,
                             hidden_size=64, num_heads=2, dropout=0.0)
        net.initialize()
        tok = mx.np.array(onp.random.RandomState(0).randint(0, 100, (2, 16)))
        seg = mx.np.zeros((2, 16), dtype="int32")
        outs = {}
        for enabled in ("1", "0"):
            monkeypatch.setenv("MXTPU_FLASH_ATTENTION", enabled)
            out = net(tok, seg)
            seq = out[0] if isinstance(out, tuple) else out
            outs[enabled] = seq.asnumpy()
        assert outs["1"].shape == (2, 16, 32)
        # flash and reference paths agree numerically
        onp.testing.assert_allclose(outs["1"], outs["0"], rtol=1e-4,
                                    atol=1e-5)

    def test_attention_dropout_still_random_per_call(self, monkeypatch):
        """With attention-prob dropout active in training, the flash path
        applies dropout IN-KERNEL with a fresh seed per call — two
        training calls must still differ (regularization preserved)."""
        from mxnet_tpu import autograd
        from mxnet_tpu.gluon.model_zoo.bert import MultiHeadAttention

        monkeypatch.setenv("MXTPU_FLASH_ATTENTION", "1")
        mx.seed(0)
        att = MultiHeadAttention(32, 2, dropout=0.5)
        att.initialize()
        x = mx.np.array(onp.random.RandomState(1).rand(2, 16, 32)
                        .astype("f"))
        with autograd.record():
            o1 = att(x).asnumpy()
            o2 = att(x).asnumpy()
        # dropout active => two training calls differ (reference path ran)
        assert not onp.allclose(o1, o2)


def test_flash_backward_kernels_match_reference_grads(backward_path):
    """The block-streamed Pallas backward (the fused kernel, or the dQ and
    dK/dV kernels, + lse residual) must match autodiff through the
    reference math."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_attention as pa

    rs = onp.random.RandomState(0)
    B, H, S, D = 1, 2, 64, 16
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("f") * 0.5)
               for _ in range(3))
    for causal in (False, True):
        def f_flash(q, k, v, c=causal):
            out = pa.flash_attention(q, k, v, causal=c, interpret=True,
                                     block_q=32, block_k=32)
            out = getattr(out, "_data", out)
            return (out.astype(jnp.float32) ** 2).sum()

        def f_ref(q, k, v, c=causal):
            o = pa.attention_reference(q, k, v, causal=c)
            return (o.astype(jnp.float32) ** 2).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            onp.testing.assert_allclose(onp.asarray(a), onp.asarray(b),
                                        rtol=2e-4, atol=2e-5)


def test_flash_forward_emits_lse():
    """Forward's saved lse equals logsumexp of the score rows (the
    backward residual contract)."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_attention import _flash_fwd, saved_lse

    rs = onp.random.RandomState(1)
    B, H, S, D = 1, 1, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("f"))
               for _ in range(3))
    scale = D ** -0.5
    out, lse = _flash_fwd(q, k, v, False, scale, 16, 16, True)
    assert lse.shape == (1, 2, 1, 16)       # a row a q tile, no column
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    onp.testing.assert_allclose(onp.asarray(saved_lse(lse, q.shape)),
                                onp.asarray(ref_lse), rtol=1e-5, atol=1e-5)


class TestFlashDropout:
    """In-kernel attention-prob dropout: the counter-hash keep mask
    (_dropout_keep) regenerates identically in the fwd kernel, both bwd
    kernels, and the jnp reference path — so kernel vs reference is an
    EXACT comparison, not a statistical one."""

    def _qkv(self, S=256, D=64):
        import jax.numpy as jnp

        rs = onp.random.RandomState(0)
        q = jnp.asarray(rs.randn(2, 3, S, D).astype("f")) * 0.3
        k = jnp.asarray(rs.randn(2, 3, S, D).astype("f")) * 0.3
        v = jnp.asarray(rs.randn(2, 3, S, D).astype("f"))
        return q, k, v

    def test_kernel_matches_reference_same_seed(self):
        import jax.numpy as jnp

        from mxnet_tpu.ops import pallas_attention as fa

        q, k, v = self._qkv()
        o_k = fa.flash_attention(q, k, v, interpret=True, dropout_p=0.1,
                                 dropout_seed=1234)
        o_r = fa.attention_reference(q, k, v, dropout_p=0.1,
                                     dropout_seed=1234)
        onp.testing.assert_allclose(onp.asarray(o_k), onp.asarray(o_r),
                                    rtol=1e-5, atol=2e-5)
        # and it actually regularizes (differs from the p=0 output)
        o_p0 = fa.attention_reference(q, k, v)
        assert float(jnp.abs(o_k - o_p0).max()) > 1e-3

    def test_causal_dropout(self):
        from mxnet_tpu.ops import pallas_attention as fa

        q, k, v = self._qkv()
        o_k = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                 dropout_p=0.2, dropout_seed=7)
        o_r = fa.attention_reference(q, k, v, causal=True, dropout_p=0.2,
                                     dropout_seed=7)
        onp.testing.assert_allclose(onp.asarray(o_k), onp.asarray(o_r),
                                    rtol=1e-5, atol=2e-5)

    def test_ragged_dropout(self):
        from mxnet_tpu.ops import pallas_attention as fa

        q, k, v = self._qkv(S=200)
        o_k = fa.flash_attention(q, k, v, interpret=True, dropout_p=0.1,
                                 dropout_seed=5)
        o_r = fa.attention_reference(q, k, v, dropout_p=0.1,
                                     dropout_seed=5)
        onp.testing.assert_allclose(onp.asarray(o_k), onp.asarray(o_r),
                                    rtol=1e-5, atol=2e-5)

    def test_dropout_grads_match_reference_autodiff(self, backward_path):
        """The hand bwd kernels (both memory plans) must equal jax
        autodiff of the identical reference function (same mask): exact
        gradient check, all three inputs."""
        import jax.numpy as jnp

        from mxnet_tpu.ops import pallas_attention as fa

        q, k, v = self._qkv()
        w = jnp.sin(jnp.arange(q.shape[-1]))

        def f_kernel(q, k, v):
            return (fa.flash_attention(q, k, v, interpret=True,
                                       dropout_p=0.15, dropout_seed=99)
                    * w).sum()

        def f_ref(q, k, v):
            return (fa.attention_reference(q, k, v, dropout_p=0.15,
                                           dropout_seed=99) * w).sum()

        g1 = jax.grad(f_kernel, (0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, (0, 1, 2))(q, k, v)
        for u, w2 in zip(g1, g2):
            onp.testing.assert_allclose(onp.asarray(u), onp.asarray(w2),
                                        rtol=1e-3, atol=1e-5)

    def test_keep_rate_statistics(self):
        """The hash mask drops ~p of the elements."""
        import jax.numpy as jnp

        from mxnet_tpu.ops.pallas_attention import _dropout_keep

        q_pos = jnp.arange(512, dtype=jnp.int32).reshape(-1, 1)
        k_pos = jnp.arange(512, dtype=jnp.int32).reshape(1, -1)
        for p in (0.1, 0.5):
            keep = _dropout_keep(42, 3, q_pos, k_pos, p)
            rate = float(jnp.mean(keep.astype(jnp.float32)))
            assert abs(rate - (1.0 - p)) < 0.01, (p, rate)

    def test_seed_requirement(self):
        import pytest

        from mxnet_tpu.ops import pallas_attention as fa

        q, k, v = self._qkv(S=32, D=8)
        with pytest.raises(ValueError):
            fa.flash_attention(q, k, v, dropout_p=0.1)


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


@pytest.mark.parametrize("grad,vmem,expect", [
    (False, None, ["flash_attention_fwd"]),
    (True, None, ["flash_attention_fwd", "flash_attention_bwd"]),
    (True, 0, ["flash_attention_fwd", "flash_attention_bwd_dq",
               "flash_attention_bwd_dkv"]),
], ids=["flash_fwd", "flash_bwd", "flash_bwd_two_kernels"])
def test_pallas_calls_carry_stable_names(grad, vmem, expect, monkeypatch):
    """One backward kernel where the plan fuses, two on a core whose fast
    memory does not hold a head's keys, values and gradients."""
    from mxnet_tpu.ops import pallas_attention as pa

    if vmem is not None:
        monkeypatch.setattr(pa, "_vmem_capacity", lambda: vmem)
    _forget_plans()
    q = jnp.ones((1, 2, 128, 64), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
    names = _pallas_names(jax.make_jaxpr(fn)(q, q, q))
    _forget_plans()
    assert sorted(set(names)) == sorted(expect)


# -- the span schedule: classes, steps, the tile the op chooses (ISSUE 40) ----

from mxnet_tpu.ops import pallas_attention as pa  # noqa: E402

# (name, _mask_codes arguments after the length, length)
_MASKS = {
    "causal": ((True, None), 256),
    "block_diffusion": ((False, (4, 128)), 256),
    "wide_blocks": ((False, (64, 128)), 256),     # a block holds sub-tiles
    "padded": ((False, None, 200), 256),
    "causal_padded": ((True, None, 200), 256),
    "unmasked": ((False, None), 256),
    # a causal band of W keys (ISSUE 51): under, equal to and over the
    # sub-tiles the cases below cut it into, and beside padded keys
    "window_24": ((False, None, None, 24), 256),
    "window_64": ((True, None, None, 64), 256),
    "window_100": ((False, None, None, 100), 256),
    "window_padded": ((True, None, 200, 48), 256),
}


def _codes(mask):
    (causal, blockdiff, *valid), s_len = _MASKS[mask]
    return pa._mask_codes(causal, blockdiff, s_len, *valid), s_len


@pytest.mark.parametrize("by_key", [False, True], ids=["q_major", "k_major"])
@pytest.mark.parametrize("tile,sub,span", [
    (64, 64, 2), (128, 32, 4), (32, 64, 1), (64, 16, 2), (256, 64, 4)])
@pytest.mark.parametrize("mask", sorted(_MASKS))
def test_span_schedule_classes_say_what_the_dense_mask_says(mask, tile, sub,
                                                            span, by_key):
    """Every sub-tile's class against the dense mask (dead <=> no pair
    kept, mask-free <=> all kept); every resident tile is written (a
    first and a last step, in order) and the grid holds exactly the spans
    with a live sub-tile."""
    codes, s_len = _codes(mask)
    dense = (onp.ones((s_len, s_len), bool) if codes is None
             else onp.asarray(pa._keep(*codes, use_eq=True)))
    unit_q, unit_k = (sub, tile) if by_key else (tile, sub)
    classes = pa._classes(codes, s_len, unit_q, unit_k)
    cells = dense.reshape(s_len // unit_q, unit_q, s_len // unit_k, unit_k)
    assert onp.array_equal(classes == pa._DEAD, ~cells.any(axis=(1, 3)))
    assert onp.array_equal(classes == pa._FREE, cells.all(axis=(1, 3)))

    by_tile = classes.T if by_key else classes
    words = pa._span_schedule(by_tile, span, by_key)
    qi, kj = words >> 20, (words >> 10) & 0x3FF
    major, minor = (kj, qi) if by_key else (qi, kj)
    assert onp.array_equal(onp.unique(major), onp.arange(s_len // tile))
    assert onp.all(onp.diff(major) >= 0)
    first, last = (words & 2) != 0, (words & 1) != 0
    edge = major[1:] != major[:-1]
    assert onp.array_equal(first[1:], edge) and first[0]
    assert onp.array_equal(last[:-1], edge) and last[-1]
    # the classes the kernel will read are the classes of those sub-tiles;
    # a tile the mask empties (keys that are all padding) keeps one masked
    by_tile = by_tile.copy()
    by_tile[~by_tile.any(1), 0] = pa._MASKED
    seen = onp.full_like(by_tile, pa._DEAD)
    got = pa._word_classes(words, span)
    for j in range(span):
        seen[major, minor * span + j] = got[:, j]
    assert onp.array_equal(seen, by_tile)
    assert (got != pa._DEAD).any(1).all()       # no step of dead sub-tiles


def test_a_tile_the_mask_empties_is_still_written():
    """Padding that empties whole k tiles: dK/dV visits them once, as a
    masked sub-tile (which adds zeros), and the forward never does."""
    codes = pa._mask_codes(False, None, 256, 100)
    by_key = pa._classes(codes, 256, 64, 64).T
    assert not by_key[2:].any()                 # k tiles 2, 3: all padding
    words = pa._span_schedule(by_key, 2, True)
    kj = (words >> 10) & 0x3FF
    assert sorted(kj) == [0, 0, 1, 1, 2, 3]
    classes = pa._word_classes(words, 2)
    assert classes[kj >= 2].tolist() == [[pa._MASKED, pa._DEAD]] * 2
    rows = pa._span_schedule(pa._classes(codes, 256, 64, 64), 2)
    assert sorted((rows >> 10) & 0x3FF) == [0] * 4


def test_classes_stay_safe_where_a_unit_straddles_the_noisy_clean_boundary():
    """Units that do not divide the half: a class may be coarser than the
    dense mask (masked where all is kept) but never promises too much."""
    codes = pa._mask_codes(False, (8, 24), 48)
    cells = onp.asarray(pa._keep(*codes)).reshape(6, 8, 3, 16).transpose(
        0, 2, 1, 3)
    classes = pa._classes(codes, 48, 8, 16)
    assert not cells[classes == pa._DEAD].any()
    assert cells[classes == pa._FREE].all()
    assert (classes == pa._MASKED)[cells.all(axis=(2, 3))].any()


@pytest.mark.parametrize("s_len,dk,dv,itemsize,padded,tile", [
    (8192, 128, 128, 2, 8192, 1024),    # the SDAR cell
    (8192, 192, 128, 2, 8192, 1024),    # the kanana-2 cell
    (384, 64, 64, 2, 384, 384),         # BERT at SQuAD's length: one tile
    (100, 16, 16, 4, 104, 104),         # whole sublanes below a lane width
    (1000, 64, 64, 2, 1024, 1024),      # whole lane widths above
    (1152, 64, 64, 2, 1152, 384),       # 9 x 128: the largest divisor
    (2176, 64, 64, 2, 2176, 128),       # 17 x 128
    (8192, 256, 256, 2, 8192, 1024),    # wider heads: still 1024, span 1
    (8192, 192, 128, 4, 8192, 512),     # float32 operands: the budget
    (8192, 640, 640, 4, 8192, 256),
    (8192, 2048, 2048, 4, 8192, 128),
])
def test_the_tile_is_read_off_the_shapes(s_len, dk, dv, itemsize, padded,
                                         tile):
    assert pa._tile_pad_len(s_len, 128) == padded
    assert pa._choose_tile(padded, dk, dv, itemsize) == tile
    span = pa._span_for(padded, tile, tile, dk, dv, itemsize)
    assert (padded // tile) % span == 0 and 1 <= span <= pa._SPAN
    assert pa._working_set(tile, tile, span, dk, dv,
                           itemsize) <= pa._VMEM_BUDGET < 16 * 2 ** 20


@pytest.mark.parametrize("blocks", [None, (32, 16), (64, 64)],
                         ids=["chosen", "q32_k16", "tile64"])
@pytest.mark.parametrize("case", [
    "causal", "block_diffusion", "padded", "unmasked", "latent_widths",
    "causal_dropout", "block_diffusion_dropout"])
def test_span_kernels_match_the_reference(case, blocks, backward_path):
    """Forward and all three gradients, interpreted, against the plain
    reference with grouped heads: at the tile the op chooses and at
    explicit ones (whose spans hold dead, mask-free and masked
    sub-tiles), through the fused backward and through the two kernels."""
    s_len = 120 if case == "padded" else 128
    dk, dv = (192, 128) if case == "latent_widths" else (16, 16)
    mask = {}
    if "causal" in case or case == "latent_widths":
        mask["causal"] = True
    if "block_diffusion" in case:
        mask["block_diffusion"] = (4, 64)
    if "dropout" in case:
        mask.update(dropout_p=0.2, dropout_seed=jnp.asarray([11], jnp.int32))
    rs = onp.random.RandomState(3)
    q, k, v, w = (jnp.asarray(rs.randn(1, h, s_len, d).astype("f")) * 0.5
                  for h, d in ((4, dk), (2, dk), (2, dv), (4, dv)))
    tiles = {} if blocks is None else dict(block_q=blocks[0],
                                           block_k=blocks[1])

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, interpret=True, **tiles, **mask)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, **mask)

    onp.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)


@pytest.mark.parametrize("mask", [{"causal": True},
                                  {"block_diffusion": (4, 1024)}],
                         ids=["causal", "block_diffusion"])
def test_the_chosen_tiles_span_a_long_sequence(mask, backward_path):
    """2048 positions: the op takes tiles of 1024 and spans of two, so a
    grid step walks dead, masked and mask-free sub-tiles of 1024 x 1024;
    bf16 operands as the cells have them.  The fused backward walks the
    forward's schedule (no k-major one is built); the two kernels theirs."""
    rs = onp.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(1, h, 2048, 16).astype("f") * 0.5,
                           jnp.bfloat16) for h in (2, 1, 1))
    plan = pa._plan_of(q, k, v, mask.get("causal", False), 1024, 1024, None,
                       mask.get("block_diffusion"))
    assert plan.rows[:3] == (1024, 1024, 2)
    assert (plan.cols is None if backward_path == "fused"
            else plan.cols[:3] == (1024, 1024, 2))
    assert set(plan.rows.classes) == ({pa._FREE, pa._MASKED} if "causal" in mask
                                 else {pa._MASKED})

    def loss(fn):
        return lambda *a: fn(*a).astype(jnp.float32).sum()

    kernel = lambda *a: pa.flash_attention(*a, interpret=True, **mask)  # noqa: E731
    plain = lambda *a: pa.attention_reference(*a, **mask)  # noqa: E731
    onp.testing.assert_allclose(kernel(q, k, v).astype("f"),
                                plain(q, k, v).astype("f"),
                                rtol=2e-2, atol=2e-2)
    got = jax.grad(loss(kernel), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        onp.testing.assert_allclose(g.astype("f"), r.astype("f"),
                                    rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("kernels", [
    {"flash_attention_fwd", "flash_attention_bwd"},
    {"flash_attention_fwd", "flash_attention_bwd_dq",
     "flash_attention_bwd_dkv"}], ids=["fused", "two_kernels"])
def test_the_gauge_reads_the_schedules_class_bits(kernels, monkeypatch):
    """`attention_maskfree_share{kernel}` is set when a plan is built: the
    cells' two masks at their tiles, and an unmasked call reads 1.  A
    process that has built only fused plans holds two series, the forward's
    and the one backward kernel's, both the q-major schedule's share."""
    from mxnet_tpu.telemetry import instruments as ti

    if len(kernels) == 3:
        monkeypatch.setattr(pa, "_vmem_capacity", lambda: 0)

    def shares(**mask):
        pa._plan.cache_clear()
        ti.attention_maskfree_share.clear()
        shape = (1, 1, 8192, 128)
        pa._plan(shape, shape, shape, "bfloat16", mask.get("causal", False),
                 1024, 1024, None, mask.get("block_diffusion"))
        return {k[0]: c.value for k, c in
                ti.attention_maskfree_share.series()}

    assert shares(causal=True) == dict.fromkeys(kernels, 28 / 36)
    assert shares(block_diffusion=(4, 4096)) == dict.fromkeys(kernels, 0.5)
    assert shares() == dict.fromkeys(kernels, 1.0)
    pa._plan.cache_clear()
    ti.attention_maskfree_share.clear()


# -- one backward kernel (ISSUE 44): the memory plan, the gauge, both paths ---

def _grads_by(vmem, fn, *operands):
    """Gradients of ``fn`` with the backward planned for a core of ``vmem``
    bytes of fast memory (None: the one the process sees)."""
    with pytest.MonkeyPatch.context() as patch:
        if vmem is not None:
            patch.setattr(pa, "_vmem_capacity", lambda: vmem)
        _forget_plans()
        try:
            return jax.grad(fn, (0, 1, 2))(*operands)
        finally:
            _forget_plans()


@pytest.mark.parametrize("case", ["causal_mha", "grouped_block_diffusion",
                                  "latent_widths_dropout"])
def test_fused_and_two_kernel_backward_agree(case):
    """The same float32 terms: dQ sums them in the same order on both
    paths (equal), dK and dV with the query heads of a group outermost
    (equal to float32 rounding)."""
    heads, dk, dv, mask = {
        "causal_mha": ((2, 2), 32, 32, {"causal": True}),
        "grouped_block_diffusion": ((8, 1), 16, 16,
                                    {"block_diffusion": (4, 64)}),
        "latent_widths_dropout": ((4, 2), 192, 128, {
            "causal": True, "dropout_p": 0.2,
            "dropout_seed": jnp.asarray([5], jnp.int32)}),
    }[case]
    rs = onp.random.RandomState(7)
    q, k, v, w = (jnp.asarray(rs.randn(1, h, 128, d).astype("f")) * 0.5
                  for h, d in ((heads[0], dk), (heads[1], dk),
                               (heads[1], dv), (heads[0], dv)))

    def loss(q, k, v):
        return (pa.flash_attention(q, k, v, interpret=True, block_q=32,
                                   block_k=32, **mask) * w).sum()

    fused = _grads_by(None, loss, q, k, v)
    two = _grads_by(0, loss, q, k, v)
    onp.testing.assert_array_equal(fused[0], two[0])
    for a, b, name in zip(fused[1:], two[1:], "kv"):
        onp.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                    err_msg="d" + name)


def test_a_key_sub_tile_no_query_visits_gets_zero_gradients(backward_path):
    """Padding that empties whole key sub-tiles (40 of 128 positions valid,
    sub-tiles of 32): the forward's schedule never visits them, so the
    fused backward leaves their dK and dV at the zeros it starts from (the
    dK/dV kernel visits each once as a masked sub-tile, which adds zeros);
    the valid keys' gradients are the reference's."""
    rs = onp.random.RandomState(9)
    q, k, v = (jnp.asarray(rs.randn(1, 2, 128, 16).astype("f")) * 0.5
               for _ in range(3))
    valid, seed = 40, jnp.zeros((1,), jnp.int32)

    def kernel(q, k, v):
        out = pa._flash(q, k, v, seed, False, 0.25, 32, 32, True, 0.0,
                        valid, None)
        return (out[:, :, :valid] ** 2).sum()

    def plain(q, k, v):
        return (pa.attention_reference(q, k, v, scale=0.25) ** 2).sum()

    got = jax.grad(kernel, (0, 1, 2))(q, k, v)
    want = jax.grad(plain, (0, 1, 2))(
        q[:, :, :valid], k[:, :, :valid], v[:, :, :valid])
    for g, r, name in zip(got, want, "qkv"):
        onp.testing.assert_allclose(g[:, :, :valid], r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)
        assert not onp.asarray(g[:, :, valid:]).any(), "d" + name


@pytest.mark.parametrize("s_len,dk,dv,itemsize,fused", [
    (8192, 128, 128, 2, True),      # the SDAR and the looped cell
    (8192, 192, 128, 2, True),      # the kanana-2 cell
    (16384, 192, 128, 2, True),
    (32768, 192, 128, 2, False),    # a head's gradients alone are 42 MB
    (32768, 128, 128, 2, False),
    (8192, 192, 128, 4, True),      # float32 operands: tiles of 512
    (384, 64, 64, 2, True),         # BERT at SQuAD's length
])
def test_the_backwards_memory_plan_is_read_off_the_shapes(s_len, dk, dv,
                                                          itemsize, fused):
    """Fused where the sequence-resident working set is at most the stated
    share of the core's fast memory (the v5e's 128 MiB off a TPU); the
    limit the kernel is given covers it and stays under the capacity."""
    tile = pa._choose_tile(s_len, dk, dv, itemsize)
    dtype = {2: "bfloat16", 4: "float32"}[itemsize]
    pa._plan.cache_clear()
    plan = pa._plan((1, 2, s_len, dk), (1, 2, s_len, dk), (1, 2, s_len, dv),
                    dtype, True, tile, tile, None, None)
    pa._plan.cache_clear()
    need = pa._working_set(tile, tile, 0, dk, dv, itemsize, resident=s_len)
    assert plan.fused == fused == (
        need <= pa._FUSED_VMEM_SHARE * pa._vmem_capacity())
    if fused:
        assert plan.cols is None
        assert need < plan.vmem_limit < pa._vmem_capacity() == 128 * 2 ** 20
    else:
        assert plan.vmem_limit is None and plan.cols[:2] == (tile, tile)


def test_the_fused_share_counts_plans(monkeypatch):
    """`attention_fused_backward_share`: 1 after a fused plan, falls with a
    two-kernel plan (a 32k-position signature) and rises again."""
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(ti, "_attention_plans", [0, 0])
    pa._plan.cache_clear()

    def plan(s_len):
        shape = (1, 1, s_len, 192)
        pa._plan(shape, shape, shape[:3] + (128,), "bfloat16", True, 1024,
                 1024, None, None)
        return ti.attention_fused_backward_share.value

    assert plan(8192) == 1.0
    assert plan(32768) == 0.5
    assert plan(4096) == pytest.approx(2 / 3)
    assert plan(4096) == pytest.approx(2 / 3)      # cached: planned once
    pa._plan.cache_clear()
    ti.attention_fused_backward_share.clear()
    ti.attention_maskfree_share.clear()


# -- the band, the kernels' fourth static mask (ISSUE 51) ---------------------

def test_the_bands_rule_is_two_thresholds_on_a_third_query_code():
    """keep[i, j] = (j <= i) & (j > i - W): the dense rule against the
    codes, whatever ``causal`` says; a window over the sequence is the
    causal mask; with block diffusion it raises."""
    i, j = onp.arange(48)[:, None], onp.arange(48)[None, :]
    for causal in (False, True):
        qc, kc = pa._mask_codes(causal, None, 48, window=10)
        assert qc.shape == (48, 3) and kc.shape == (2, 48)
        assert onp.array_equal(pa._keep(qc, kc), (j <= i) & (i - j < 10))
    assert onp.array_equal(pa._keep(*pa._mask_codes(False, None, 48,
                                                    window=48)), j <= i)
    assert pa._mask_codes(True, None, 48)[0].shape == (48, 2)
    q = jnp.zeros((1, 1, 48, 8))
    for fn in (pa.attention_reference,
               lambda *a, **kw: pa.flash_attention(*a, interpret=True, **kw)):
        with pytest.raises(ValueError, match="window and block_diffusion"):
            fn(q, q, q, window=8, block_diffusion=(4, 24))
    with pytest.raises(ValueError, match="window=0"):
        pa._mask_codes(False, None, 48, window=0)


def test_classes_are_exact_on_a_band_counted_by_hand():
    """256 positions in 64 x 64 sub-tiles under a window of 128: q tile t
    meets sub-tile t - 2 half dead (its first key is in no query's
    window), t - 1 whole, t on the diagonal — 6 masked, 3 mask-free, 7
    dead; the schedule visits the 9 and the gauge pair reads their pairs
    beside the band's W (W + 1) / 2 + (S - W) W."""
    from mxnet_tpu.telemetry import instruments as ti

    codes = pa._mask_codes(True, None, 256, window=128)
    classes = pa._classes(codes, 256, 64, 64)
    m, f, d = pa._MASKED, pa._FREE, pa._DEAD
    assert classes.tolist() == [[m, d, d, d], [f, m, d, d], [m, f, m, d],
                                [d, m, f, m]]
    assert [(classes == c).sum() for c in (m, f, d)] == [6, 3, 7]
    words = pa._span_schedule(classes, 2)
    # q tiles 0 and 1 take one span, 2 and 3 two: the dead sub-tile of a
    # visited span is skipped by its class, a dead span is not in the grid
    assert (words >> 20).tolist() == [0, 1, 2, 2, 3, 3]
    assert pa._word_classes(words, 2).tolist() == [
        [m, d], [f, m], [m, f], [m, d], [d, m], [f, m]]
    # by key: k tile t is met by q sub-tiles t, t + 1, t + 2
    assert onp.array_equal(pa._classes(codes, 256, 64, 64).T,
                           classes.T)
    _forget_plans()
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()
    shape = (1, 1, 256, 16)
    pa._plan(shape, shape, shape, "float32", True, 64, 64, None, None, 128)
    pa._plan(shape, shape, shape, "float32", True, 64, 64, None, None)
    read = lambda g: {k[0]: c.value for k, c in g.series()}  # noqa: E731
    # sub-tiles of 64 keys are not cut in quarters: computed whole
    assert read(ti.attention_pairs_visited) == {"window": 9 * 64 * 64,
                                                "causal": 10 * 64 * 64}
    assert read(ti.attention_pairs_kept) == {
        "window": 128 * 129 // 2 + 128 * 128, "causal": 256 * 257 // 2}
    _forget_plans()
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()


def test_the_cells_band_visits_three_sub_tiles_for_the_two_it_needs():
    """16,384 positions, a window of 2,048, the chosen tile of 1,024 (the
    Trinity-Mini cell's sliding layers): 45 of 256 sub-tiles visited, 15
    of them mask-free; the 30 masked ones are walked in quarters, of which
    one is dead (ISSUE 52), so 37.5 sub-tiles' pairs are computed: 1.25
    pairs a pair kept, where 1.5 are visited; the fused backward holds a
    head's keys and values at 128 / 128 bf16."""
    shape, kv = (1, 32, 16384, 128), (1, 4, 16384, 128)
    _forget_plans()
    plan = pa._plan(shape, kv, kv, "bfloat16", True, 1024, 1024, None, None,
                    2048)
    assert plan.mask == "window" and plan.rows[:3] == (1024, 1024, 2)
    classes = pa._word_classes(plan.rows.words, 2)
    assert (classes != pa._DEAD).sum() == 45
    assert (classes == pa._FREE).sum() == 15
    kept = pa._pairs_kept(plan.codes)
    assert kept == 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert 45 * 1024 ** 2 / kept == pytest.approx(1.5, abs=1e-3)
    assert pa._pairs_visited(plan.rows) == (15 + 30 * 3 / 4) * 1024 ** 2
    assert pa._pairs_visited(plan.rows) / kept == pytest.approx(1.25,
                                                                abs=1e-3)
    assert plan.fused and plan.cols is None
    full = pa._plan(shape, kv, kv, "bfloat16", True, 1024, 1024, None, None)
    assert (pa._word_classes(full.rows.words, 2) != pa._DEAD).sum() == 136
    assert full.fused and pa._pairs_visited(full.rows) == 132 * 1024 ** 2
    _forget_plans()


# ---- a masked sub-tile walked in quarters (ISSUE 52) ---------------------

@pytest.fixture
def small_rows(monkeypatch):
    """A body of 16 query rows by 16 keys, so that sub-tiles of 32 x 32 are
    walked as the cells' 1024 x 1024 are at `_ROWS` = 512: two chunks by
    two key halves.  Yields the plans built meanwhile."""
    built, init = [], pa._Plan.__init__
    monkeypatch.setattr(
        pa._Plan, "__init__",
        lambda self, *a: (init(self, *a), built.append(self))[0])
    monkeypatch.setattr(pa, "_ROWS", 16)
    _forget_plans()
    yield built
    _forget_plans()


# (causal, block_diffusion, valid_len, window) over 128 positions
_QUARTERED = {
    "causal": (True, None, None, None),
    "band_edge_and_diagonal": (False, None, None, 64),
    "band_inside_a_quarter": (True, None, None, 40),
    "block_diffusion": (False, (4, 64), None, None),
    "padding": (False, None, 100, None),
    "causal_padding": (True, None, 100, None),
}


@pytest.mark.parametrize("by_key", [False, True], ids=["q_major", "k_major"])
@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 64)],
                         ids=lambda b: "%dx%d" % b)
@pytest.mark.parametrize("mask", sorted(_QUARTERED))
def test_quarter_bits_say_what_the_dense_mask_says(mask, blocks, by_key,
                                                   small_rows, monkeypatch):
    """Every quarter of every masked sub-tile of a plan's schedule against
    the dense mask: its bit is set <=> a pair of it is kept, and the class
    `_classes` gives it at the body's size says dead <=> none kept, whole
    <=> all kept; sub-tiles of another class leave their bits 0; the pairs
    the side counts as computed are those of its whole sub-tiles and live
    quarters; and the walk is cut only where that skips something."""
    causal, blockdiff, valid, window = _QUARTERED[mask]
    monkeypatch.setattr(pa, "_vmem_capacity", lambda: 0)  # the by-key side
    shape = (1, 2, 128, 16)
    plan = pa._plan(shape, shape, shape, "float32", causal, *blocks, valid,
                    blockdiff, window)
    side = plan.cols if by_key else plan.rows
    rows, cols = pa._extent(side)
    dense = onp.asarray(pa._keep(*plan.codes, use_eq=True))
    fine = pa._classes(plan.codes, 128, 16, 16)
    cells = dense.reshape(8, 16, 8, 16)
    assert onp.array_equal(fine == pa._DEAD, ~cells.any(axis=(1, 3)))
    assert onp.array_equal(fine == pa._FREE, cells.all(axis=(1, 3)))
    classes = pa._word_classes(side.words, side.span)
    qi, kj = pa._tiles_of(side.words)

    def cell(step, j):
        r0, k0 = ((qi[step] * side.span + j) * rows, kj[step] * cols) \
            if by_key else (qi[step] * rows,
                            (kj[step] * side.span + j) * cols)
        return dense[r0:r0 + rows, k0:k0 + cols]

    masked = [cell(step, j).reshape(rows // 16, 16, -1, 16).any(axis=(1, 3))
              for step, j in zip(*onp.nonzero(classes == pa._MASKED))]
    if cols % 32 or all(live.all() for live in masked):
        # a sub-tile's keys are one body's, or no quarter is dead: not cut
        assert side.keys == cols and not side.whole_rows
        assert not side.quarters.any()
        assert pa._pairs_visited(side) == (classes != pa._DEAD).sum() \
            * rows * cols
        return
    assert (side.chunk, side.keys) == (16, 16) and side.by_key == by_key
    quarters = pa._quarters_of(side)
    assert quarters.shape[1:] == (side.span, rows // 16, cols // 16)
    assert not quarters[classes != pa._MASKED].any()
    assert onp.array_equal(quarters[classes == pa._MASKED],
                           onp.stack(masked))
    assert side.whole_rows == any(live.all(-1).any() for live in masked)
    computed = (classes == pa._FREE).sum() * rows * cols \
        + 256 * sum(int(live.sum()) for live in masked)
    assert pa._pairs_visited(side) == computed >= dense.sum()
    assert computed < (classes != pa._DEAD).sum() * rows * cols


_M, _F, _D = pa._MASKED, pa._FREE, pa._DEAD
_DIAGONAL = ((_M, _D), (_F, _M))
_BAND_EDGE = ((_M, _F), (_D, _M))
_NOISY_DIAGONAL = ((_M, _D), (_D, _M))


@pytest.mark.parametrize("case,s_len,mask,visited,computed,kinds", [
    ("causal_8k", 8192, {"causal": True}, 36, 34, {_DIAGONAL: 8}),
    ("causal_16k", 16384, {"causal": True}, 136, 132, {_DIAGONAL: 16}),
    ("band_2k_over_16k", 16384, {"window": 2048}, 45, 37.5,
     {_DIAGONAL: 16, _BAND_EDGE: 14}),
    ("block_diffusion_8k", 8192, {"block_diffusion": (4, 4096)}, 24, 20,
     {_NOISY_DIAGONAL: 4, _DIAGONAL: 8}),
], ids=lambda c: c if isinstance(c, str) else None)
def test_the_cells_masked_sub_tiles_hold_a_dead_quarter(case, s_len, mask,
                                                        visited, computed,
                                                        kinds):
    """ISSUE 52's four tables at the cells' shapes (the chosen tile of
    1024, a span of 2, `_ROWS` = 512): the sub-tiles a head's schedule
    visits, the quarters [rows 0-511 / 512-1023] x [keys 0-511 / 512-1023]
    of every masked one, and the sub-tiles' worth of pairs computed."""
    import collections

    _forget_plans()
    shape, kv = (1, 32, s_len, 128), (1, 4, s_len, 128)
    plan = pa._plan(shape, kv, kv, "bfloat16", mask.get("causal", False),
                    1024, 1024, None, mask.get("block_diffusion"),
                    mask.get("window"))
    side = plan.rows
    assert side[:3] == (1024, 1024, 2) and (side.chunk, side.keys) == (
        512, 512)
    classes = pa._word_classes(side.words, 2)
    assert (classes != pa._DEAD).sum() == visited
    # the second classification: `_classes` at the body's own size
    fine = pa._classes(plan.codes, s_len, 512, 512)
    qi, kj = pa._tiles_of(side.words)
    found = collections.Counter()
    for step, j in zip(*onp.nonzero(classes == pa._MASKED)):
        r, c = 2 * qi[step], 2 * (2 * kj[step] + j)
        quarters = fine[r:r + 2, c:c + 2]
        found[tuple(map(tuple, quarters.tolist()))] += 1
        # the kernels' word: a bit a live quarter
        assert onp.array_equal(pa._quarters_of(side)[step, j],
                               quarters != pa._DEAD)
    assert found == kinds
    # a row of live quarters takes one body on all the keys: every mask
    # here has such rows beside the cut ones
    assert side.whole_rows
    assert pa._pairs_visited(side) == computed * 1024 ** 2
    # the span schedule is the parent's: its share of whole sub-tiles too
    assert pa._maskfree_share(side) == pytest.approx(
        1 - sum(kinds.values()) / visited)
    _forget_plans()


@pytest.mark.parametrize("case,s_len,heads,mask,dropout", [
    ("causal", 128, (4, 2), {"causal": True}, 0.0),
    ("band_edge_and_diagonal", 128, (4, 2), {"window": 64}, 0.0),
    ("band_inside_a_quarter", 128, (4, 2), {"window": 40}, 0.0),
    ("block_diffusion", 128, (4, 2), {"block_diffusion": (4, 64)}, 0.0),
    ("padding", 104, (4, 2), {}, 0.0),
    ("causal_padding", 104, (4, 2), {"causal": True}, 0.0),
    ("causal_dropout", 128, (4, 2), {"causal": True}, 0.3),
    ("block_diffusion_dropout", 128, (2, 2), {"block_diffusion": (4, 64)},
     0.2),
    ("band_grouped_8_to_1", 128, (8, 1), {"window": 64}, 0.0),
], ids=lambda c: c if isinstance(c, str) else None)
def test_quartered_kernels_match_the_reference(case, s_len, heads, mask,
                                               dropout, small_rows,
                                               backward_path):
    """Forward and all three gradients through the quartered walk (tiles
    and sub-tiles of 32, a span of 2, quarters of 16 x 16), interpreted,
    against the plain reference: every mask, padded keys, dropout (the
    quarter's key offset enters the keep mask), grouped heads; through the
    fused backward and through the two kernels."""
    h, kv = heads
    rs = onp.random.RandomState(11)
    q, k, v, w = (jnp.asarray(rs.randn(1, n, s_len, 16).astype("f")) * 0.5
                  for n in (h, kv, kv, h))
    extra = dict(mask, dropout_p=dropout, dropout_seed=jnp.int32(
        77)) if dropout else mask

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, interpret=True, block_q=32,
                                  block_k=32, **extra)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, **extra)

    onp.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)
    assert small_rows
    for plan in small_rows:
        sides = [plan.rows] + ([] if plan.fused else [plan.cols])
        for side in sides:
            assert side[:3] == (32, 32, 2) and side.keys == 16
            live = pa._quarters_of(side)[
                pa._word_classes(side.words, 2) == pa._MASKED]
            assert not live.all() and live.any()
            # both walks of a chunk of rows: all its keys, a quarter alone
            assert side.whole_rows == bool(live.all(-1).any())


def test_an_unmasked_call_and_one_tile_keep_the_whole_walk(small_rows):
    """Nothing masked: no quarters, whatever the sizes.  A masked sub-tile
    whose keys are one body's (a sequence of one tile of 16) is computed
    whole, as before."""
    shape = (1, 2, 128, 16)
    free = pa._plan(shape, shape, shape, "float32", False, 32, 32, None,
                    None)
    assert free.rows.keys == 32 and not free.rows.whole_rows
    assert not free.rows.quarters.any()
    assert pa._pairs_visited(free.rows) == 128 * 128
    one = pa._plan((1, 2, 16, 16), (1, 2, 16, 16), (1, 2, 16, 16), "float32",
                   True, 16, 16, None, None)
    assert one.rows.keys == 16 and not one.rows.whole_rows
    assert pa._pairs_visited(one.rows) == 16 * 16
    q, k, v = _qkv(1, 2, 16, 16, seed=3)
    onp.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, interpret=True, block_q=16,
                        block_k=16),
        attention_reference(q, k, v, causal=True), rtol=1e-5, atol=2e-6)


@pytest.mark.parametrize("case,s_len,window,blocks,heads", [
    ("under_the_tile", 128, 8, (32, 16), (4, 2)),
    ("the_sub_tile", 128, 16, (32, 16), (4, 2)),
    ("over_the_tile", 128, 40, (32, 16), (4, 2)),
    ("not_a_divisor_padded", 120, 48, (32, 16), (4, 2)),
    ("chosen_tile", 128, 64, None, (4, 2)),
    ("grouped_8_to_1", 128, 24, (64, 32), (8, 1)),
], ids=lambda c: c if isinstance(c, str) else None)
def test_window_kernels_match_the_reference(case, s_len, window, blocks,
                                            heads, backward_path):
    """Forward and all three gradients under a band, interpreted, against
    the plain reference: W under, equal to and over the sub-tile, a
    sequence that W does not divide (padded to its tile), the tile the op
    chooses, eight query heads to a key-value head; through the fused
    backward and through the two kernels."""
    h, kv = heads
    rs = onp.random.RandomState(7)
    q, k, v, w = (jnp.asarray(rs.randn(1, n, s_len, 16).astype("f")) * 0.5
                  for n in (h, kv, kv, h))
    tiles = {} if blocks is None else dict(block_q=blocks[0],
                                           block_k=blocks[1])

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, interpret=True, window=window,
                                  **tiles)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, window=window)

    # the reference against the rule itself, once
    i, j = onp.arange(s_len)[:, None], onp.arange(s_len)[None, :]
    scores = onp.einsum("bhqd,bhkd->bhqk", q, onp.repeat(k, h // kv, 1)) / 4
    scores = onp.where((j <= i) & (i - j < window), scores, -onp.inf)
    p = onp.exp(scores - scores.max(-1, keepdims=True))
    dense = onp.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True),
                       onp.repeat(v, h // kv, 1))
    onp.testing.assert_allclose(plain(q, k, v), dense, rtol=1e-4, atol=1e-5)
    onp.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)


def test_a_band_over_the_sequence_is_the_causal_kernel(backward_path):
    """W >= S keeps what the causal mask keeps: the same classes, the
    same results."""
    q, k, v = _qkv(1, 2, 128, 16, seed=9)
    a = pa.flash_attention(q, k, v, interpret=True, window=128, block_q=32,
                           block_k=32)
    b = pa.flash_attention(q, k, v, interpret=True, causal=True, block_q=32,
                           block_k=32)
    onp.testing.assert_array_equal(a, b)
    codes = [pa._mask_codes(True, None, 128, window=w) for w in (128, None)]
    assert onp.array_equal(*(pa._classes(c, 128, 32, 32) for c in codes))


# ---- row statistics off HBM's padded columns (ISSUE 53) -------------------

def _reference_lse(q, k, scale, **mask):
    """logsumexp of the masked score rows, (B, H, S), by the plain rule."""
    s_len, group = q.shape[2], q.shape[1] // k.shape[1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q,
                        jnp.repeat(k, group, axis=1)) * scale
    codes = pa._mask_codes(mask.get("causal", False),
                           mask.get("block_diffusion"), s_len,
                           window=mask.get("window"))
    if codes is not None:
        scores = jnp.where(pa._keep(*codes), scores, -jnp.inf)
    return jax.scipy.special.logsumexp(scores, axis=-1)


@pytest.mark.parametrize("heads,dk,dv", [
    ((2, 2), 128, 128), ((4, 1), 192, 128), ((8, 1), 64, 64)],
    ids=["group1_128_128", "group4_192_128", "group8_64_64"])
@pytest.mark.parametrize("mask", [
    {"causal": True}, {"window": 192}, {"block_diffusion": (4, 256)}],
    ids=["causal", "window", "block_diffusion"])
def test_lane_dense_statistics_match_the_reference(mask, heads, dk, dv,
                                                   backward_path):
    """512 positions in tiles of 256 (two rows of a lane width a tile, two
    tiles a head: the stored form's every index moves), the cells' widths
    and head groups: the output and the three gradients against the plain
    reference, the saved lse against the reference's logsumexp by position,
    and no statistic wider than 32 bytes a row between the kernels — lse
    alone under either plan: delta is made inside the kernels."""
    from mxnet_tpu.telemetry import instruments as ti

    h, kv = heads
    rs = onp.random.RandomState(13)
    q, k, v, w = (jnp.asarray(rs.randn(1, n, 512, d).astype("f")) * 0.5
                  for n, d in ((h, dk), (kv, dk), (kv, dv), (h, dv)))
    scale = dk ** -0.5

    def kernel(q, k, v):
        return pa.flash_attention(q, k, v, interpret=True, block_q=256,
                                  block_k=256, **mask)

    def plain(q, k, v):
        return pa.attention_reference(q, k, v, **mask)

    onp.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                                rtol=1e-5, atol=2e-6)
    got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        onp.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5,
                                    err_msg="d" + name)
    _, lse = pa._flash_fwd(q, k, v, mask.get("causal", False), scale, 256,
                           256, True, block_diffusion=mask.get(
                               "block_diffusion"), window=mask.get("window"))
    assert lse.shape == (h, 2, 2, 128) and lse.dtype == jnp.float32
    onp.testing.assert_allclose(pa.saved_lse(lse, q.shape),
                                _reference_lse(q, k, scale, **mask),
                                rtol=1e-5, atol=1e-5)
    assert ti.attention_stat_bytes_per_row.value == 16 <= 32


@pytest.mark.parametrize("block_q,tail,want", [
    (1024, (8, 128), 4),           # the cells: one dense (8, 128) tile
    (512, (4, 128), 8),
    (128, (1, 128), 32),
    (32, (1, 32), 128),            # a toy tile: one row, lane-padded
    (104, (1, 104), 4096 / 104),
])
def test_the_stored_form_is_read_off_the_tile(block_q, tail, want,
                                              monkeypatch):
    """(heads, q tiles, rows, lanes): rows of a lane width where the tile
    is a multiple of one, else the tile as one row; never a trailing 1.
    The gauge is the form's bytes a row under the (8, 128) tiling, under
    either plan of the backward: 1024 where lse and delta were columns."""
    from mxnet_tpu.telemetry import instruments as ti

    s_len = 4 * block_q
    shape = (2, 3, s_len, 64)
    for capacity in (None, 0):
        with pytest.MonkeyPatch.context() as patch:
            if capacity is not None:
                patch.setattr(pa, "_vmem_capacity", lambda: capacity)
            _forget_plans()
            plan = pa._plan(shape, shape, shape, "bfloat16", True, block_q,
                            block_q, None, None)
            assert plan.stat_shape == (6, 4) + tail
            assert plan.fused == (capacity is None)
            assert plan.stat_bytes_per_row == pytest.approx(want)
            assert ti.attention_stat_bytes_per_row.value == pytest.approx(
                want)
            _forget_plans()


@pytest.mark.parametrize("unroll", [True, False],
                         ids=["unrolled", "rolled"])
@pytest.mark.parametrize("tile,lanes", [(256, 128), (32, 32)])
def test_rows_and_columns_turn_into_each_other_exactly(tile, lanes, unroll):
    """`_store_lse` and `_load_column`, the two turns of the kernels, in a
    kernel of their own (a running sum of 1, so lse is the running max):
    a relayout each way, so every float32 comes back bit for bit, -inf (a
    row the mask empties) included, and the stored form holds the
    column's values in order — the fused backward's unrolled turn and the
    dQ and dK/dV kernels' rolled one alike."""
    import jax.experimental.pallas as pl

    rs = onp.random.RandomState(2)
    col = jnp.asarray(rs.randn(tile, 1).astype("f") * 1e3)
    col = col.at[5, 0].set(-jnp.inf).at[tile - 3, 0].set(0.0)

    def kernel(m_ref, l_ref, stat_ref, back_ref):
        pa._store_lse(stat_ref, m_ref, l_ref)
        pa._load_column(stat_ref, 0, back_ref, unroll=unroll)

    stat, back = pl.pallas_call(
        kernel, interpret=True,
        out_shape=[jax.ShapeDtypeStruct((1, 1, tile // lanes, lanes),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((tile, 1), jnp.float32)])(
        col, jnp.ones_like(col))
    onp.testing.assert_array_equal(stat.reshape(-1), col[:, 0])
    onp.testing.assert_array_equal(back, col)


def test_the_backward_takes_no_delta_and_the_forward_gives_no_column():
    """The jaxpr of a gradient: the forward kernel's second result is the
    stored form, the fused backward takes ``out`` where it took two
    columns, and nothing between them touches the statistics — no
    reduction over dO . O, no reshape of lse."""
    _forget_plans()
    q = jnp.ones((1, 2, 256, 64), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True, causal=True,
                               block_q=128, block_k=128).sum()

    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, q, q)
    _forget_plans()
    calls = {}

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                name = (eqn.params.get("name")
                        or eqn.params["name_and_src_info"].name)
                calls[name] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    fwd, bwd = calls["flash_attention_fwd"], calls["flash_attention_bwd"]
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]  # noqa: E731
    assert shapes(fwd.outvars) == [(2, 2, 128, 64), (2, 2, 1, 128)]
    assert all(s[-1] != 1 for s in shapes(bwd.invars) if len(s) > 1)
    # q, k, v, dO and out (as many key-value heads as query heads: one
    # shape), and one statistic, the stored lse
    assert shapes(bwd.invars).count((2, 2, 128, 64)) == 5
    assert shapes(bwd.invars).count((2, 2, 1, 128)) == 1
