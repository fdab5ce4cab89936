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

    def test_gradients(self):
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

    def test_ragged_length_causal_grads(self):
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


def test_flash_backward_kernels_match_reference_grads():
    """The block-streamed Pallas backward (dQ/dK/dV kernels + lse
    residual) must match autodiff through the reference math."""
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

    from mxnet_tpu.ops.pallas_attention import _flash_fwd

    rs = onp.random.RandomState(1)
    B, H, S, D = 1, 1, 32, 8
    q, k, v = (jnp.asarray(rs.randn(B, H, S, D).astype("f"))
               for _ in range(3))
    scale = D ** -0.5
    out, lse = _flash_fwd(q, k, v, False, scale, 16, 16, True)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1).reshape(-1, S)
    onp.testing.assert_allclose(onp.asarray(lse), onp.asarray(ref_lse),
                                rtol=1e-5, atol=1e-5)


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

    def test_dropout_grads_match_reference_autodiff(self):
        """The hand bwd kernels must equal jax autodiff of the identical
        reference function (same mask): exact gradient check, all three
        inputs."""
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


@pytest.mark.parametrize("grad,expect", [
    (False, ["flash_attention_fwd"]),
    (True, ["flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"]),
], ids=["flash_fwd", "flash_bwd"])
def test_pallas_calls_carry_stable_names(grad, expect):
    q = jnp.ones((1, 2, 128, 64), jnp.float32)

    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True).sum()

    fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
    names = _pallas_names(jax.make_jaxpr(fn)(q, q, q))
    assert sorted(set(names)) == sorted(expect)
