"""Custom-VJP normalization kernels vs autodiff of the naive composition.

batch_norm / layer_norm train with hand-written closed-form backwards
(single fused reduction passes on TPU — see ops/nn.py); these tests pin
their numerics to jax autodiff through the textbook formulation
(reference semantics: src/operator/nn/batch_norm.cc, layer_norm.cc).
"""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import nn as N


def _naive_bn(x, g, b, axis, eps=1e-5):
    ra = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    mean = jnp.mean(x, ra)
    var = jnp.var(x, ra)
    inv = jax.lax.rsqrt(var + eps)
    out = ((x - mean.reshape(bshape)) * inv.reshape(bshape)
           * g.reshape(bshape) + b.reshape(bshape))
    return out, mean, var


def _naive_ln(x, g, b, axis, eps=1e-5):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    if g is not None:
        out = out * g.reshape(bshape)
    if b is not None:
        out = out + b.reshape(bshape)
    return out


@pytest.mark.parametrize("axis", [1, -1])
def test_batch_norm_train_vjp_matches_autodiff(axis):
    rs = onp.random.RandomState(0)
    c = 5
    x = jnp.asarray(rs.randn(4, c, 6, c).astype("f"))
    g = jnp.asarray(rs.rand(c).astype("f") + 0.5)
    b = jnp.asarray(rs.randn(c).astype("f"))
    mm, mv = jnp.zeros(c), jnp.ones(c)

    def f_new(x, g, b):
        out, nm, nv = N.batch_norm(x, g, b, mm, mv, axis=axis, training=True)
        # weigh the moving-stat outputs so their cotangent paths are tested
        return (out * jnp.cos(out)).sum() + nm.sum() * 0.3 + nv.sum() * 0.7

    def f_old(x, g, b):
        out, mean, var = _naive_bn(x, g, b, axis)
        nm = mm * 0.9 + mean * 0.1
        nv = mv * 0.9 + var * 0.1
        return (out * jnp.cos(out)).sum() + nm.sum() * 0.3 + nv.sum() * 0.7

    assert onp.allclose(f_new(x, g, b), f_old(x, g, b), rtol=1e-5)
    g1 = jax.grad(f_new, (0, 1, 2))(x, g, b)
    g2 = jax.grad(f_old, (0, 1, 2))(x, g, b)
    for u, w in zip(g1, g2):
        onp.testing.assert_allclose(u, w, rtol=1e-4, atol=1e-5)


def test_batch_norm_eval_matches_reference_formula():
    rs = onp.random.RandomState(1)
    x = jnp.asarray(rs.randn(2, 3, 4, 4).astype("f"))
    g = jnp.asarray(rs.rand(3).astype("f") + 0.5)
    b = jnp.asarray(rs.randn(3).astype("f"))
    mm = jnp.asarray(rs.randn(3).astype("f"))
    mv = jnp.asarray(rs.rand(3).astype("f") + 0.1)
    out, nm, nv = N.batch_norm(x, g, b, mm, mv, axis=1, training=False)
    inv = jax.lax.rsqrt(mv + 1e-5)
    want = ((x - mm[None, :, None, None]) * inv[None, :, None, None]
            * g[None, :, None, None] + b[None, :, None, None])
    onp.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    onp.testing.assert_allclose(nm, mm)
    onp.testing.assert_allclose(nv, mv)


@pytest.mark.parametrize("with_gamma,with_beta",
                         [(True, True), (True, False), (False, False)])
@pytest.mark.parametrize("axis", [1, -1])
def test_layer_norm_vjp_matches_autodiff(axis, with_gamma, with_beta):
    rs = onp.random.RandomState(2)
    x = jnp.asarray(rs.randn(4, 7, 7).astype("f"))
    c = x.shape[axis]
    g = jnp.asarray(rs.rand(c).astype("f") + 0.5) if with_gamma else None
    b = jnp.asarray(rs.randn(c).astype("f")) if with_beta else None

    def f_new(*a):
        o = N.layer_norm(a[0], a[1] if with_gamma else None,
                         a[2] if len(a) > 2 else None, axis=axis)
        return (o * jnp.sin(o)).sum()

    def f_old(*a):
        o = _naive_ln(a[0], a[1] if with_gamma else None,
                      a[2] if len(a) > 2 else None, axis=axis)
        return (o * jnp.sin(o)).sum()

    args = tuple(v for v in (x, g, b) if v is not None)
    idx = tuple(range(len(args)))
    assert onp.allclose(f_new(*args), f_old(*args), rtol=1e-5)
    g1 = jax.grad(f_new, idx)(*args)
    g2 = jax.grad(f_old, idx)(*args)
    for u, w in zip(g1, g2):
        onp.testing.assert_allclose(u, w, rtol=1e-4, atol=1e-5)


def test_norm_large_mean_no_cancellation():
    """Shifted single-pass variance must stay accurate for large-mean,
    small-variance data (the raw E[x²]−E[x]² form loses ~20% of the
    variance at mean≈300, std≈0.1 in f32)."""
    rs = onp.random.RandomState(7)
    big = (rs.randn(64, 8).astype("f") * 0.1 + 300.0)
    out = N.layer_norm(jnp.asarray(big), None, None)
    want = ((big - big.mean(1, keepdims=True))
            / onp.sqrt(big.var(1) + 1e-5)[:, None])
    assert onp.abs(onp.asarray(out) - want).max() < 1e-2

    xb = jnp.asarray((rs.randn(16, 4, 8, 8) * 0.1 + 300.0).astype("f"))
    mm = jnp.full(4, 300.0)  # warm running mean = the BN shift
    _, _, nv = N.batch_norm(xb, jnp.ones(4), jnp.zeros(4), mm, jnp.ones(4),
                            axis=1, training=True, momentum=0.0)
    true_var = onp.asarray(xb).var(axis=(0, 2, 3))
    onp.testing.assert_allclose(onp.asarray(nv), true_var, rtol=1e-2)


def test_batch_norm_mixed_param_dtypes():
    """dgamma/dbeta cotangent dtypes must match their primals (gamma f32 +
    beta bf16 previously raised in custom_vjp)."""
    rs = onp.random.RandomState(8)
    x = jnp.asarray(rs.randn(4, 4, 6, 6).astype("f"))
    mm, mv = jnp.zeros(4), jnp.ones(4)

    def f(x, g, b):
        o, _, _ = N.batch_norm(x, g, b, mm, mv, axis=1, training=True)
        return o.astype(jnp.float32).sum()

    grads = jax.grad(f, (0, 1, 2))(
        x, jnp.ones(4, jnp.float32), jnp.zeros(4, jnp.bfloat16))
    assert grads[1].dtype == jnp.float32
    assert grads[2].dtype == jnp.bfloat16


def test_batch_norm_bf16_stats_are_fp32():
    """bf16 activations must produce fp32-accurate batch stats (the fused
    sum/sum² path accumulates in fp32 — better than reducing in bf16)."""
    rs = onp.random.RandomState(3)
    big = rs.randn(8, 4, 16, 16).astype("f") * 3 + 100.0  # mean >> var
    x = jnp.asarray(big, jnp.bfloat16)
    g = jnp.ones(4, jnp.float32)
    b = jnp.zeros(4, jnp.float32)
    _, nm, _ = N.batch_norm(x, g, b, jnp.zeros(4), jnp.ones(4),
                            axis=1, training=True, momentum=0.0)
    want = big.astype("f").mean(axis=(0, 2, 3))
    onp.testing.assert_allclose(onp.asarray(nm), want, rtol=1e-2)
