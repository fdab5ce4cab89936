"""`npx.short_conv` (ops/short_conv.py): the depthwise causal taps and
their activation against a padded convolution written out, its
hand-written VJP against autodiff of that, what the backward keeps, and
LFM2's `gated_short_conv` unchanged beside it."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, npx
from mxnet_tpu.ops import short_conv as sc


def _draw(b, s, d, taps, dtype="float32", seed=0):
    rs = onp.random.RandomState(seed)
    return (jnp.asarray(rs.randn(b, s, d), dtype),
            jnp.asarray(rs.uniform(-0.5, 0.5, (d, taps)), jnp.float32))


def _plain(x, w, activation="silu"):
    """PyTorch's Conv1d(D, D, L, groups=D, padding=L - 1) cut to its first
    S outputs, then the activation: float32."""
    taps, s = w.shape[1], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(w[:, j] * padded[:, j:j + s] for j in range(taps))
    return jax.nn.silu(c) if activation == "silu" else c


@pytest.mark.parametrize("activation", ["silu", None])
@pytest.mark.parametrize("b,s,d,taps", [(2, 9, 6, 4), (1, 3, 8, 4),
                                        (2, 16, 4, 3), (1, 12, 5, 1)],
                         ids=["4taps", "shorter-than-taps", "3taps", "1tap"])
def test_the_taps_are_the_padded_convolution(b, s, d, taps, activation):
    x, w = _draw(b, s, d, taps)
    got = sc.short_conv(x, w, activation)
    assert got.shape == x.shape and got.dtype == x.dtype
    onp.testing.assert_allclose(got, _plain(x, w, activation), atol=1e-6)


@pytest.mark.parametrize("activation", ["silu", None])
def test_the_vjp_is_autodiffs(activation):
    x, w = _draw(2, 11, 6, 4, seed=1)
    weight = jnp.asarray(onp.random.RandomState(2).randn(2, 11, 6), "f")

    def loss(fn):
        return lambda x_, w_: jnp.sum(jnp.sin(fn(x_, w_, activation))
                                      * weight)

    got = jax.grad(loss(sc.short_conv), (0, 1))(x, w)
    want = jax.grad(loss(_plain), (0, 1))(x, w)
    for g, wnt in zip(got, want):
        onp.testing.assert_allclose(g, wnt, atol=2e-6)


def test_bfloat16_rounds_once_and_the_taps_gradient_sums_in_float32():
    x, w = _draw(2, 64, 8, 4, "bfloat16", seed=3)
    got = sc.short_conv(x, w)
    assert got.dtype == jnp.bfloat16
    onp.testing.assert_array_equal(
        onp.asarray(got, "f"),
        onp.asarray(_plain(x, w).astype(jnp.bfloat16), "f"))
    dx, dw = jax.grad(lambda x_, w_: jnp.sum(
        sc.short_conv(x_, w_).astype(jnp.float32)), (0, 1))(x, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    want = jax.grad(lambda w_: jnp.sum(_plain(x, w_)))(w)
    onp.testing.assert_allclose(dw, want, rtol=2e-2, atol=2e-2)


def test_the_backward_keeps_the_two_operands_alone():
    x, w = _draw(2, 32, 8, 4, "bfloat16", seed=4)
    _, pullback = jax.vjp(sc.short_conv, x, w)
    kept = jax.tree_util.tree_leaves(pullback)
    assert sorted(k.shape for k in kept if hasattr(k, "shape")
                  and k.size > 1) == sorted([x.shape, w.shape])


def test_the_op_is_causal():
    x, w = _draw(1, 10, 4, 4, seed=5)
    moved = x.at[:, 6].add(1.0)
    a, b = sc.short_conv(x, w), sc.short_conv(moved, w)
    onp.testing.assert_array_equal(a[:, :6], b[:, :6])
    assert float(jnp.abs(a[:, 6:10] - b[:, 6:10]).max()) > 1e-3


def test_operands_that_do_not_fit_are_refused():
    x, w = _draw(1, 8, 4, 4)
    with pytest.raises(ValueError, match="taps"):
        sc.short_conv(x, w[:3])
    with pytest.raises(ValueError, match="activation"):
        sc.short_conv(x, w, "relu")


def test_the_frontend_op_is_taped():
    x, w = (mx.np.array(onp.asarray(a)) for a in _draw(1, 8, 4, 4, seed=6))
    x.attach_grad()
    w.attach_grad()
    with autograd.record():
        loss = (npx.short_conv(x, w) ** 2).sum()
    loss.backward()
    want = jax.grad(lambda x_, w_: jnp.sum(_plain(x_, w_) ** 2), (0, 1))(
        x._data, w._data)
    onp.testing.assert_allclose(x.grad.asnumpy(), want[0], atol=2e-6)
    onp.testing.assert_allclose(w.grad.asnumpy(), want[1], atol=2e-6)


def test_the_gated_mix_is_what_it_was():
    """LFM2's op beside the new one: y = C * conv(B * x~), its own VJP,
    by the formula written out."""
    rs = onp.random.RandomState(7)
    bcx = jnp.asarray(rs.randn(2, 10, 12), "f")
    w = jnp.asarray(rs.randn(4, 3), "f")
    b, c, x = jnp.split(bcx, 3, axis=-1)

    def plain(bcx_, w_):
        b_, c_, x_ = jnp.split(bcx_, 3, axis=-1)
        return c_ * _plain(b_ * x_, w_, None)

    onp.testing.assert_allclose(sc.gated_short_conv(bcx, w), plain(bcx, w),
                                atol=1e-6)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(sc.gated_short_conv(*a))),
                   (0, 1))(bcx, w)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))), (0, 1))(bcx, w)
    for g, wnt in zip(got, want):
        onp.testing.assert_allclose(g, wnt, atol=2e-6)
    # and the plain taps are the gated mix with both gates at one
    ones = jnp.ones_like(x)
    onp.testing.assert_allclose(
        sc.gated_short_conv(jnp.concatenate([ones, ones, x], -1), w),
        sc.short_conv(x, w, None), atol=1e-6)
