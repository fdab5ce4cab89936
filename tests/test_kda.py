"""`npx.kda_scan` (Kimi Delta Attention's chunked scan) against the plain
recurrence: the composition of XLA ops and the Pallas kernels interpreted,
output and all five gradients; lengths of one chunk, several, and no
multiple of the chunk; strong decays; the delta rule's special cases; the
gauges and the loud fallback."""
import warnings

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, npx
from mxnet_tpu.ops import pallas_kda as pk
from mxnet_tpu.telemetry import instruments as ti


@pytest.fixture
def path(request, monkeypatch):
    """``composition``: what runs off a TPU; ``kernel``: the two Pallas
    kernels, interpreted."""
    mode = {"composition": None, "kernel": True}[request.param]
    monkeypatch.setattr(pk, "_kernel_mode", lambda: mode)
    pk._shared.cache_clear()
    yield request.param
    pk._shared.cache_clear()


BOTH = pytest.mark.parametrize("path", ["composition", "kernel"],
                               indirect=True)


def _inputs(b, s, h, dk, dv, seed=0, dtype=jnp.float32, strongest=-1.0):
    r = onp.random.default_rng(seed)
    q, k = r.normal(size=(2, b, s, h, dk))
    q /= onp.linalg.norm(q, axis=-1, keepdims=True)
    k /= onp.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(b, s, h, dv))
    # most channels decay little, a few by ``strongest`` a step
    g = strongest * r.uniform(size=(b, s, h, dk)) ** 3
    beta = r.uniform(size=(b, s, h))
    return ([jnp.asarray(x, dtype) for x in (q, k, v)]
            + [jnp.asarray(g, jnp.float32), jnp.asarray(beta, jnp.float32)])


def _rel(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def _against_the_recurrence(args, chunk, tol, seed=5):
    w = jnp.asarray(onp.random.default_rng(seed).normal(
        size=args[2].shape), jnp.float32)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    scan = lambda *a: pk.kda_scan(*a, chunk=chunk)      # noqa: E731
    out, want = scan(*args), pk.kda_recurrence(*args)
    assert out.shape == want.shape and out.dtype == args[2].dtype
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    assert _rel(out, want) < tol, "output"
    got = jax.grad(loss(scan), argnums=range(5))(*args)
    ref = jax.grad(loss(pk.kda_recurrence), argnums=range(5))(*args)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, ref):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) < tol, name


@pytest.mark.parametrize("shape,chunk", [
    ((1, 16, 1, 8, 8), 16),         # one chunk
    ((2, 64, 2, 16, 8), 16),        # several, B > 1, d_v != d_k
    ((1, 40, 3, 8, 16), 16),        # no multiple of the chunk
    ((1, 96, 1, 16, 16), 32),
], ids=["one-chunk", "several-b2", "ragged", "chunk32"])
@pytest.mark.parametrize("path", ["composition"], indirect=True)
def test_the_composition_is_the_recurrence(path, shape, chunk):
    _against_the_recurrence(_inputs(*shape, seed=1), chunk, 2e-5)


@pytest.mark.parametrize("shape,chunk", [
    ((1, 64, 1, 128, 128), 64),     # one chunk
    ((2, 128, 2, 128, 128), 32),    # several, B > 1, H > 1
    ((1, 100, 1, 128, 128), 32),    # no multiple of the chunk
    ((1, 144, 1, 128, 256), 16),    # two grid steps of 8 chunks, padded
], ids=["one-chunk", "several-b2h2", "ragged", "two-blocks"])
@pytest.mark.parametrize("path", ["kernel"], indirect=True)
def test_the_interpreted_kernels_are_the_recurrence(path, shape, chunk):
    before = ti._kda_scan_calls["kernel"]
    _against_the_recurrence(_inputs(*shape, seed=2), chunk, 2e-5)
    assert ti._kda_scan_calls["kernel"] > before


@BOTH
def test_bfloat16_operands_follow_the_recurrence_at_their_precision(path):
    args = _inputs(1, 128, 2, 128, 128, seed=3, dtype=jnp.bfloat16)
    _against_the_recurrence(args, 64, 3e-2)


@BOTH
@pytest.mark.parametrize("strongest", [-5.0, -40.0])
def test_strong_decays_stay_finite_and_equal_the_recurrence(path, strongest):
    """g down to -40 a step: a chunk of 64 sums to -2,560, where
    exp(G_t) * exp(-G_j) would be 0 * inf.  No clamp: the result is the
    recurrence's."""
    d = 128 if path == "kernel" else 16
    args = _inputs(1, 128, 1, d, d, seed=4, strongest=strongest)
    assert float(jnp.min(args[3])) < 0.9 * strongest
    _against_the_recurrence(args, 64, 2e-5)


@BOTH
def test_beta_zero_writes_nothing(path):
    d = 128 if path == "kernel" else 8
    q, k, v, g, beta = _inputs(1, 32, 1, d, d, seed=6)
    out = pk.kda_scan(q, k, v, g, jnp.zeros_like(beta), chunk=16)
    assert float(jnp.max(jnp.abs(out))) == 0.0
    dv = jax.grad(lambda v_: jnp.sum(pk.kda_scan(
        q, k, v_, g, jnp.zeros_like(beta), chunk=16)))(v)
    assert float(jnp.max(jnp.abs(dv))) == 0.0


@BOTH
def test_no_decay_and_beta_one_is_the_classic_delta_rule(path):
    """g = 0, beta = 1: S_t = S_{t-1} + k_t (v_t - S_{t-1}^T k_t)^T."""
    d = 128 if path == "kernel" else 8
    q, k, v, g, beta = _inputs(1, 32, 1, d, d, seed=7)
    out = pk.kda_scan(q, k, v, jnp.zeros_like(g), jnp.ones_like(beta),
                      scale=1.0, chunk=16)
    state, want = onp.zeros((d, d)), []
    for t in range(32):
        kt, vt = onp.asarray(k[0, t, 0]), onp.asarray(v[0, t, 0])
        state = state + onp.outer(kt, vt - state.T @ kt)
        want.append(state.T @ onp.asarray(q[0, t, 0]))
    onp.testing.assert_allclose(out[0, :, 0], onp.stack(want), atol=2e-5)


@BOTH
def test_orthonormal_keys_hold_the_last_value_written(path):
    """Keys e_0, e_1, e_2 in turn, no decay, beta = 1: asking with e_i
    returns exactly the last value written under e_i."""
    d, s = (128, 24) if path == "kernel" else (8, 24)
    keys = jnp.eye(d)[jnp.arange(s) % 3][None, :, None, :]
    v = jnp.asarray(onp.random.default_rng(8).normal(size=(1, s, 1, d)),
                    jnp.float32)
    asked = jnp.eye(d)[(jnp.arange(s) + 1) % 3][None, :, None, :]
    out = pk.kda_scan(asked, keys, v, jnp.zeros((1, s, 1, d)),
                      jnp.ones((1, s, 1)), scale=1.0, chunk=8)
    for t in range(3, s):
        # key (t + 1) % 3 was last written at t - 2
        onp.testing.assert_allclose(out[0, t, 0], v[0, t - 2, 0], atol=1e-6)


@BOTH
def test_a_padded_tail_leaves_the_head_as_it_was(path):
    d = 128 if path == "kernel" else 8
    args = _inputs(1, 48, 1, d, d, seed=9)
    whole = pk.kda_scan(*args, chunk=16)
    head = pk.kda_scan(*(a[:, :40] for a in args), chunk=16)
    onp.testing.assert_allclose(head, whole[:, :40], atol=1e-6)


@pytest.mark.parametrize("path", ["composition"], indirect=True)
def test_the_gauges_count_calls_chunks_and_kept_bytes(path):
    before = dict(ti._kda_scan_calls)
    pk.kda_scan(*_inputs(2, 40, 3, 8, 16), chunk=16)
    assert ti._kda_scan_calls["composition"] == before["composition"] + 1
    assert ti._kda_scan_calls["kernel"] == before["kernel"]
    calls = {k[0]: g.value for k, g in ti.kda_scan_calls.series()}
    assert calls["composition"] == ti._kda_scan_calls["composition"]
    assert ti.kda_scan_chunks.value == 2 * 3 * 3        # ceil(40 / 16) = 3
    assert ti.kda_scan_kept_bytes.value == 2 * 3 * 3 * 8 * 16 * 4


def test_a_width_the_kernels_cannot_tile_falls_back_loudly(monkeypatch):
    monkeypatch.setattr(pk, "_kernel_mode", lambda: True)
    pk._shared.cache_clear()
    before = dict(ti._kda_scan_calls)
    args = _inputs(1, 32, 1, 16, 16, seed=10)
    with pytest.warns(RuntimeWarning, match="cannot be tiled.*width"):
        out = pk.kda_scan(*args, chunk=16)
    assert ti._kda_scan_calls["composition"] == before["composition"] + 1
    assert ti._kda_scan_calls["kernel"] == before["kernel"]
    assert _rel(out, pk.kda_recurrence(*args)) < 2e-5
    with pytest.warns(RuntimeWarning, match="chunk"):
        pk.kda_scan(*_inputs(1, 8, 1, 128, 128), chunk=4)
    # off a TPU the composition is the path, and says nothing
    monkeypatch.setattr(pk, "_kernel_mode", lambda: None)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pk.kda_scan(*args, chunk=16)
    pk._shared.cache_clear()


def test_operands_that_do_not_fit_are_refused():
    q, k, v, g, beta = _inputs(1, 16, 2, 8, 8)
    with pytest.raises(ValueError, match="beta"):
        pk.kda_scan(q, k, v, g, beta[:, :, :1])
    with pytest.raises(ValueError, match="d_k"):
        pk.kda_scan(q, k[..., :4], v, g, beta)
    with pytest.raises(ValueError, match="power of two"):
        pk.kda_scan(q, k, v, g, beta, chunk=24)


def test_the_frontend_op_is_taped():
    args = [mx.np.array(onp.asarray(a)) for a in _inputs(1, 16, 1, 8, 8)]
    for a in args:
        a.attach_grad()
    with autograd.record():
        out = npx.kda_scan(*args, chunk=16)
        loss = (out * out).sum()
    loss.backward()
    want = jax.grad(lambda *a: jnp.sum(pk.kda_recurrence(*a) ** 2),
                    argnums=range(5))(*(a._data for a in args))
    for a, w in zip(args, want):
        assert _rel(a.grad._data, w) < 2e-5
