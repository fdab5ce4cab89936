"""Interleaved (virtual-stage) pipeline schedule + 1F1B training step
(VERDICT r2 next #5): bubble (S-1)/v, O(S) activation memory, numerics
vs the sequential oracle."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import Mesh

from mxnet_tpu.parallel.pipeline import (interleave_stages, pipeline_apply_sharded,
                                         pipeline_step_1f1b_sharded)

S = 4          # pipeline stages (8 virtual CPU devices available)
DIM = 6


def _mesh():
    return Mesh(onp.array(jax.devices()[:S]), ("pp",))


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _mk_params(n, seed=0):
    rs = onp.random.RandomState(seed)
    return [{"w": jnp.asarray(rs.randn(DIM, DIM).astype("f") * 0.5),
             "b": jnp.asarray(rs.randn(DIM).astype("f") * 0.1)}
            for _ in range(n)]


def _sequential(params_list, mbs):
    out = []
    for m in range(mbs.shape[0]):
        x = mbs[m]
        for p in params_list:
            x = _stage_fn(p, x)
        out.append(x)
    return jnp.stack(out)


@pytest.mark.parametrize("v,M", [(1, 8), (2, 8), (4, 8)])
def test_interleaved_forward_matches_sequential(v, M):
    plist = _mk_params(S * v)
    stacked = interleave_stages(plist, S)
    mbs = jnp.asarray(onp.random.RandomState(1).randn(M, 3, DIM)
                      .astype("f"))
    got = pipeline_apply_sharded(_stage_fn, stacked, mbs, _mesh(),
                                 num_virtual=v)
    want = _sequential(plist, mbs)
    onp.testing.assert_allclose(onp.asarray(got), onp.asarray(want),
                                rtol=2e-5, atol=2e-5)


def test_interleaved_requires_divisible_microbatches():
    plist = _mk_params(S * 2)
    stacked = interleave_stages(plist, S)
    mbs = jnp.zeros((6, 3, DIM), jnp.float32)   # 6 % 4 != 0
    with pytest.raises(ValueError, match="M % S"):
        pipeline_apply_sharded(_stage_fn, stacked, mbs, _mesh(),
                               num_virtual=2)


def _loss_fn(y, label):
    return jnp.mean((y - label) ** 2)


@pytest.mark.parametrize("M", [4, 8, 7])
def test_1f1b_loss_and_grads_match_sequential(M):
    plist = _mk_params(S, seed=2)
    stacked = interleave_stages(plist, S)   # v=1: identity ordering
    rs = onp.random.RandomState(3)
    mbs = jnp.asarray(rs.randn(M, 3, DIM).astype("f"))
    labels = jnp.asarray(rs.randn(M, 3, DIM).astype("f"))

    loss, grads = pipeline_step_1f1b_sharded(
        _stage_fn, _loss_fn, stacked, mbs, labels, _mesh())

    def seq_loss(stacked_p):
        total = 0.0
        for m in range(M):
            x = mbs[m]
            for k in range(S):
                p = jax.tree_util.tree_map(lambda a: a[k], stacked_p)
                x = _stage_fn(p, x)
            total = total + _loss_fn(x, labels[m])
        return total / M

    want_loss = seq_loss(stacked)
    want_grads = jax.grad(seq_loss)(stacked)
    onp.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for k in ("w", "b"):
        onp.testing.assert_allclose(
            onp.asarray(grads[k]), onp.asarray(want_grads[k]),
            rtol=3e-5, atol=3e-5)


def test_1f1b_grad_step_reduces_loss():
    plist = _mk_params(S, seed=5)
    stacked = interleave_stages(plist, S)
    rs = onp.random.RandomState(6)
    mbs = jnp.asarray(rs.randn(8, 2, DIM).astype("f"))
    labels = jnp.asarray(rs.randn(8, 2, DIM).astype("f"))
    l0, g = pipeline_step_1f1b_sharded(
        _stage_fn, _loss_fn, stacked, mbs, labels, _mesh())
    stacked = jax.tree_util.tree_map(lambda p, d: p - 0.1 * d.astype(
        p.dtype), stacked, g)
    l1, _ = pipeline_step_1f1b_sharded(
        _stage_fn, _loss_fn, stacked, mbs, labels, _mesh())
    assert float(l1) < float(l0)


def test_schedule_efficiency_bound():
    """The analytic bound of the interleaved schedule:
    M*v/(M*v + S - 1) >= 0.90 at M=32, S=8, v=4 (GPipe v=1 was
    0.8205)."""
    M, S_, v = 32, 8, 4
    eff = (M * v) / (M * v + S_ - 1)
    assert eff > 0.94
    assert M / (M + S_ - 1) < 0.83   # the bound this replaces
