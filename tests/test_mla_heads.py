"""`npx.mla_heads` (ops/pallas_mla_heads.py) on the CPU: the four kernels
in interpret mode against the composition of XLA ops (`rotary_embedding`,
broadcast, concatenate, transpose) through the lane order the docstring
states, which path the op takes by what it sees, and the gauge."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, npx
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops import pallas_mla_heads as mh
from mxnet_tpu.telemetry import instruments as ti

THETA = 1e6


@pytest.fixture
def mode(monkeypatch):
    """Steers what the op sees of the platform: True = the kernels,
    interpreted; None = no TPU.  The gauge's tally starts at nothing."""
    monkeypatch.setattr(ti, "_mla_heads_sites", [0, 0])
    ti.mla_heads_kernel_share.clear()

    def set_mode(value):
        monkeypatch.setattr(mh, "_kernel_mode", lambda: value)

    yield set_mode
    ti.mla_heads_kernel_share.clear()


def _operands(b, s_len, heads, nope, v, dtype, rope=64, seed=0):
    rs = onp.random.RandomState(seed)

    def draw(*shape):
        return jnp.asarray(1.3 * rs.randn(*shape), dtype)

    return (draw(b, s_len, heads * (nope + rope)),
            draw(b, s_len, heads * (nope + v)), draw(b, s_len, rope))


def _weights(b, s_len, heads, nope, v, rope=64, seed=1):
    rs = onp.random.RandomState(seed)
    return [jnp.asarray(rs.randn(b, heads, s_len, w), jnp.float32)
            for w in (nope + rope, nope + rope, v)]


def _kernels_lanes(nope, rope, interleaved):
    """For each lane of the kernels' q and k, the lane of the
    composition's that holds the same number: the kernels turn pair (2i,
    2i + 1) where it is, `rotary_embedding` leaves it at (i, i + rope /
    2); without ``interleaved`` both leave every lane where it was."""
    lane = onp.arange(rope)
    there = lane // 2 + lane % 2 * (rope // 2) if interleaved else lane
    return onp.concatenate([onp.arange(nope), nope + there])


def _value_and_grads(fn, operands, positions, heads, interleaved, weights):
    def loss(q, kv, k_rope):
        outs = fn(q, kv, k_rope, positions, THETA, heads, interleaved)
        return sum(jnp.sum(o.astype(jnp.float32) * w)
                   for o, w in zip(outs, weights)), outs

    (_, outs), grads = jax.value_and_grad(loss, (0, 1, 2),
                                          has_aux=True)(*operands)
    return outs + grads


_POSITIONS = {
    "contiguous": lambda s: onp.arange(s),
    "scattered": lambda s: (onp.arange(s) * 37 + 11) % 4099,
}


@pytest.mark.parametrize("interleaved", [True, False],
                         ids=["pairs", "halves"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,s_len,nope,v,tile,positions", [
    (32, 16, 128, 128, None, "contiguous"),   # kanana-2's heads
    (2, 16, 128, 128, None, "scattered"),     # one pair of heads
    (4, 48, 128, 128, 16, "scattered"),       # three row tiles
    (2, 40, 128, 128, 16, "contiguous"),      # the last tile hangs over
    (2, 128, 128, 128, None, "scattered"),    # two chunks of a block's rows
    (2, 16, 256, 128, None, "contiguous"),    # two lane blocks of nope lanes
    (4, 16, 128, 256, None, "scattered"),     # values wider than the keys'
], ids=["h32", "h2", "tiles", "edge", "chunks", "nope256", "v256"])
def test_the_kernels_match_the_composition(mode, monkeypatch, dtype, heads,
                                           s_len, nope, v, tile, positions,
                                           interleaved):
    """q, k, v and dq, dkv, dk_rope, lane for lane through the stated
    order.  Both round once after the float32 rotation: in float32 they
    agree to rounding, in bfloat16 to the unit a tie rounds by; the
    composition sums dk_rope over the heads in the tensors' type, the
    kernels in float32, so in bfloat16 that one differs by a unit or
    two."""
    if tile:
        monkeypatch.setattr(mh, "_MAX_ROWS", tile)
    operands = _operands(2, s_len, heads, nope, v, dtype)
    weights = _weights(2, s_len, heads, nope, v)
    pos = jnp.asarray(_POSITIONS[positions](s_len), jnp.int32)
    lanes = _kernels_lanes(nope, 64, interleaved)
    back = onp.argsort(lanes)
    mode(True)
    got = _value_and_grads(mh.mla_heads, operands, pos, heads, interleaved,
                           weights)
    # the same weight on the same number: the composition's in its order
    moved = [weights[0][..., back], weights[1][..., back], weights[2]]
    want = _value_and_grads(mh._composition, operands, pos, heads,
                            interleaved, moved)
    assert ti.mla_heads_kernel_share.value == 1.0
    want = (want[0][..., lanes], want[1][..., lanes]) + want[2:]
    shapes = [(2, heads, s_len, nope + 64), (2, heads, s_len, nope + 64),
              (2, heads, s_len, v)] + [o.shape for o in operands]
    names = ["q", "k", "v", "dq", "dkv", "dk_rope"]
    for name, g, w, shape in zip(names, got, want, shapes):
        assert g.shape == shape and g.dtype == operands[0].dtype, name
        tol = 1e-5 if dtype == "float32" \
            else 2.0 ** (-6 if name == "dk_rope" else -7)
        g, w = onp.asarray(g, "f"), onp.asarray(w, "f")
        onp.testing.assert_allclose(g / onp.abs(w).max(),
                                    w / onp.abs(w).max(), atol=tol,
                                    err_msg=name)


@pytest.mark.parametrize("interleaved", [True, False],
                         ids=["pairs", "halves"])
def test_every_score_is_the_compositions(mode, interleaved):
    """q . k over a head's lanes does not see the order q and k share: the
    kernels' scores equal the composition's to float32 rounding."""
    operands = _operands(2, 32, 4, 128, 128, "float32", seed=3)
    pos = jnp.asarray(_POSITIONS["scattered"](32), jnp.int32)
    mode(True)
    q, k, _ = mh.mla_heads(*operands, pos, THETA, 4, interleaved)
    rq, rk, _ = mh._composition(*operands, pos, THETA, 4, interleaved)
    hi = jax.lax.Precision.HIGHEST
    got = jnp.einsum("bhsd,bhtd->bhst", q, k, precision=hi)
    want = jnp.einsum("bhsd,bhtd->bhst", rq, rk, precision=hi)
    onp.testing.assert_allclose(got, want, rtol=1e-5,
                                atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("heads,s_len,nope,v,rope,kernel_mode", [
    (3, 32, 128, 128, 64, True),     # the last head has no partner
    (2, 32, 128, 128, 96, True),     # a rope width the kernels do not tile
    (2, 32, 128, 128, 128, True),
    (2, 12, 128, 128, 64, True),     # rows that are not whole sublanes
    (2, 32, 64, 128, 64, True),      # nope lanes that fill half a block
    (2, 32, 128, 64, 64, True),
    (4, 32, 16, 16, 8, True),        # the toy configuration's widths
    (2, 32, 128, 128, 64, None),     # no TPU
    (32, 16, 128, 128, 64, None),
], ids=["odd", "rope96", "rope128", "s12", "nope64", "v64", "toy", "cpu",
        "cpu-h32"])
def test_the_composition_runs_where_the_kernels_cannot(mode, monkeypatch,
                                                       heads, s_len, nope, v,
                                                       rope, kernel_mode):
    def no_kernel(*_):
        raise AssertionError("the kernels were called")

    monkeypatch.setattr(mh, "_assembled", no_kernel)
    operands = _operands(2, s_len, heads, nope, v, "float32", rope)
    weights = _weights(2, s_len, heads, nope, v, rope)
    pos = jnp.arange(s_len, dtype=jnp.int32)
    mode(kernel_mode)
    got = _value_and_grads(mh.mla_heads, operands, pos, heads, True, weights)
    want = _value_and_grads(mh._composition, operands, pos, heads, True,
                            weights)
    for g, w in zip(got, want):
        onp.testing.assert_array_equal(g, w)
    assert ti.mla_heads_kernel_share.value == 0.0


def test_the_composition_is_the_blocks_formula_before_the_op():
    """`MultiHeadLatentAttention.forward`'s ``heads`` as it was: the
    rotation on each head's rope lanes and on the one key part, the
    broadcast, the two concatenations and the transposes."""
    b, s_len, heads, nope, v = 2, 16, 4, 16, 24
    q, kv, k_rope = _operands(b, s_len, heads, nope, v, "float32", rope=8)
    pos = jnp.arange(s_len, dtype=jnp.int32)

    def rotate(t):
        return ops_nn.rotary_embedding(t, pos.reshape((s_len, 1)), THETA,
                                       interleaved=True)

    q_ = q.reshape((b, s_len, heads, -1))
    kv_ = kv.reshape((b, s_len, heads, nope + v))
    q_ = jnp.concatenate([q_[..., :nope], rotate(q_[..., nope:])], axis=-1)
    kr_ = jnp.broadcast_to(rotate(k_rope[:, :, None, :]),
                           (b, s_len, heads, 8))
    k_ = jnp.concatenate([kv_[..., :nope], kr_], axis=-1)
    want = [t.transpose((0, 2, 1, 3)) for t in (q_, k_, kv_[..., nope:])]
    got = mh.mla_heads(q, kv, k_rope, pos, THETA, heads, True)
    for g, w in zip(got, want):
        onp.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("nope,v,itemsize,rows", [
    (128, 128, 2, 1024), (128, 128, 4, 512), (512, 128, 2, 512)])
def test_a_blocks_rows_fit_the_widest_kernel_to_the_budget(nope, v, itemsize,
                                                           rows):
    """The backward of k and v: dk (its 64 rope lanes a whole lane block
    in fast memory), dv, dkv, dk_rope and the two float32 tables,
    double-buffered, and the float32 sum over the heads."""
    assert mh._row_tile(8192, nope, v, itemsize) == rows
    a_row = 2 * (2 * itemsize * (nope + 128) + 2 * itemsize * v
                 + 2 * itemsize * (nope + v) + itemsize * 128
                 + 2 * 4 * 128) + 4 * 128
    assert rows * a_row <= mh._VMEM_BUDGET < 2 * rows * a_row
    assert mh._row_tile(24, nope, v, itemsize) == 24      # one block


def test_the_gauge_is_the_share_of_traced_sites_on_the_kernels(mode):
    pos = jnp.arange(16, dtype=jnp.int32)
    even = _operands(1, 16, 2, 128, 128, "float32")
    odd = _operands(1, 16, 3, 128, 128, "float32")
    mode(True)
    assert ti.mla_heads_kernel_share.value == 0.0         # nothing traced

    @jax.jit
    def three_sites(even, odd):
        return sum(o.sum() for heads, operands in ((2, even), (2, even),
                                                   (3, odd))
                   for o in mh.mla_heads(*operands, pos, THETA, heads, True))

    three_sites(even, odd)
    assert ti.mla_heads_kernel_share.value == pytest.approx(2 / 3)
    three_sites(even, odd)                 # a cached program traces nothing
    assert ti.mla_heads_kernel_share.value == pytest.approx(2 / 3)


def test_operands_that_do_not_fit_are_refused(mode):
    q, kv, k_rope = _operands(1, 16, 2, 128, 128, "float32")
    pos = jnp.arange(16, dtype=jnp.int32)
    with pytest.raises(ValueError, match="heads"):
        mh.mla_heads(q, kv, k_rope, pos, THETA, 3)
    with pytest.raises(ValueError, match="heads"):
        mh.mla_heads(q, kv[..., :-1], k_rope, pos, THETA, 2)
    with pytest.raises(ValueError, match="heads"):
        mh.mla_heads(q, kv, k_rope[:, :8], pos, THETA, 2)
    with pytest.raises(ValueError, match="position"):
        mh.mla_heads(q, kv, k_rope, pos[:8], THETA, 2)


def test_the_frontend_op_is_taped(mode):
    mode(True)
    operands = _operands(1, 16, 2, 128, 128, "float32")
    weights = _weights(1, 16, 2, 128, 128)
    pos = jnp.arange(16, dtype=jnp.int32)
    arrays = [NDArray(t) for t in operands]
    for a in arrays:
        a.attach_grad()
    with autograd.record():
        outs = npx.mla_heads(*arrays, NDArray(pos), THETA, 2, True)
        loss = sum((o * NDArray(w)).sum() for o, w in zip(outs, weights))
    loss.backward()
    want = _value_and_grads(mh.mla_heads, operands, pos, 2, True, weights)
    for a, w in zip(arrays, want[3:]):
        onp.testing.assert_allclose(a.grad.asnumpy(), w, atol=1e-5)


# -- a layer that carries no positions (``positions=None``) -------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,s_len,nope,v,tile", [
    (32, 16, 128, 128, None),     # the delta / latent hybrid's heads
    (2, 40, 128, 128, 16),        # the last tile hangs over
    (4, 16, 128, 256, None),      # values wider than the keys'
], ids=["h32", "edge", "v256"])
def test_without_positions_the_kernels_only_assemble(mode, monkeypatch, dtype,
                                                     heads, s_len, nope, v,
                                                     tile):
    """q, k, v and the three gradients against the composition without
    rotation, lane for lane (nothing turns, so no lane moves): the shared
    key part reaches every head as it is, and its gradient is the sum over
    the heads."""
    if tile:
        monkeypatch.setattr(mh, "_MAX_ROWS", tile)
    operands = _operands(2, s_len, heads, nope, v, dtype)
    weights = _weights(2, s_len, heads, nope, v)
    mode(True)
    got = _value_and_grads(mh.mla_heads, operands, None, heads, True,
                           weights)
    assert ti.mla_heads_kernel_share.value == 1.0
    want = _value_and_grads(mh._composition, operands, None, heads, True,
                            weights)
    names = ["q", "k", "v", "dq", "dkv", "dk_rope"]
    for name, g, w in zip(names, got, want):
        assert g.shape == w.shape and g.dtype == operands[0].dtype, name
        tol = 1e-6 if dtype == "float32" \
            else 2.0 ** (-6 if name == "dk_rope" else -9)
        g, w = onp.asarray(g, "f"), onp.asarray(w, "f")
        onp.testing.assert_allclose(g / onp.abs(w).max(),
                                    w / onp.abs(w).max(), atol=tol,
                                    err_msg=name)
    # what the composition without positions is: the projections' own
    # numbers, the shared part under every head
    q, kv, k_rope = operands
    b = q.shape[0]
    onp.testing.assert_array_equal(
        onp.asarray(want[0], "f"),
        onp.asarray(q.reshape(b, s_len, heads, -1).transpose(0, 2, 1, 3),
                    "f"))
    for h in (0, heads - 1):
        onp.testing.assert_array_equal(onp.asarray(want[1][:, h, :, nope:],
                                                   "f"),
                                       onp.asarray(k_rope, "f"))


def _pallas_operands(jaxpr):
    """Operand counts of every pallas_call under ``jaxpr``."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(len(eqn.invars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_operands(sub)
    return found


def test_without_positions_no_table_is_among_the_kernels_operands(mode):
    """The rotation is compiled out: the kernels take q alone and kv,
    k_rope alone, where a rotating layer's take two tables more."""
    mode(True)
    operands = _operands(1, 16, 2, 128, 128, "float32")

    def kernels(positions):
        return sorted(_pallas_operands(jax.make_jaxpr(
            lambda *o: mh.mla_heads(*o, positions, THETA, 2, True))(
                *operands).jaxpr))

    assert kernels(None) == [1, 2]
    assert kernels(jnp.arange(16)) == [3, 4]


def test_the_frontend_op_takes_none_for_the_positions(mode):
    mode(None)
    q, kv, k_rope = (NDArray(o) for o in _operands(1, 8, 2, 16, 16,
                                                   "float32", rope=8))
    out = npx.mla_heads(q, kv, k_rope, None, THETA, 2, True)
    want = mh._composition(q._data, kv._data, k_rope._data, None, THETA, 2,
                           True)
    for o, w in zip(out, want):
        onp.testing.assert_array_equal(o.asnumpy(), onp.asarray(w))
