"""`npx.rms_norm_rotary` (ops/pallas_qk_prep.py) on the CPU: the two
kernels in interpret mode against the composition ``rms_norm`` ->
``rotary_embedding`` -> ``transpose``, which path the op takes by what it
sees, the gauge, and `GroupedQueryAttention` against the formula it had
before the op."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, npx
from mxnet_tpu.gluon.model_zoo.decoder import attend
from mxnet_tpu.gluon.model_zoo.sdar import GroupedQueryAttention
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import pallas_qk_prep as qp
from mxnet_tpu.telemetry import instruments as ti

THETA, EPS = 1e6, 1e-6


@pytest.fixture
def mode(monkeypatch):
    """Steers what the op sees of the platform: True = the kernels,
    interpreted; None = no TPU.  The gauge's tally starts at nothing."""
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    ti.qk_prep_kernel_share.clear()

    def set_mode(value):
        monkeypatch.setattr(qp, "_kernel_mode", lambda: value)

    yield set_mode
    ti.qk_prep_kernel_share.clear()


def _operands(b, s_len, heads, d, dtype, seed=0):
    rs = onp.random.RandomState(seed)
    x = jnp.asarray(1.7 * rs.randn(b, s_len, heads * d), dtype)
    gamma = jnp.asarray(rs.uniform(0.9, 1.1, (d,)), jnp.float32)
    weight = jnp.asarray(rs.randn(b, heads, s_len, d), jnp.float32)
    return x, gamma, weight


def _value_and_grads(fn, x, gamma, positions, heads, weight):
    def loss(x, gamma):
        out = fn(x, gamma, positions, THETA, heads, EPS)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(x, gamma)
    return (out,) + grads


_POSITIONS = {
    "contiguous": lambda s: onp.arange(s),
    "twice": lambda s: onp.concatenate([onp.arange(s // 2)] * 2),
    "scattered": lambda s: (onp.arange(s) * 37 + 11) % 4099,
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d,heads,s_len,tile,positions", [
    (128, 8, 32, None, "twice"),      # many query heads, SDAR's positions
    (128, 2, 32, None, "contiguous"),  # a group's few key heads
    (128, 2, 48, 16, "scattered"),    # three row tiles
    (128, 4, 40, 16, "twice"),        # the last tile hangs over the sequence
    (64, 32, 32, None, "contiguous"),  # LFM2's queries: two heads a block
    (64, 8, 32, None, "twice"),       # ... and its keys
    (64, 2, 48, 16, "scattered"),     # one block of heads, three row tiles
    (64, 8, 40, 16, "twice"),         # the hanging tile's dgamma mask
], ids=["queries", "keys", "tiles", "edge",
        "d64-queries", "d64-keys", "d64-tiles", "d64-edge"])
def test_the_kernels_match_the_composition(mode, monkeypatch, dtype, d, heads,
                                           s_len, tile, positions):
    """Forward, dx and dgamma, a head a lane block (D = 128) and two heads
    a block (D = 64).  In float32 the two agree to rounding; in bfloat16
    the kernels round once where the composition rounds after the norm
    too, so they differ by a unit or two of bfloat16."""
    if tile:
        monkeypatch.setattr(qp, "_MAX_ROWS", tile)
    x, gamma, weight = _operands(2, s_len, heads, d, dtype)
    pos = jnp.asarray(_POSITIONS[positions](s_len), jnp.int32)
    mode(True)
    got = _value_and_grads(qp.rms_norm_rotary, x, gamma, pos, heads, weight)
    want = _value_and_grads(qp._composition, x, gamma, pos, heads, weight)
    assert ti.qk_prep_kernel_share.value == 1.0
    assert got[0].shape == (2, heads, s_len, d) and got[0].dtype == x.dtype
    assert got[1].shape == x.shape and got[1].dtype == x.dtype
    assert got[2].shape == (d,) and got[2].dtype == gamma.dtype
    tol = 1e-5 if dtype == "float32" else 2.0 ** -6
    for g, w in zip(got, want):
        g, w = onp.asarray(g, "f"), onp.asarray(w, "f")
        onp.testing.assert_allclose(g / onp.abs(w).max(),
                                    w / onp.abs(w).max(), atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d,heads,s_len,tile", [
    (128, 4, 32, None), (64, 8, 32, None), (64, 2, 40, 16)],
    ids=["d128", "d64", "d64-edge"])
def test_the_norm_free_form_matches_the_composition(mode, monkeypatch, dtype,
                                                    d, heads, s_len, tile):
    """``gamma=None``: the rotation and the head-major store alone, and a
    backward that reads the cotangent alone; both round once, so they
    agree to float32 rounding in either type."""
    if tile:
        monkeypatch.setattr(qp, "_MAX_ROWS", tile)
    x, _, weight = _operands(2, s_len, heads, d, dtype)
    pos = jnp.asarray(_POSITIONS["scattered"](s_len), jnp.int32)
    mode(True)

    def value_and_grad(fn):
        def loss(x):
            out = fn(x, None, pos, THETA, heads, EPS)
            return jnp.sum(out.astype(jnp.float32) * weight), out

        (_, out), dx = jax.value_and_grad(loss, has_aux=True)(x)
        return out, dx

    got, want = value_and_grad(qp.rms_norm_rotary), \
        value_and_grad(qp._composition)
    assert ti.qk_prep_kernel_share.value == 1.0
    assert got[0].shape == (2, heads, s_len, d) and got[1].shape == x.shape
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    for g, w in zip(got, want):
        assert g.dtype == x.dtype
        g, w = onp.asarray(g, "f"), onp.asarray(w, "f")
        onp.testing.assert_allclose(g / onp.abs(w).max(),
                                    w / onp.abs(w).max(), atol=tol)


@pytest.mark.parametrize("d", [128, 64])
def test_nothing_is_rounded_between_norm_and_rotation(mode, d):
    """bfloat16 in, the kernels against the float32 formula on the same
    values: within half a unit of bfloat16 of the result, which the
    composition, rounding twice, is not."""
    x, gamma, weight = _operands(2, 64, 4, d, jnp.bfloat16, seed=3)
    pos = jnp.arange(64, dtype=jnp.int32)
    mode(True)
    exact = qp._composition(x.astype(jnp.float32), gamma, pos, THETA, 4, EPS)
    once = qp.rms_norm_rotary(x, gamma, pos, THETA, 4, EPS)
    twice = qp._composition(x, gamma, pos, THETA, 4, EPS)
    # half a unit in the last place of a bfloat16 of that size, and a bit
    half_ulp = 2.0 ** (onp.floor(onp.log2(onp.abs(exact) + 1e-30)) - 8)
    gap = lambda a: onp.abs(onp.asarray(a, "f") - exact)  # noqa: E731
    assert onp.all(gap(once) <= half_ulp * 1.01)
    assert onp.any(gap(twice) > half_ulp * 1.01)


@pytest.mark.parametrize("d,heads,s_len,kernel_mode", [
    (192, 2, 32, True),      # a lane width and a half
    (96, 4, 32, True),       # a width that divides no lane block
    (32, 4, 32, True),       # four heads a lane block: not a tested width
    (64, 3, 32, True),       # the last 64-wide head fills half a block
    (128, 2, 12, True),      # rows that are not whole sublanes
    (64, 2, 12, True),
    (128, 2, 32, None),      # no TPU
    (64, 2, 32, None),
], ids=["d192", "d96", "d32", "d64-odd", "s12", "d64-s12", "cpu", "d64-cpu"])
def test_the_composition_runs_where_the_kernels_cannot(mode, monkeypatch, d,
                                                       heads, s_len,
                                                       kernel_mode):
    def no_kernel(*_):
        raise AssertionError("the kernels were called")

    monkeypatch.setattr(qp, "_prepared", no_kernel)
    x, gamma, weight = _operands(2, s_len, heads, d, jnp.float32)
    pos = jnp.arange(s_len, dtype=jnp.int32)
    mode(kernel_mode)
    got = _value_and_grads(qp.rms_norm_rotary, x, gamma, pos, heads, weight)
    want = _value_and_grads(qp._composition, x, gamma, pos, heads, weight)
    for g, w in zip(got, want):
        onp.testing.assert_array_equal(g, w)
    assert ti.qk_prep_kernel_share.value == 0.0


@pytest.mark.parametrize("d,pack,itemsize,rows", [
    (128, 1, 2, 2048), (128, 1, 4, 2048), (256, 1, 2, 1024),
    (64, 2, 2, 2048), (64, 2, 4, 2048)])
def test_a_blocks_rows_fit_the_backward_to_the_budget(d, pack, itemsize, rows):
    """x, dy, dx and the two float32 tables, double-buffered; a packed
    block's head-major side is ``pack`` slices of a whole lane each."""
    assert qp._row_tile(8192, d, itemsize, pack) == rows
    flat, major = pack * d, pack * max(d, 128)
    bytes_a_row = 2 * itemsize * (2 * flat + major) + 16 * flat
    assert rows * bytes_a_row <= qp._VMEM_BUDGET
    assert qp._row_tile(24, d, itemsize, pack) == 24      # one block


def test_the_gauge_is_the_share_of_traced_sites_on_the_kernels(mode):
    pos = jnp.arange(16, dtype=jnp.int32)
    wide, gamma, _ = _operands(1, 16, 2, 128, jnp.float32)
    narrow, small, _ = _operands(1, 16, 2, 96, jnp.float32)
    mode(True)
    assert ti.qk_prep_kernel_share.value == 0.0           # nothing traced

    @jax.jit
    def three_sites(wide, narrow):
        return (qp.rms_norm_rotary(wide, gamma, pos, THETA, 2).sum()
                + qp.rms_norm_rotary(wide, gamma, pos, THETA, 2).sum()
                + qp.rms_norm_rotary(narrow, small, pos, THETA, 2).sum())

    three_sites(wide, narrow)
    assert ti.qk_prep_kernel_share.value == pytest.approx(2 / 3)
    three_sites(wide, narrow)              # a cached program traces nothing
    assert ti.qk_prep_kernel_share.value == pytest.approx(2 / 3)


def test_operands_that_do_not_fit_are_refused(mode):
    x, gamma, _ = _operands(1, 16, 2, 128, jnp.float32)
    pos = jnp.arange(16, dtype=jnp.int32)
    with pytest.raises(ValueError, match="heads"):
        qp.rms_norm_rotary(x, gamma, pos, THETA, 3)
    with pytest.raises(ValueError, match="heads"):
        qp.rms_norm_rotary(x, gamma[:64], pos, THETA, 2)
    with pytest.raises(ValueError, match="position"):
        qp.rms_norm_rotary(x, gamma, pos[:8], THETA, 2)


def test_the_frontend_op_is_taped(mode):
    mode(True)
    x, gamma, weight = _operands(1, 16, 2, 128, jnp.float32)
    pos = jnp.arange(16, dtype=jnp.int32)
    xs, gs = NDArray(x), NDArray(gamma)
    xs.attach_grad()
    gs.attach_grad()
    with autograd.record():
        out = npx.rms_norm_rotary(xs, gs, NDArray(pos), THETA, 2, EPS)
        loss = (out * NDArray(weight)).sum()
    loss.backward()
    _, dx, dgamma = _value_and_grads(qp._composition, x, gamma, pos, 2,
                                     weight)
    onp.testing.assert_allclose(xs.grad.asnumpy(), dx, atol=1e-5)
    onp.testing.assert_allclose(gs.grad.asnumpy(), dgamma, rtol=1e-5,
                                atol=1e-5)


# -- the block that calls it --------------------------------------------------

def _parent_names(d):
    return {"q_proj.weight": (4 * d, 32), "k_proj.weight": (2 * d, 32),
            "v_proj.weight": (2 * d, 32), "o_proj.weight": (32, 4 * d),
            "q_norm.gamma": (d,), "k_norm.gamma": (d,)}


def _block(dtype, d=128):
    block = GroupedQueryAttention(32, 4, 2, d, rope_theta=THETA,
                                  epsilon=EPS, dtype=dtype)
    block.initialize()
    rs = onp.random.RandomState(5)
    for name, p in block.collect_params().items():
        value = (rs.uniform(0.9, 1.1, p.shape) if name.endswith("gamma")
                 else 0.2 * rs.randn(*p.shape))
        p.set_data(NDArray(jnp.asarray(
            value, "float32" if name.endswith("gamma") else dtype)))
    return block


def _parent_forward(block, d, x, positions, block_diffusion):
    """`GroupedQueryAttention.forward` as it was before the op: norm block,
    rotation and transpose one after the other."""
    b, s, _ = x.shape

    def heads(t, n):
        return t.reshape((b, s, n, d))

    def rotated(t, norm):
        return npx.rotary_embedding(norm(t), positions.reshape((s, 1)),
                                    THETA).transpose((0, 2, 1, 3))

    q = rotated(heads(block.q_proj(x), 4), block.q_norm)
    k = rotated(heads(block.k_proj(x), 2), block.k_norm)
    v = heads(block.v_proj(x), 2).transpose((0, 2, 1, 3))
    out = attend(q, k, v, block_diffusion=block_diffusion)
    return block.o_proj(out.transpose((0, 2, 1, 3)).reshape((b, s, 4 * d)))


def _block_grads(block, forward, x, positions, weight):
    params = block.collect_params()
    for p in params.values():
        p.zero_grad()
    with autograd.record():
        out = forward(x, positions, (4, 8))
        loss = (out.astype("float32") * weight).sum()
    loss.backward()
    return out.asnumpy().astype("f"), {
        n: p.grad().asnumpy().astype("f") for n, p in params.items()}


@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("dtype,kernel_mode", [
    ("float32", None), ("float32", True), ("bfloat16", True)],
    ids=["composition", "kernels", "kernels-bf16"])
def test_the_attention_block_matches_the_parents_formula(mode, dtype,
                                                         kernel_mode, d):
    """Output and every parameter gradient, under the parent's names, at
    128-wide heads and at 64-wide ones (two a lane block): on the
    composition exactly, on the kernels to float32 rounding, and in
    bfloat16 within what one rounding fewer moves."""
    block = _block(dtype, d)
    assert {n: p.shape for n, p in block.collect_params().items()} \
        == _parent_names(d)
    rs = onp.random.RandomState(1)
    x = NDArray(jnp.asarray(rs.randn(2, 16, 32), dtype))
    weight = NDArray(jnp.asarray(rs.randn(2, 16, 32), "float32"))
    positions = NDArray(jnp.asarray(list(range(8)) * 2, jnp.int32))
    mode(kernel_mode)
    got, got_grads = _block_grads(block, block, x, positions, weight)
    want, want_grads = _block_grads(
        block, lambda *a: _parent_forward(block, d, *a), x, positions,
        weight)
    assert ti.qk_prep_kernel_share.value == (kernel_mode is True)
    tol = {None: 0.0, True: 1e-5}[kernel_mode] if dtype == "float32" \
        else 2.0 ** -5
    for name, w in dict(want_grads, out=want).items():
        g = got if name == "out" else got_grads[name]
        onp.testing.assert_allclose(g / onp.abs(w).max(),
                                    w / onp.abs(w).max(), atol=tol,
                                    err_msg=name)


def test_a_parents_checkpoint_loads(tmp_path):
    """The norms' scales keep their names though no norm block runs."""
    block = _block("float32")
    path = str(tmp_path / "attention.params")
    block.save_parameters(path)
    fresh = GroupedQueryAttention(32, 4, 2, 128)
    fresh.load_parameters(path)
    for name, p in block.collect_params().items():
        onp.testing.assert_array_equal(
            fresh.collect_params()[name].data().asnumpy(),
            p.data().asnumpy())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("d,heads,s_len,tile", [
    (128, 8, 32, None), (128, 2, 40, 16), (64, 8, 32, None),
    (64, 2, 40, 16)],
    ids=["d128", "d128-edge", "d64", "d64-edge"])
def test_the_position_free_form_matches_the_composition(mode, monkeypatch,
                                                        dtype, d, heads,
                                                        s_len, tile):
    """``positions=None`` (a layer that carries no positions): the norm
    and the head-major store alone, the rotation compiled out and no
    table among the kernels' operands; forward, dx and dgamma.  Both
    round once, so they agree to float32 rounding in either type; the
    site counts in the gauge like any other."""
    if tile:
        monkeypatch.setattr(qp, "_MAX_ROWS", tile)
    x, gamma, weight = _operands(2, s_len, heads, d, dtype)
    mode(True)
    got = _value_and_grads(qp.rms_norm_rotary, x, gamma, None, heads, weight)
    want = _value_and_grads(qp._composition, x, gamma, None, heads, weight)
    assert ti.qk_prep_kernel_share.value == 1.0
    assert got[0].shape == (2, heads, s_len, d) and got[0].dtype == x.dtype
    assert got[1].shape == x.shape and got[2].shape == (d,)
    tol = 1e-5 if dtype == "float32" else 2.0 ** -7
    for g, w in zip(got, want):
        g, w = onp.asarray(g, "f"), onp.asarray(w, "f")
        onp.testing.assert_allclose(g / onp.abs(w).max(),
                                    w / onp.abs(w).max(), atol=tol)


def test_the_position_free_form_is_the_unrotated_norm(mode):
    """Against the formula itself, and off the kernels too: with
    ``positions=None`` the result is rms_norm of each head, head-major;
    neither a norm nor positions is refused."""
    x, gamma, _ = _operands(1, 16, 2, 128, "float32")
    heads = onp.asarray(x).reshape(1, 16, 2, 128)
    want = (heads / onp.sqrt((heads ** 2).mean(-1, keepdims=True) + EPS)
            * onp.asarray(gamma)).transpose(0, 2, 1, 3)
    for value in (True, None):
        mode(value)
        got = qp.rms_norm_rotary(x, gamma, None, THETA, 2, EPS)
        onp.testing.assert_allclose(onp.asarray(got), want, rtol=2e-5,
                                    atol=2e-6)
    with pytest.raises(ValueError, match="neither"):
        qp.rms_norm_rotary(x, None, None, THETA, 2, EPS)
