"""64-bit dtype contract (VERDICT r4 missing #4).

Policy: explicit float64/int64 requests are HONORED (x64 enabled at
package import — reference: mshadow DType templates support real 64-bit
compute), while every creation default stays float32/int32 exactly like
the reference's defaults. `npx.set_np(dtype=True)` switches creation
defaults to official-numpy (float64/int64), mirroring
reference numpy/multiarray.py:7004.
"""
import numpy as onp

import pytest

import mxnet_tpu as mx
from mxnet_tpu import npx


@pytest.mark.parametrize("dtype", ["float64", "int64"])
def test_explicit_64bit_creation_honored(dtype):
    a = mx.np.ones((2, 3), dtype=dtype)
    assert str(a.dtype) == dtype
    b = mx.nd.zeros((2,), dtype=dtype)
    assert str(b.dtype) == dtype
    c = mx.np.array([1, 2], dtype=dtype)
    assert str(c.dtype) == dtype


def test_astype_64bit_honored():
    a = mx.nd.ones((4,))
    assert str(a.astype("int64").dtype) == "int64"
    assert str(a.astype("float64").dtype) == "float64"


def test_float64_compute_is_real_float64():
    # 1e-12 is representable at f64 (eps~2.2e-16) but vanishes at f32
    a = mx.np.array([1e-12, 1.0], dtype="float64")
    assert float(a.sum()) != 1.0
    f32 = mx.np.array([1e-12, 1.0], dtype="float32")
    assert float(f32.sum()) == 1.0


def test_int64_compute_beyond_int32_range():
    big = mx.np.array([2**40], dtype="int64")
    assert int((big + 1).asnumpy()[0]) == 2**40 + 1
    assert str((big * 2).dtype) == "int64"


def test_shape_array_int64_contract():
    # reference: matrix_op.cc shape_array outputs int64
    s = mx.nd.shape_array(mx.nd.ones((2, 3)))
    assert str(s.dtype) == "int64"
    assert s.asnumpy().tolist() == [2, 3]
    assert str(mx.nd.size_array(mx.nd.ones((2, 3))).dtype) == "int64"


def test_defaults_stay_32bit():
    assert str(mx.np.ones((2,)).dtype) == "float32"
    assert str(mx.nd.array([1.0, 2.0]).dtype) == "float32"
    assert str(mx.np.random.uniform(size=(2,)).dtype) == "float32"
    assert str(mx.np.arange(3).dtype) == "float32"  # ref: f32 even for ints
    assert str(mx.nd.arange(3).dtype) == "float32"  # ref: mx_real_t
    assert str(mx.np.array(onp.random.rand(2)).dtype) == "float32"


def test_nd_arange_repeat():
    # reference ndarray.py:3510 example
    out = mx.nd.arange(2, 6, step=2, repeat=3)
    assert out.asnumpy().tolist() == [2.0, 2.0, 2.0, 4.0, 4.0, 4.0]


def test_set_np_dtype_switches_defaults():
    npx.set_np(dtype=True)
    try:
        assert npx.is_np_default_dtype()
        assert str(mx.np.arange(3).dtype) == "int64"
    finally:
        npx.set_np()
    assert not npx.is_np_default_dtype()
    assert str(mx.np.arange(3).dtype) == "float32"


def test_64bit_checkpoint_roundtrip(tmp_path):
    a = mx.nd.array(onp.arange(5), dtype="int64")
    b = mx.nd.array([1e-12, 1.0], dtype="float64")
    path = str(tmp_path / "x64.params")
    mx.nd.save(path, {"a": a, "b": b})
    mx.waitall()
    loaded = mx.nd.load(path)
    assert str(loaded["a"].dtype) == "int64"
    assert str(loaded["b"].dtype) == "float64"
    assert float(loaded["b"].asnumpy().sum()) != 1.0


def test_binary_promotion_with_64bit():
    a64 = mx.np.ones((2,), dtype="float64")
    a32 = mx.np.ones((2,), dtype="float32")
    assert str((a64 + a32).dtype) == "float64"
    i64 = mx.np.ones((2,), dtype="int64")
    assert str((i64 + 1).dtype) == "int64"


def test_gradient_flows_in_float64():
    a = mx.np.array([2.0, 3.0], dtype="float64")
    a.attach_grad()
    with mx.autograd.record():
        y = (a * a).sum()
    y.backward()
    assert str(a.grad.dtype) == "float64"
    assert a.grad.asnumpy().tolist() == [4.0, 6.0]


def test_nd_save_synchronous_on_return(tmp_path):
    # reference: MXNDArraySave returns with the file on disk (c_api.cc);
    # VERDICT r4 weak #2 — no waitall required before an external stat
    import os

    path = str(tmp_path / "sync.params")
    mx.nd.save(path, {"w": mx.nd.ones((256, 256))})
    assert os.path.exists(path)  # NO mx.waitall() before this stat
    assert mx.nd.load(path)["w"].shape == (256, 256)


def test_random_sampler_32bit_defaults():
    # code-review r5: x64 must not leak f64/i64 through dtype-less
    # jax.random call sites (~50 across the frontends); the _jax_defaults
    # shim pins the public samplers
    from mxnet_tpu.gluon import probability as prob

    n = prob.Normal(mx.np.zeros((3,)), mx.np.ones((3,)))
    assert str(n.sample().dtype) == "float32"
    g = prob.Gamma(mx.np.ones((3,)), mx.np.ones((3,)))
    assert str(g.sample().dtype) == "float32"
    c = prob.Categorical(num_events=4,
                         prob=mx.np.ones((4,)) / 4)
    s = c.sample()
    assert "int" in str(s.dtype) or str(s.dtype) == "float32"
    assert str(mx.nd.random_normal(shape=(3,)).dtype) == "float32"
    assert str(mx.nd.random_uniform(shape=(3,)).dtype) == "float32"
    assert str(mx.np.random.gamma(1.0, 1.0, size=(3,)).dtype) == "float32"
    init = mx.initializer.Xavier()
    w = mx.nd.zeros((4, 4))
    init("w", w)
    assert str(w.dtype) == "float32"


def test_creation_32bit_defaults_more():
    assert str(mx.np.full((2, 2), 3.14).dtype) == "float32"
    assert str(mx.np.full((2, 2), 7).dtype) == "int32"
    assert str(mx.np.full((2, 2), 3.14, dtype="float64").dtype) == "float64"
    # python int lists default to FLOAT32 (reference ndarray.py array:
    # 'float32 otherwise'; test_numpy_default_dtype.py pins it)
    assert str(mx.nd.array([0, 1, 2]).dtype) == "float32"
    assert str(mx.np.array([1, 2, 3]).dtype) == "float32"
    assert str(mx.nd.array([0, 1, 2], dtype="int64").dtype) == "int64"
    assert str(mx.nd.array([0, 1, 2], dtype="int32").dtype) == "int32"
    import numpy as onp

    # explicit 64-bit numpy input + explicit dtype keeps 64-bit
    assert str(mx.nd.array(onp.zeros(2, onp.int64),
                           dtype="int64").dtype) == "int64"
    # vision grid generator stays in the data dtype
    theta = mx.nd.array(onp.tile(onp.eye(2, 3, dtype="float32"), (2, 1, 1)))
    out = mx.nd.GridGenerator(theta, transform_type="affine",
                              target_shape=(4, 4))
    assert str(out.dtype) == "float32"
    # multibox_prior anchors stay f32
    x = mx.nd.zeros((1, 3, 8, 8))
    anchors = mx.nd.contrib.MultiBoxPrior(x, sizes=[0.5], ratios=[1.0])
    assert str(anchors.dtype) == "float32"


def test_np_default_dtype_mode_port():
    # reference: tests/python/unittest/test_numpy_default_dtype.py —
    # deep-np default f32, np-default mode f64, for the creation corpus
    from mxnet_tpu import npx

    fns = {
        "array": lambda: mx.np.array([1, 2, 3]),
        "ones": lambda: mx.np.ones((5,)),
        "zeros": lambda: mx.np.zeros(5),
        "eye": lambda: mx.np.eye(3),
        "identity": lambda: mx.np.identity(3),
        "linspace": lambda: mx.np.linspace(0, 1, 5),
        "logspace": lambda: mx.np.logspace(0, 1, 5),
        "hanning": lambda: mx.np.hanning(5),
        "hamming": lambda: mx.np.hamming(5),
        "blackman": lambda: mx.np.blackman(5),
        "random.uniform": lambda: mx.np.random.uniform(size=(3,)),
        "random.normal": lambda: mx.np.random.normal(size=(3,)),
        "random.gamma": lambda: mx.np.random.gamma(1.0, 1.0, size=(3,)),
        "mean": lambda: mx.np.mean(mx.np.ones((3,))),
        "true_divide": lambda: mx.np.true_divide(
            mx.np.array([1, 2]), mx.np.array([2, 2])),
    }
    for name, fn in fns.items():
        assert str(fn().dtype) == "float32", (name, fn().dtype)
    npx.set_np(dtype=True)
    try:
        for name in ("array", "ones", "zeros", "eye", "identity",
                     "linspace", "logspace", "hanning",
                     "random.uniform", "random.normal", "random.gamma"):
            assert str(fns[name]().dtype) == "float64", name
        # indices is int64 in BOTH modes (reference)
        assert str(mx.np.indices((3,)).dtype) == "int64"
        assert str(mx.np.arange(3, 7, 2).dtype) == "int64"
    finally:
        npx.set_np()
    assert str(mx.np.indices((3,)).dtype) == "int64"
    assert str(mx.np.arange(3, 7, 2).dtype) == "float32"


def test_float_index_arrays_work_everywhere():
    # code-review r5: default-created (float32) index arrays must index
    # like the reference (indexing_op.h casts); bool masks unaffected
    x = mx.nd.array([[1.0, 2.0], [3.0, 4.0]])
    idx = mx.nd.array([0, 1])  # float32 now
    assert x[idx].shape == (2, 2)
    x[idx] = 0.0
    assert float(x.asnumpy().sum()) == 0.0
    # method keeps numpy semantics: axis=None flattens (crash-free is
    # the contract here — lists/ints must not hit the dtype guard)
    assert x.take([0, 1]).shape == (2,)
    assert x.take(1).shape == ()
    assert x.take([0, 1], axis=0).shape == (2, 2)
    mask = mx.np.array([True, False, True])
    got = mx.npx.index_update(mx.np.array([1.0, 2.0, 3.0]), mask, 9.0)
    assert got.asnumpy().tolist() == [9.0, 2.0, 9.0]


def test_tri_positional_dtype():
    # np.tri(3, 3, 0, 'int32') is legal numpy spelling
    assert str(mx.np.tri(3, 3, 0, "int32").dtype) == "int32"
    assert str(mx.np.tri(3).dtype) == "float32"


# -- the contract must not leak 64-bit MATH into compiled training programs --
# (a TPU emulates f64: found on the chip by chip_smoke.py, PR 22)

def _eqn_dtypes(jaxpr, prims):
    """(primitive, out dtype) of every equation named in `prims`, nested
    call bodies included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in prims:
            out.append((eqn.primitive.name, str(eqn.outvars[0].aval.dtype)))
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _eqn_dtypes(getattr(inner, "jaxpr", inner), prims)
    return out


def test_dropout_mask_draws_32bit_uniforms():
    """`bernoulli(key, 0.9)`: a Python-float p is a float64 under x64 and
    used to draw 64-bit random bits for every Dropout; an explicit
    float64 p still does."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(0)
    jp = jax.make_jaxpr(lambda k: jax.random.bernoulli(k, 0.9, (8, 16)))(key)
    bits = _eqn_dtypes(jp.jaxpr, ("random_bits",))
    assert bits and all(dt == "uint32" for _, dt in bits), bits
    jp = jax.make_jaxpr(lambda k: jax.random.bernoulli(
        k, jnp.float64(0.9), (8, 16)))(key)
    assert ("random_bits", "uint64") in _eqn_dtypes(jp.jaxpr,
                                                    ("random_bits",))


@pytest.mark.parametrize("name", ["Adam", "AdamW", "Nadam", "Adamax",
                                  "LAMB"])
def test_adam_family_rules_trace_no_64bit_transcendentals(name):
    """lr / wd / t / betas reach a fused rule as traced Python scalars
    (weak float64 / int64 under x64); the bias correction must not become
    f64 pow / log / exp / sqrt / div — per parameter that made BERT-base's
    whole step take over 20 minutes to compile for the chip."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.optimizer.optimizer import Optimizer

    cls = getattr(opt_mod, name)
    opt = cls()
    w = jnp.ones((8, 128), jnp.float32)
    st = jax.tree_util.tree_map(
        lambda s: s._data if hasattr(s, "_data") else s,
        opt.create_state(0, mx.nd.array(onp.ones((8, 128), "float32"))),
        is_leaf=lambda s: hasattr(s, "_data"))
    hyper = dict(opt._hyper(), rescale_grad=1.0)

    def step(w, st, g, lr, wd, t, hyper):
        return Optimizer._fused_param_step(cls, None, False, False, w, st,
                                           g, lr, wd, t, 1.0, hyper)

    jp = jax.make_jaxpr(step)(w, st, w, 1e-3, 0.01, 3, hyper)
    heavy = _eqn_dtypes(jp.jaxpr, ("pow", "log", "exp", "sqrt", "div",
                                   "tanh", "rsqrt", "integer_pow"))
    assert heavy, "rule traced no math at all?"
    assert all(dt != "float64" for _, dt in heavy), heavy
