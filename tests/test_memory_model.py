"""passes/memory.py analytic byte-model edge cases: empty programs, the
1 MiB widening-convert fusion-root boundary in estimate_region_bytes,
liveness freeing in estimate_peak_bytes and call-primitive inlining —
the numbers the remat `auto` policy and the CostDB drift auditor read.
"""
import jax
import jax.numpy as jnp

from mxnet_tpu.passes import memory as pmem


def _regions(fn, *args, **kw):
    return pmem.estimate_region_bytes(jax.make_jaxpr(fn)(*args), **kw)


# -- degenerate programs -----------------------------------------------------

def test_identity_program_has_no_regions():
    x = jnp.ones((4, 4), jnp.float32)
    closed = jax.make_jaxpr(lambda x: x)(x)
    assert closed.jaxpr.eqns == []
    assert pmem.estimate_region_bytes(closed) == []
    # peak = the pinned input/output buffer, nothing else
    assert pmem.estimate_peak_bytes(closed) == 4 * 4 * 4


def test_zero_element_operands_cost_zero():
    x = jnp.ones((0, 8), jnp.float32)
    closed = jax.make_jaxpr(lambda x: x + 1.0)(x)
    assert pmem.estimate_peak_bytes(closed) == 0
    for r in pmem.estimate_region_bytes(closed):
        assert r["external_bytes"] == 0


def test_no_argument_program():
    closed = jax.make_jaxpr(lambda: jnp.zeros((8,), jnp.float32) + 1.0)()
    assert pmem.estimate_peak_bytes(closed) >= 8 * 4
    assert isinstance(pmem.estimate_region_bytes(closed), list)


# -- dtype widths ------------------------------------------------------------

def test_aval_bytes_respects_dtype_width():
    for dtype, itemsize in ((jnp.float32, 4), (jnp.bfloat16, 2),
                            (jnp.int8, 1)):
        x = jnp.zeros((512,), dtype)
        closed = jax.make_jaxpr(lambda x: x)(x)
        assert pmem.estimate_peak_bytes(closed) == 512 * itemsize


# -- the widening-convert fusion-root boundary -------------------------------

def test_widen_threshold_boundary_exact():
    """A bf16→f32 convert producing EXACTLY 1 MiB is a fusion root at
    the default threshold (out bytes >= threshold) and fuses one byte
    above it — the audit's empirical f32-materialization boundary."""
    x = jnp.ones((512, 512), jnp.bfloat16)  # f32 out: 512*512*4 = 1 MiB

    def fn(x):
        return x.astype(jnp.float32) * 2.0

    at = _regions(fn, x, widen_threshold=1 << 20)
    above = _regions(fn, x, widen_threshold=(1 << 20) + 1)
    # root splits convert and its consumer into separate generations
    assert len(at) == 2
    assert len(above) == 1
    # the split pays the round-trip: 1 MiB crosses the boundary twice
    ext_at = sum(r["external_bytes"] for r in at)
    ext_above = sum(r["external_bytes"] for r in above)
    assert ext_at == ext_above + 2 * (1 << 20)


def test_narrowing_convert_never_roots():
    """f32→bf16 shrinks; only widening converts mark the boundary."""
    x = jnp.ones((512, 512), jnp.float32)
    regions = _regions(lambda x: x.astype(jnp.bfloat16) * jnp.bfloat16(2),
                       x, widen_threshold=1)
    assert len(regions) == 1


def test_reduce_is_always_a_root():
    x = jnp.ones((256, 256), jnp.float32)
    regions = _regions(lambda x: (x * 2.0).sum() + 1.0, x)
    # mul fuses INTO the reduce root; the scalar add downstream of the
    # root output is a later generation
    assert len(regions) == 2
    prims = [set(r["prims"]) for r in regions]
    assert any("reduce_sum" in p for p in prims)


# -- liveness: intermediates free at last use --------------------------------

def test_peak_frees_dead_intermediates():
    x = jnp.ones((1024,), jnp.float32)  # 4 KiB

    def chain(x):
        y = x + 1.0
        z = y + 1.0
        return z + 1.0

    closed = jax.make_jaxpr(chain)(x)
    # pinned input + live value + value being produced = 3 buffers, not
    # 1 (input) + 3 (all intermediates kept)
    assert pmem.estimate_peak_bytes(closed) == 3 * 4096


def test_call_primitives_are_inlined():
    x = jnp.ones((64, 64), jnp.float32)

    def flat(x):
        return jnp.tanh(x) + 1.0

    def nested(x):
        return jax.jit(jnp.tanh)(x) + 1.0

    flat_peak = pmem.estimate_peak_bytes(jax.make_jaxpr(flat)(x))
    nested_peak = pmem.estimate_peak_bytes(jax.make_jaxpr(nested)(x))
    assert flat_peak == nested_peak
