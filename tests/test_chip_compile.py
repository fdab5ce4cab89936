"""Compile the main path's Pallas kernels, and a whole training step,
for a DESCRIBED TPU v5e.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached — so what Mosaic refuses (an i64 index
map, a block that does not tile, too much VMEM) fails HERE, at real
widths, at no chip time.  Interpret-mode parity
(tests/test_flash_attention.py) cannot see any of that.  A compile that
passes is not a chip run: chip_smoke.py checks the numbers on the chip.

This is the ONLY file that describes the chip, and it does so inside a
module-scoped fixture: only one process may hold the TPU library, and
pytest-xdist runs no test at all when workers collect different tests —
so nothing here touches the topology while a module is imported (no
top-level call, no skipif condition, no parametrize argument), and the
compiles run in the test's own process.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu  # noqa: F401  (x64 on: the contract the kernels escape)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology executable can be written to the persistent
    # cache but not read back without a chip; keep these compiles out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _kernel_calls(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


class _Lowered(Exception):
    pass


def _lowered_step(step, *batch, describe=lambda operands: operands):
    """The whole step of a `gluon.TrainStep` exactly as it builds it,
    lowered instead of run: the step's own jitted function is caught at
    its first call and lowered on what ``describe`` makes of the operands
    it was handed."""
    jitted = step._jitted

    def intercept(donate):
        fn = jitted(donate)

        def lower_only(*a):
            raise _Lowered(fn.lower(*describe(a)))
        return lower_only

    step._jitted = intercept
    with pytest.raises(_Lowered) as caught:
        step(*batch)
    return caught.value.args[0]


def _compiled_step(step, one_chip, *batch):
    """`_lowered_step` on the shapes of the operands, compiled for the
    described chip."""
    def describe(operands):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                           sharding=one_chip)
            if hasattr(v, "shape") else v, operands)

    return _lowered_step(step, *batch, describe=describe).compile()


# -- flash attention: BERT-base heads at the SQuAD and the 512 lengths ------

@pytest.mark.parametrize("seq", [384, 512])
@pytest.mark.parametrize("case", ["fwd", "fwd_causal_dropout", "grad"])
def test_flash_attention_compiles_for_v5e(spec, seq, case):
    from mxnet_tpu.ops.pallas_attention import flash_attention

    q = spec((8, 12, seq, 64), jnp.bfloat16)
    seed = spec((1,), jnp.int32)
    if case == "fwd":
        calls = _kernel_calls(
            lambda q, k, v: flash_attention(q, k, v, interpret=False),
            q, q, q)
        assert calls == 1
    elif case == "fwd_causal_dropout":
        calls = _kernel_calls(
            lambda q, k, v, s: flash_attention(
                q, k, v, causal=True, dropout_p=0.1, dropout_seed=s,
                interpret=False), q, q, q, seed)
        assert calls == 1
    else:
        def loss(q, k, v, s):
            out = flash_attention(q, k, v, dropout_p=0.1, dropout_seed=s,
                                  interpret=False)
            return out.astype(jnp.float32).sum()

        calls = _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)),
                              q, q, q, seed)
        assert calls == 2      # forward, the fused backward


# -- flash attention: SDAR's 32 query over 4 key-value heads of 128, 2 x 4096
#    positions under the block-diffusion mask, at explicit tiles of 512 and
#    at the tiles the op chooses (1024, spans of two, chunks of 512 rows) ----

def _tiles(tile):
    return {} if tile is None else {"block_q": tile, "block_k": tile}


@pytest.mark.parametrize("tile", [512, None], ids=["tile512", "chosen"])
@pytest.mark.parametrize("case", ["fwd", "grad"])
def test_grouped_block_diffusion_attention_compiles_for_v5e(spec, case, tile):
    from mxnet_tpu.ops.pallas_attention import flash_attention

    q = spec((1, 32, 8192, 128), jnp.bfloat16)
    kv = spec((1, 4, 8192, 128), jnp.bfloat16)

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, **_tiles(tile),
                               block_diffusion=(4, 4096))

    if case == "fwd":
        assert _kernel_calls(attend, q, kv, kv) == 1
    else:
        calls = _kernel_calls(jax.grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q, kv, kv)
        assert calls == 2      # forward; dQ, dK, dV summed over the group


# -- flash attention: latent attention's 32 heads with keys 192 and values
#    128 wide, 8192 positions under the causal mask, both tilings -----------

@pytest.mark.parametrize("tile", [512, None], ids=["tile512", "chosen"])
@pytest.mark.parametrize("case", ["fwd", "grad"])
def test_latent_attention_widths_compile_for_v5e(spec, case, tile):
    from mxnet_tpu.ops.pallas_attention import flash_attention

    qk = spec((1, 32, 8192, 192), jnp.bfloat16)
    v = spec((1, 32, 8192, 128), jnp.bfloat16)

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, **_tiles(tile),
                               causal=True)

    if case == "fwd":
        assert _kernel_calls(attend, qk, qk, v) == 1
    else:
        calls = _kernel_calls(jax.grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), qk, qk, v)
        assert calls == 2      # forward; dQ, dK (192 wide) and dV (128)


# -- the flash kernels' set-up (ISSUE 40): one plan and one lowered kernel a
#    signature, a plan that costs a millisecond, bodies that stay small -------

# (q, k, v shapes, mask, an earlier tree's serialised body bytes by kernel:
# 512 x 512 tiles lowered for this described v5e at commit ef34035; the
# looped cell's signature at commit af1c3c2, tiles of 1024 in chunks of 512)
_CELL_KERNELS = {
    "sdar": ((1, 32, 8192, 128), (1, 4, 8192, 128), (1, 4, 8192, 128),
             {"block_diffusion": (4, 4096)},
             {"flash_attention_fwd": 11384, "flash_attention_bwd_dq": 6720,
              "flash_attention_bwd_dkv": 7636}),
    "kanana2": ((1, 32, 8192, 192), (1, 32, 8192, 192), (1, 32, 8192, 128),
                {"causal": True},
                {"flash_attention_fwd": 10876, "flash_attention_bwd_dq": 6492,
                 "flash_attention_bwd_dkv": 7568}),
    "ouro": ((1, 16, 8192, 128), (1, 16, 8192, 128), (1, 16, 8192, 128),
             {"causal": True},
             {"flash_attention_fwd": 13888, "flash_attention_bwd_dq": 8348,
              "flash_attention_bwd_dkv": 9408}),
}


def _lowered_kernels(pa, spec, cell, layers):
    """(module text, {kernel name: serialised body bytes}) of ``layers``
    layers at a cell's shapes, forward and backward, lowered for the
    described v5e."""
    import re

    qs, ks, vs, mask, _ = _CELL_KERNELS[cell]

    def loss(q, k, v):
        total = 0.0
        for i in range(layers):
            with jax.named_scope(f"layer{i}"), jax.named_scope("attention"):
                out = pa.flash_attention(q, k, v, interpret=False, **mask)
            total = total + out.astype(jnp.float32).sum()
            q = q + out.mean().astype(q.dtype)
        return total

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        spec(qs, jnp.bfloat16), spec(ks, jnp.bfloat16),
        spec(vs, jnp.bfloat16)).as_text()
    calls = re.findall(r"stablehlo.custom_call @tpu_custom_call.*", text)
    names = [re.search(r'kernel_name = "([^"]+)"', c).group(1) for c in calls]
    assert len(set(names)) == len(names), names
    return text, {n: len(re.search(r'backend_config = "([^"]*)"', c).group(1))
                  for n, c in zip(names, calls)}


@pytest.mark.parametrize("cell", sorted(_CELL_KERNELS))
def test_layers_of_one_signature_share_the_plan_and_the_kernels(
        spec, cell, monkeypatch):
    """Four layers at a cell's shapes, forward and backward, lowered for the
    described v5e: the plan is built once and fuses the backward, the
    module holds two Mosaic calls (three before the backward was one
    kernel, three a layer before PR 40), the forward's serialised body is
    under twice the earlier tree's bytes and the fused body under the sum
    of the two it replaces — the same tree's dQ and dK/dV bodies, lowered
    for a core whose fast memory holds no head, which stay under twice the
    earlier tree's (with the statistics' turns of PR 53: dQ turns lse,
    makes delta and stores it; dK/dV turns lse and delta a q unit of the
    span it streams in one rolled loop over the span)."""
    from mxnet_tpu.ops import pallas_attention as pa

    earlier = _CELL_KERNELS[cell][4]
    layers, built = 4, []
    init = pa._Plan.__init__
    monkeypatch.setattr(
        pa._Plan, "__init__",
        lambda self, *a: (init(self, *a), built.append(self))[0])
    pa._plan.cache_clear()
    pa._shared.cache_clear()
    text, fused = _lowered_kernels(pa, spec, cell, layers)
    assert len(built) == 1 and built[0].fused
    assert built[0].vmem_limit < pa._V5E_VMEM == pa._vmem_capacity()
    assert sorted(fused) == ["flash_attention_bwd", "flash_attention_fwd"]
    assert fused["flash_attention_fwd"] < 2 * earlier["flash_attention_fwd"]
    # every layer still calls its kernels under its own scope
    assert text.count("call @flash_fwd_call") == layers
    assert text.count("call @flash_bwd_call") == layers

    monkeypatch.setattr(pa, "_vmem_capacity", lambda: 0)
    pa._plan.cache_clear()
    pa._shared.cache_clear()
    _, two = _lowered_kernels(pa, spec, cell, 1)
    pa._plan.cache_clear()
    pa._shared.cache_clear()
    assert len(built) == 2 and not built[1].fused
    assert sorted(two) == sorted(earlier)
    for name, size in two.items():
        assert size < 2 * earlier[name], (name, size)
    assert fused["flash_attention_bwd"] < (
        two["flash_attention_bwd_dq"] + two["flash_attention_bwd_dkv"])


@pytest.mark.parametrize("seq,fused", [(16384, True), (32768, False)],
                         ids=["16k_fused", "32k_two_kernels"])
def test_the_memory_plan_at_long_sequences_compiles_for_v5e(spec, seq,
                                                             fused):
    """Keys 192 and values 128 wide: 16k positions are the longest power of
    two whose head (keys, values, both gradients and their float32 sums)
    the plan holds in the v5e's fast memory, and Mosaic takes the limit it
    asks for; at 32k the plan is the dQ and the dK/dV kernel."""
    from mxnet_tpu.ops import pallas_attention as pa

    qk = spec((1, 2, seq, 192), jnp.bfloat16)
    v = spec((1, 2, seq, 128), jnp.bfloat16)
    pa._plan.cache_clear()
    plan = pa._plan_of(qk, qk, v, True, 1024, 1024, None, None)
    assert plan.fused == fused
    calls = _kernel_calls(jax.grad(
        lambda *a: pa.flash_attention(*a, interpret=False, causal=True)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)), qk, qk, v)
    pa._plan.cache_clear()
    assert calls == (2 if fused else 3)


@pytest.mark.parametrize("mask", [{"causal": True},
                                  {"block_diffusion": (4, 4096)}],
                         ids=["causal", "block_diffusion"])
def test_the_plan_for_8192_positions_costs_a_millisecond(mask):
    """Codes, the schedule, classes, the memory plan and the gauges for
    8192 positions: well under 50 ms on the host, and no (S, S) array on
    the way (the dense mask would be 64 MiB of booleans; the plan's peak
    is the codes in 64 bits)."""
    import time
    import tracemalloc

    from mxnet_tpu.ops import pallas_attention as pa

    shape = (2, 32, 8192, 128)
    args = (shape, shape, shape, "bfloat16", mask.get("causal", False),
            1024, 1024, None, mask.get("block_diffusion"))
    pa._plan.cache_clear()
    pa._plan(*args)                              # imports, first numpy calls
    pa._plan.cache_clear()
    tracemalloc.start()
    t = time.perf_counter()
    plan = pa._plan(*args)
    seconds = time.perf_counter() - t
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert seconds < 0.05, seconds
    assert peak < 4 * 2 ** 20, peak
    assert plan.rows[:3] == (1024, 1024, 2) and plan.fused
    assert pa._plan(*args) is plan
    pa._plan.cache_clear()


# -- the kernels' row statistics (ISSUE 53): lse lane-dense, delta made in
#    the backward kernel — no (heads, S, 1) float32 column, 128-fold padding
#    in HBM, is an operand or a result of a flash call or lives beside one ---

def _shapes(text):
    """Every array type printed in a piece of HLO text, as (type, dims)."""
    import re

    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in re.findall(r"\b(f32|bf16|s32)\[([\d,]*)\]", text)]


def _call_shapes(text, line):
    """(type, dims) of every result and every operand of the instruction
    on ``line`` of a compiled module's ``text`` (which prints an operand
    by name: its type is where the name is defined)."""
    import re

    types = {}
    for l in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*?) [\w\-]+\(", l)
        if m:
            types[m.group(1)] = _shapes(m.group(2))
    m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\((.*?)\), ", line)
    operands = re.findall(r"%[\w.\-]+", m.group(2))
    return _shapes(m.group(1)) + [t for o in operands for t in types[o]]


def _columns_under_attention(text, s_len):
    """Lines of a compiled step under the ``attention`` scope that hold a
    float32 (heads, S, 1) array."""
    return [l for l in text.splitlines() if "/attention/" in l and any(
        t == "f32" and len(d) == 3 and d[1:] == (s_len, 1)
        for t, d in _shapes(l.split("metadata=")[0]))]


# (q, k, v shapes, mask) of the five decoder cells' flash calls
_CELL_SIGNATURES = {
    "sdar": ((2, 32, 8192, 128), (2, 4, 8192, 128), (2, 4, 8192, 128),
             {"block_diffusion": (4, 4096)}),
    "kanana2": ((2, 32, 8192, 192), (2, 32, 8192, 192), (2, 32, 8192, 128),
                {"causal": True}),
    "ouro": ((1, 16, 8192, 128), (1, 16, 8192, 128), (1, 16, 8192, 128),
             {"causal": True}),
    "lfm2": ((2, 32, 8192, 64), (2, 8, 8192, 64), (2, 8, 8192, 64),
             {"causal": True}),
    "trinity_window": ((1, 32, 16384, 128), (1, 4, 16384, 128),
                       (1, 4, 16384, 128), {"window": 2048}),
    "trinity_global": ((1, 32, 16384, 128), (1, 4, 16384, 128),
                       (1, 4, 16384, 128), {"causal": True}),
}


@pytest.mark.parametrize("cell", sorted(_CELL_SIGNATURES))
def test_no_flash_call_takes_or_gives_a_padded_column(spec, cell):
    """A decoder cell's flash signature, forward and backward under the
    ``attention`` scope, compiled for the described v5e: two Mosaic calls,
    none with an operand or a result whose trailing dimension is 1 (the
    seed, one int32, apart), lse as one dense (8, 128) float32 tile a q
    tile of 1024, and no float32 (heads, S, 1) array anywhere in the
    program — the four XLA passes a layer that moved such columns are
    gone with them."""
    from mxnet_tpu.ops import pallas_attention as pa

    qs, ks, vs, mask = _CELL_SIGNATURES[cell]

    def loss(q, k, v):
        with jax.named_scope("attention"):
            out = pa.flash_attention(q, k, v, interpret=False, **mask)
        return out.astype(jnp.float32).sum()

    pa._plan.cache_clear()
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        spec(qs, jnp.bfloat16), spec(ks, jnp.bfloat16),
        spec(vs, jnp.bfloat16)).compile().as_text()
    pa._plan.cache_clear()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l
             and " custom-call(" in l and "flash_attention" in l]
    assert len(calls) == 2
    heads, s_len = qs[0] * qs[1], qs[2]
    for line in calls:
        shapes = _call_shapes(text, line)
        assert len(shapes) >= 10            # results and operands, resolved
        assert [d for t, d in shapes if d[-1:] == (1,) and len(d) > 1] == []
        assert shapes.count(("f32", (heads, s_len // 1024, 8, 128))) == 1
    assert not _columns_under_attention(text, s_len)
    assert not [d for t, d in _shapes(text)
                if t == "f32" and len(d) == 3 and d[1:] == (s_len, 1)]


# -- query / key preparation (ISSUE 42): per-head norm, rotary positions and
#    the head-major store as one kernel, its backward as one more -----------

def _qk_prep_loss(heads, layers=1):
    from mxnet_tpu.ops import pallas_qk_prep as qp

    def loss(x, gamma, positions):
        total = 0.0
        for i in range(layers):
            with jax.named_scope(f"layer{i}"):
                out = qp.rms_norm_rotary(x, gamma, positions, 1e6, heads)
            total = total + out.astype(jnp.float32).sum()
            x = x + out.mean().astype(x.dtype)
        return total

    return loss


@pytest.mark.parametrize("heads,seq,dtype", [
    (32, 8192, jnp.bfloat16),      # SDAR's queries
    (4, 8192, jnp.bfloat16),       # ... and keys
    (4, 8200, jnp.bfloat16),       # the last block hangs over the sequence
    (2, 24, jnp.bfloat16),         # one block, not whole bf16 sublane tiles
    (2, 2056, jnp.float32),
], ids=["q", "k", "edge", "short", "float32"])
def test_query_key_preparation_compiles_for_v5e(spec, monkeypatch, heads,
                                                seq, dtype):
    from mxnet_tpu.ops import pallas_qk_prep as qp

    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    x = spec((2, seq, heads * 128), dtype)
    gamma, pos = spec((128,), jnp.float32), spec((seq,), jnp.int32)
    assert _kernel_calls(_qk_prep_loss(heads), x, gamma, pos) == 1
    assert _kernel_calls(jax.value_and_grad(_qk_prep_loss(heads), (0, 1)),
                         x, gamma, pos) == 2


def test_layers_share_one_copy_of_each_preparation_kernel(spec, monkeypatch):
    """Four layers at the cell's query shape, forward and backward, lowered
    for the described v5e: two Mosaic calls in the module, not two a
    layer, and every layer calls them."""
    import re

    from mxnet_tpu.ops import pallas_qk_prep as qp

    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    layers = 4
    step = jax.jit(jax.value_and_grad(_qk_prep_loss(32, layers), (0, 1)))
    text = step.lower(
        spec((2, 8192, 4096), jnp.bfloat16), spec((128,), jnp.float32),
        spec((8192,), jnp.int32)).as_text()
    names = re.findall(r'stablehlo.custom_call @tpu_custom_call.*'
                       r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == ["rms_norm_rotary_bwd", "rms_norm_rotary_fwd"]
    assert text.count("call @qk_prep_fwd_call") == layers
    assert text.count("call @qk_prep_bwd_call") == layers


# -- a looped decoder (ISSUE 43): 16 heads of 128 under the causal mask, the
#    rotation without a per-head norm, and the whole rolled step --------------

@pytest.mark.parametrize("case", ["fwd", "grad"])
def test_multi_head_causal_attention_compiles_for_v5e(spec, case):
    """The flash kernels' third signature among the cells: 16 query and 16
    key-value heads, keys and values 128 wide, 8192 positions, causal."""
    from mxnet_tpu.ops.pallas_attention import flash_attention

    q = spec((1, 16, 8192, 128), jnp.bfloat16)

    def attend(q, k, v):
        return flash_attention(q, k, v, interpret=False, causal=True)

    if case == "fwd":
        assert _kernel_calls(attend, q, q, q) == 1
    else:
        calls = _kernel_calls(jax.grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q, q, q)
        assert calls == 2      # forward, the fused backward


@pytest.mark.parametrize("heads,seq,dtype", [
    (16, 8192, jnp.bfloat16),      # the looped cell's queries and keys
    (16, 8200, jnp.bfloat16),      # the last block hangs over the sequence
    (2, 24, jnp.bfloat16),         # one block, not whole bf16 sublane tiles
    (2, 2056, jnp.float32),
], ids=["qk", "edge", "short", "float32"])
def test_norm_free_preparation_compiles_for_v5e(spec, monkeypatch, heads,
                                                seq, dtype):
    """`rms_norm_rotary(x, None, ...)`: the rotation and the head-major
    store through the same two kernels with the norm compiled out; the
    backward reads the cotangent alone and has no dgamma."""
    from mxnet_tpu.ops import pallas_qk_prep as qp

    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    x, pos = spec((1, seq, heads * 128), dtype), spec((seq,), jnp.int32)

    def loss(x, positions):
        out = qp.rms_norm_rotary(x, None, positions, 1e6, heads)
        return out.astype(jnp.float32).sum()

    assert _kernel_calls(loss, x, pos) == 1
    assert _kernel_calls(jax.value_and_grad(loss), x, pos) == 2


def test_the_looped_cells_whole_step_compiles_for_v5e(one_chip, monkeypatch):
    """The looped cell's step as gluon.TrainStep builds it — every
    published width, 8192 positions, 4 loop steps, the whole vocabulary,
    bf16 under Adam with masters, remat; ONE of its six layers — compiled
    for the described v5e: the passes are one rolled loop (a layer's
    eight Mosaic calls once, not once a loop step: flash forward and the
    fused backward, and the rotation of q and k forward, replayed and back),
    the four exits'
    logits go by blocks, and arguments and temporaries fit the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo.ouro import ouro
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops import pallas_qk_prep as qp

    kernel = pa.flash_attention
    monkeypatch.setattr(pa, "flash_attention", lambda *a, **kw: kernel(
        *a, **{**kw, "interpret": False}))
    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    net = ouro(49152, 2048, 1, 16, 16, 128, 5632, 4, remat=True)
    net.initialize(init=mx.initializer.Zero())
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": 1e-5, "multi_precision": True}, kvstore="tpu_dist")
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    compiled = _compiled_step(step, one_chip,
                              mx.np.zeros((1, 8192), dtype="int32"))
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    assert "f64[" not in text
    # no (.., 8192, 49152) float32 logits anywhere: blocks of 256 positions
    assert "f32[4,8192,49152]" not in text and "f32[1,8192,49152]" not in text
    assert "f32[4,256,49152]" in text
    # the kernels' row statistics: no (heads, S, 1) column (ISSUE 53)
    assert not _columns_under_attention(text, 8192)
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 12 * 2 ** 30


# -- a conv-attention hybrid (ISSUE 47): 64-wide heads, 32 query over 8
#    key-value heads; the short convolution's mix; the whole step ------------

@pytest.mark.parametrize("case", ["fwd", "grad"])
def test_grouped_causal_attention_at_64_wide_heads_compiles_for_v5e(
        spec, case):
    """The flash kernels' fourth signature among the cells: 32 query heads
    over 8 key-value heads, keys and values 64 wide — half a lane, planned
    as a whole one — 8192 positions, causal.  The plan has no reason to
    leave the kernels (no fallback recorded) and the backward is the one
    fused kernel, inside its fast-memory limit."""
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.telemetry import instruments as ti

    q = spec((2, 32, 8192, 64), jnp.bfloat16)
    kv = spec((2, 8, 8192, 64), jnp.bfloat16)
    before = {k: c.value for k, c in
              ti.attention_kernel_fallback_total.series()}

    def attend(q, k, v):
        return pa.flash_attention(q, k, v, interpret=False, causal=True)

    if case == "fwd":
        assert _kernel_calls(attend, q, kv, kv) == 1
    else:
        calls = _kernel_calls(jax.grad(
            lambda *a: attend(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), q, kv, kv)
        assert calls == 2      # forward, the fused backward
    assert before == {k: c.value for k, c in
                      ti.attention_kernel_fallback_total.series()}
    plan = pa._plan((2, 32, 8192, 64), (2, 8, 8192, 64), (2, 8, 8192, 64),
                    "bfloat16", True, *(2 * [pa._choose_tile(
                        8192, 64, 64, 2)]), None, None)
    assert plan.fused and plan.group == 4
    # what a 64-wide head is planned as is what a 128-wide one is
    assert pa._working_set(1024, 1024, 0, 64, 64, 2, resident=8192) == \
        pa._working_set(1024, 1024, 0, 128, 128, 2, resident=8192)


@pytest.mark.parametrize("heads", [32, 8], ids=["q", "k"])
def test_preparation_at_64_wide_heads_takes_the_kernels(spec, monkeypatch,
                                                        heads):
    """`rms_norm_rotary` at D = 64, the hybrid cell's query and key shapes:
    a 128-lane block read as two heads.  One Mosaic call forward and one
    back a site, no 64-bit value in the program, and nothing float32 of
    the tensor's size among the backward's temporaries: what it holds is
    the head-major result (lane-padded, twice its data) and the tables."""
    import re

    from mxnet_tpu.ops import pallas_qk_prep as qp
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    x = spec((2, 8192, heads * 64), jnp.bfloat16)
    g, pos = spec((64,), jnp.float32), spec((8192,), jnp.int32)

    def loss(x, gamma, positions):
        out = qp.rms_norm_rotary(x, gamma, positions, 1e6, heads, 1e-5)
        return out.astype(jnp.float32).sum()

    assert _kernel_calls(loss, x, g, pos) == 1
    assert ti._qk_prep_sites == [1, 1]
    assert ti.qk_prep_kernel_share.value == 1.0
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        x, g, pos).compile()
    text = compiled.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'(rms_norm_rotary_\w+?)/', text)
    assert sorted(names) == ["rms_norm_rotary_bwd", "rms_norm_rotary_fwd"]
    assert "f64[" not in text and "s64[" not in text
    padded = 2 * heads * 8192 * 128 * 2         # the result, a lane a head
    tables = 2 * 8192 * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < padded + tables + 2 ** 20


def test_layers_share_one_copy_of_each_packed_preparation_kernel(
        spec, monkeypatch):
    """Two layers at each of the hybrid cell's head counts: four Mosaic
    kernels in the module (a forward and a backward for 32 heads, the
    same for 8), not two a site, and every site calls them."""
    import re

    from mxnet_tpu.ops import pallas_qk_prep as qp

    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    q_loss, k_loss = _qk_prep_loss(32, 2), _qk_prep_loss(8, 2)
    step = jax.jit(jax.value_and_grad(
        lambda q, k, gamma, pos: q_loss(q, gamma, pos) + k_loss(k, gamma, pos),
        (0, 1, 2)))
    text = step.lower(
        spec((2, 8192, 32 * 64), jnp.bfloat16),
        spec((2, 8192, 8 * 64), jnp.bfloat16), spec((64,), jnp.float32),
        spec((8192,), jnp.int32)).as_text()
    names = re.findall(r'stablehlo.custom_call @tpu_custom_call.*'
                       r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == 2 * ["rms_norm_rotary_bwd"] \
        + 2 * ["rms_norm_rotary_fwd"]
    assert len(re.findall(r"call @qk_prep_fwd_call", text)) == 4
    assert len(re.findall(r"call @qk_prep_bwd_call", text)) == 4


# -- latent attention's heads (ISSUE 49): the rotary part, the shared key's
#    broadcast and the move to (B, H, S, 192 / 128) as two kernels, their
#    backward as two more ------------------------------------------------------

def _mla_heads_loss(heads, interleaved=True, layers=1):
    from mxnet_tpu.ops import pallas_mla_heads as mh

    def loss(q, kv, k_rope, positions):
        total = 0.0
        for i in range(layers):
            with jax.named_scope(f"layer{i}"):
                outs = mh.mla_heads(q, kv, k_rope, positions, 1e6, heads,
                                    interleaved)
            total = total + sum(o.astype(jnp.float32).sum() for o in outs)
            q = q + outs[0].mean().astype(q.dtype)
        return total

    return loss


def _mla_heads_specs(spec, heads, seq, dtype, nope=128, v=128):
    return (spec((2, seq, heads * (nope + 64)), dtype),
            spec((2, seq, heads * (nope + v)), dtype),
            spec((2, seq, 64), dtype), spec((seq,), jnp.int32))


@pytest.mark.parametrize("heads,seq,dtype,interleaved", [
    (32, 8192, jnp.bfloat16, True),     # the kanana-2 cell's shape
    (32, 8192, jnp.bfloat16, False),    # ... its rope lanes paired by halves
    (4, 8200, jnp.bfloat16, True),      # the last block hangs over
    (2, 24, jnp.bfloat16, True),        # one block, not whole sublane tiles
    (2, 2056, jnp.float32, True),
], ids=["cell", "halves", "edge", "short", "float32"])
def test_latent_heads_assembly_compiles_for_v5e(spec, monkeypatch, heads, seq,
                                                dtype, interleaved):
    """`mla_heads` on its kernels: two Mosaic calls forward (q; k and v),
    two more back, no 64-bit value in the program, and among the
    backward's temporaries nothing float32 of a tensor's size — what it
    holds is the three head-major results (q and k 192 wide, stored as
    256 lanes) and the tables."""
    import re

    from mxnet_tpu.ops import pallas_mla_heads as mh
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(mh, "_kernel_mode", lambda: False)
    monkeypatch.setattr(ti, "_mla_heads_sites", [0, 0])
    operands = _mla_heads_specs(spec, heads, seq, dtype)
    loss = _mla_heads_loss(heads, interleaved)
    assert _kernel_calls(loss, *operands) == 2
    assert ti._mla_heads_sites == [1, 1]
    assert ti.mla_heads_kernel_share.value == 1.0
    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        *operands).compile()
    text = compiled.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'(mla_heads_\w+?)/', text)
    assert sorted(names) == ["mla_heads_kv_bwd", "mla_heads_kv_fwd",
                             "mla_heads_q_bwd", "mla_heads_q_fwd"]
    assert "f64[" not in text and "s64[" not in text
    rows = -(-seq // 16) * 16
    padded = 2 * heads * rows * (256 + 256 + 128) * jnp.dtype(dtype).itemsize
    tables = 2 * rows * 128 * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < padded + tables + 2 ** 20


def test_layers_share_one_copy_of_each_assembly_kernel(spec, monkeypatch):
    """Five layers at the cell's shape, forward and backward, lowered for
    the described v5e: four Mosaic calls in the module, not four a layer,
    and every layer calls them."""
    import re

    from mxnet_tpu.ops import pallas_mla_heads as mh

    monkeypatch.setattr(mh, "_kernel_mode", lambda: False)
    layers = 5
    step = jax.jit(jax.value_and_grad(_mla_heads_loss(32, True, layers),
                                      (0, 1, 2)))
    text = step.lower(
        *_mla_heads_specs(spec, 32, 8192, jnp.bfloat16)).as_text()
    names = re.findall(r'stablehlo.custom_call @tpu_custom_call.*'
                       r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == ["mla_heads_kv_bwd", "mla_heads_kv_fwd",
                             "mla_heads_q_bwd", "mla_heads_q_fwd"]
    assert text.count("call @heads_fwd_call") == layers
    assert text.count("call @heads_bwd_call") == layers


def _entry_work(text):
    """The entry computation's instructions that do work."""
    entry = text[text.index("ENTRY"):]
    idle = ("parameter(", "bitcast(", "get-tuple-element(", " tuple(",
            "constant(")
    return [line for line in entry.splitlines()
            if " = " in line and not any(k in line for k in idle)]


def test_the_short_convolutions_mix_compiles_to_one_pass_for_v5e(spec):
    """`gated_short_conv` at the hybrid cell's shapes: the forward is ONE
    fusion over the tensor (and one over the taps' three columns) with no
    temporary, the backward a few, and neither holds a convolution."""
    from mxnet_tpu.ops.short_conv import gated_short_conv

    bcx = spec((2, 8192, 3 * 2048), jnp.bfloat16)
    w = spec((2048, 3), jnp.float32)
    fwd = jax.jit(gated_short_conv).lower(bcx, w).compile()
    work = _entry_work(fwd.as_text())
    assert len([l for l in work if "8192" in l]) == 1, work
    assert all(" fusion(" in l for l in work), work
    assert fwd.memory_analysis().temp_size_in_bytes < 2 ** 20

    def loss(bcx, w):
        return gated_short_conv(bcx, w).astype(jnp.float32).sum()

    bwd = jax.jit(jax.grad(loss, (0, 1))).lower(bcx, w).compile()
    text = bwd.as_text()
    assert len([l for l in _entry_work(text) if "8192" in l]) <= 4
    # what is stored between its passes is bf16, a few (B, S, D) tensors:
    # nothing float32 of the tensor's size (128 MiB each)
    assert bwd.memory_analysis().temp_size_in_bytes < 4 * 64 * 2 ** 20
    assert not [l for l in _entry_work(text) if "= f32[2,8192," in l]
    for t in (fwd.as_text(), text):
        assert " convolution(" not in t and "tpu_custom_call" not in t


def test_the_hybrid_cells_whole_step_compiles_for_v5e(one_chip, monkeypatch):
    """The hybrid cell's step as gluon.TrainStep builds it — every
    published width, 2 x 8192 positions, the 8,192 rows of a tied
    embedding, bf16 under Adam with masters, remat; THREE of its seven
    layers, one of each kind it has (convolution + dense, attention +
    experts, convolution + experts) — compiled for the described v5e.  The
    mix brings no convolution of its own (a TPU prints every matrix
    product as one, with no feature groups), the one
    attention layer is two Mosaic calls (flash forward, the fused
    backward) with no fallback, its preparation two kernels of 32 and of
    8 heads, two to a lane block (forward, replayed, backward: six Mosaic
    calls), and arguments and temporaries fit the chip.  (`chipbench/compile_check_large.py`
    compiles all seven: 8.445 GiB of arguments, 3.205 GiB of
    temporaries.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo.lfm2_moe import lfm2_moe
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops import pallas_qk_prep as qp
    from mxnet_tpu.telemetry import instruments as ti

    kernel = pa.flash_attention
    monkeypatch.setattr(pa, "flash_attention", lambda *a, **kw: kernel(
        *a, **{**kw, "interpret": False}))
    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    fallbacks = {k: c.value for k, c in
                 ti.attention_kernel_fallback_total.series()}
    net = lfm2_moe(8192, 2048, ["conv", "full_attention", "conv"], 32, 8,
                   11776, 1536, 64, 4, num_dense_layers=1, ep_size=8,
                   remat=True)
    net.initialize(init=mx.initializer.Zero())
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": 1e-5, "multi_precision": True}, kvstore="tpu_dist")
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    compiled = _compiled_step(step, one_chip,
                              mx.np.zeros((2, 8192), dtype="int32"))
    text = compiled.as_text()
    assert "f64[" not in text and "feature_group_count" not in text
    convolutions = [l for l in text.splitlines() if " convolution(" in l]
    assert convolutions and not [l for l in convolutions
                                 if "short_conv.mix" in l]
    assert all("dot_general" in l for l in convolutions)
    flash = [l for l in text.splitlines() if "tpu_custom_call" in l
             and "flash_attention" in l and " custom-call(" in l]
    assert len(flash) == 2
    assert not _columns_under_attention(text, 8192)
    prep = [l for l in text.splitlines() if "tpu_custom_call" in l
            and "rms_norm_rotary" in l and " custom-call(" in l]
    assert len(prep) == 6
    assert len([l for l in prep if "rms_norm_rotary_bwd" in l]) == 2
    assert fallbacks == {k: c.value for k, c in
                         ti.attention_kernel_fallback_total.series()}
    assert ti._qk_prep_sites == [2, 2]
    assert ti.short_conv_sites.value == 2
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("conv", "dense"): 1, ("attention", "moe"): 1, ("conv", "moe"): 1}
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 10 * 2 ** 30


# -- the window / global cell (ISSUE 51): the band, no positions, the step --

@pytest.mark.parametrize("case", ["fwd", "grad"])
@pytest.mark.parametrize("mask", [{"window": 2048}, {"causal": True}],
                         ids=["window", "global"])
def test_window_and_global_attention_at_16k_compile_for_v5e(spec, mask, case):
    """The Trinity-Mini cell's two flash signatures — 32 query heads over
    4 key-value heads of 128, 16,384 positions, bf16, under the band of
    2,048 keys and under the causal mask — at the tile the op chooses:
    one forward kernel, and ONE fused backward (a head's keys, values and
    their gradients fit the described core's fast memory at 128 / 128)."""
    from mxnet_tpu.ops import pallas_attention as pa

    q = spec((1, 32, 16384, 128), jnp.bfloat16)
    kv = spec((1, 4, 16384, 128), jnp.bfloat16)
    pa._plan.cache_clear()

    def loss(*a):
        return pa.flash_attention(*a, interpret=False, **mask).astype(
            jnp.float32).sum()

    fn = loss if case == "fwd" else jax.grad(loss, argnums=(0, 1, 2))
    calls = _kernel_calls(fn, q, kv, kv)
    plan = pa._plan_of(q, kv, kv, mask.get("causal", False), 1024, 1024,
                       None, None, mask.get("window"))
    pa._plan.cache_clear()
    assert plan.fused and plan.rows[:3] == (1024, 1024, 2)
    assert calls == (1 if case == "fwd" else 2)


@pytest.mark.parametrize("heads,seq,dtype", [
    (32, 16384, jnp.bfloat16),     # the global layer's queries
    (4, 16384, jnp.bfloat16),      # ... and keys
    (4, 8200, jnp.bfloat16),       # the last block hangs over the sequence
    (2, 2056, jnp.float32),
], ids=["q", "k", "edge", "float32"])
def test_position_free_preparation_compiles_for_v5e(spec, monkeypatch, heads,
                                                    seq, dtype):
    """`rms_norm_rotary(x, gamma, None, ...)`: the norm and the head-major
    store through the same two kernels with the rotation compiled out and
    no table among the operands."""
    from mxnet_tpu.ops import pallas_qk_prep as qp

    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    x, gamma = spec((1, seq, heads * 128), dtype), spec((128,), jnp.float32)

    def loss(x, gamma):
        out = qp.rms_norm_rotary(x, gamma, None, 1e4, heads, 1e-5)
        return out.astype(jnp.float32).sum()

    assert _kernel_calls(loss, x, gamma) == 1
    assert _kernel_calls(jax.value_and_grad(loss, (0, 1)), x, gamma) == 2


def test_the_window_cells_whole_step_compiles_for_v5e(one_chip, monkeypatch):
    """The Trinity-Mini cell's step as gluon.TrainStep builds it — every
    published width, 1 x 16,384 positions, the 25,024 rows held of
    embedding and head, bf16 under Adam with masters, remat; TWO of its
    five layers, one of each mask (sliding + dense, global + experts
    beside the shared one) — compiled for the described v5e.  Each layer
    is two Mosaic flash calls (forward, the fused backward) with no
    fallback, its preparation of 32 and of 4 heads three each (forward,
    replayed, backward), the global layer's with no table; arguments and
    temporaries fit the chip.  (`chipbench/compile_check_large.py`
    compiles all five: 6.571 GiB of arguments, 4.91 GiB of
    temporaries.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo.afmoe import afmoe
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops import pallas_qk_prep as qp
    from mxnet_tpu.telemetry import instruments as ti

    kernel = pa.flash_attention
    monkeypatch.setattr(pa, "flash_attention", lambda *a, **kw: kernel(
        *a, **{**kw, "interpret": False}))
    monkeypatch.setattr(qp, "_kernel_mode", lambda: False)
    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    pa._plan.cache_clear()
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()
    fallbacks = {k: c.value for k, c in
                 ti.attention_kernel_fallback_total.series()}
    net = afmoe(25024, 2048, ["sliding_attention", "full_attention"], 32, 4,
                128, 6144, 1024, 128, 8, 2048, num_dense_layers=1,
                route_scale=2.826, ep_size=16, remat=True)
    net.initialize(init=mx.initializer.Zero())
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": 1e-5, "multi_precision": True}, kvstore="tpu_dist")
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    compiled = _compiled_step(step, one_chip,
                              mx.np.zeros((1, 16384), dtype="int32"))
    text = compiled.as_text()
    assert "f64[" not in text
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l
             and " custom-call(" in l]
    for scope in ("attention.window", "attention.global"):
        flash = [l for l in calls if "flash_attention" in l and scope in l]
        assert len(flash) == 2, scope
        assert len([l for l in flash if "flash_attention_bwd" in l]) == 1
    assert not _columns_under_attention(text, 16384)
    prep = [l for l in calls if "rms_norm_rotary" in l]
    assert len(prep) == 12
    assert len([l for l in prep if "rms_norm_rotary_bwd" in l]) == 4
    assert fallbacks == {k: c.value for k, c in
                         ti.attention_kernel_fallback_total.series()}
    assert ti._qk_prep_sites == [4, 4]
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("window", "dense"): 1, ("global", "moe"): 1}
    visited, kept = ({k[0]: c.value for k, c in g.series()} for g in (
        ti.attention_pairs_visited, ti.attention_pairs_kept))
    # computed: the masked sub-tiles less their dead quarters (ISSUE 52)
    assert visited == {"window": 37.5 * 1024 ** 2, "causal": 132 * 1024 ** 2}
    assert kept == {"window": 31_458_304, "causal": 134_225_920}
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 10 * 2 ** 30
    pa._plan.cache_clear()
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept,
              ti.decoder_layers):
        g.clear()


# -- the delta / latent hybrid (ISSUE 54): the chunked scan's two kernels,
#    latent attention's assembly without positions, and the cell's step ------

def _kda_specs(spec, b, seq, heads, d, dtype):
    return (spec((b, seq, heads, d), dtype), spec((b, seq, heads, d), dtype),
            spec((b, seq, heads, d), dtype),
            spec((b, seq, heads, d), jnp.float32),
            spec((b, seq, heads), jnp.float32))


@pytest.mark.parametrize("b,seq,heads,dtype", [
    (2, 8192, 32, jnp.bfloat16),        # the cell's shape
    (1, 1000, 2, jnp.bfloat16),         # padded to 16 chunks of 64
    (1, 256, 2, jnp.float32),           # one grid step of four chunks
], ids=["cell", "ragged", "float32"])
def test_the_delta_rules_scan_compiles_for_v5e(spec, monkeypatch, b, seq,
                                               heads, dtype):
    """`kda_scan` on its kernels: one Mosaic call forward, one back (the
    backward's own forward is the first again), no 64-bit value in the
    program, and what the backward holds beside the operands is the
    states that entered the chunks."""
    from mxnet_tpu.ops import pallas_kda as pk
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(pk, "_kernel_mode", lambda: False)
    monkeypatch.setattr(ti, "_kda_scan_calls",
                        {"kernel": 0, "composition": 0})
    pk._shared.cache_clear()
    operands = _kda_specs(spec, b, seq, heads, 128, dtype)

    def loss(*a):
        return pk.kda_scan(*a).astype(jnp.float32).sum()

    assert _kernel_calls(loss, *operands) == 1
    compiled = jax.jit(jax.value_and_grad(loss, range(5))).lower(
        *operands).compile()
    text = compiled.as_text()
    names = re.findall(r'custom_call_target="tpu_custom_call".*?'
                       r'(kda_scan_\w+?)/', text)
    assert sorted(names) == ["kda_scan_bwd", "kda_scan_fwd"]
    assert "f64[" not in text and "s64[" not in text
    assert ti._kda_scan_calls == {"kernel": 2, "composition": 0}
    chunks = b * heads * (-(-seq // 64) if seq <= 512
                          else -(-seq // 512) * 8)
    assert ti.kda_scan_chunks.value == chunks
    assert ti.kda_scan_kept_bytes.value == chunks * 128 * 128 * 4
    pk._shared.cache_clear()


def test_layers_share_one_copy_of_each_scan_kernel(spec, monkeypatch):
    """Four delta layers at the cell's shape, forward and backward,
    lowered for the described v5e: two Mosaic calls in the module, not two
    a layer."""
    from mxnet_tpu.ops import pallas_kda as pk

    monkeypatch.setattr(pk, "_kernel_mode", lambda: False)
    pk._shared.cache_clear()

    def loss(q, k, v, g, beta):
        total = 0.0
        for i in range(4):
            with jax.named_scope(f"layer{i}"):
                out = pk.kda_scan(q, k, v, g, beta)
            total = total + out.astype(jnp.float32).sum()
            q = q + out.mean().astype(q.dtype)
        return total

    text = jax.jit(jax.value_and_grad(loss, range(5))).lower(
        *_kda_specs(spec, 2, 8192, 32, 128, jnp.bfloat16)).as_text()
    names = re.findall(r'stablehlo.custom_call @tpu_custom_call.*'
                       r'kernel_name = "([^"]+)"', text)
    assert sorted(names) == ["kda_scan_bwd", "kda_scan_fwd"]
    assert text.count("call @kernel_fwd") == 4
    assert text.count("call @kernel_bwd") == 4
    pk._shared.cache_clear()


@pytest.mark.parametrize("heads,seq,dtype", [
    (32, 8192, jnp.bfloat16),           # the cell's shape
    (2, 24, jnp.float32),
], ids=["cell", "short-float32"])
def test_position_free_latent_heads_assembly_compiles_for_v5e(
        spec, monkeypatch, heads, seq, dtype):
    """`mla_heads(positions=None)` on its kernels: the same four Mosaic
    calls, and no table among the operands of any."""
    from mxnet_tpu.ops import pallas_mla_heads as mh

    monkeypatch.setattr(mh, "_kernel_mode", lambda: False)
    q, kv, k_rope, _ = _mla_heads_specs(spec, heads, seq, dtype)

    def loss(q, kv, k_rope):
        outs = mh.mla_heads(q, kv, k_rope, None, 1e4, heads, True)
        return sum(o.astype(jnp.float32).sum() for o in outs)

    assert _kernel_calls(loss, q, kv, k_rope) == 2
    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        q, kv, k_rope).compile().as_text()
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l
             and " custom-call(" in l]
    assert len(calls) == 4
    assert "cosine" not in text and "f64[" not in text
    for l in calls:
        # q alone; kv and k_rope; dq alone; dk and dv
        assert l.count("%") - 1 <= 2, l


def test_the_delta_cells_whole_step_compiles_for_v5e(one_chip, monkeypatch):
    """The Kimi-Linear cell's step as gluon.TrainStep builds it — every
    published width, 2 x 8,192 positions, the 20,480 rows held of
    embedding and head, bf16 under Adam with masters, remat; TWO of its
    five layers, one of each mixer (published layer 1: delta + dense;
    layer 8: latent attention without positions + experts beside the
    shared one) — compiled for the described v5e.  The delta layer is
    three scan calls (forward, replayed, backward), the latent layer two
    flash calls and six assembly calls with no table; arguments and
    temporaries fit the chip.  (`chipbench/compile_check_large.py`
    compiles all five.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon.model_zoo.kimi_linear import kimi_linear
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.ops import pallas_kda as pk
    from mxnet_tpu.ops import pallas_mla_heads as mh
    from mxnet_tpu.telemetry import instruments as ti

    kernel = pa.flash_attention
    monkeypatch.setattr(pa, "flash_attention", lambda *a, **kw: kernel(
        *a, **{**kw, "interpret": False}))
    monkeypatch.setattr(mh, "_kernel_mode", lambda: False)
    monkeypatch.setattr(pk, "_kernel_mode", lambda: False)
    monkeypatch.setattr(ti, "_mla_heads_sites", [0, 0])
    monkeypatch.setattr(ti, "_kda_scan_calls",
                        {"kernel": 0, "composition": 0})
    pa._plan.cache_clear()
    pk._shared.cache_clear()
    fallbacks = {k: c.value for k, c in
                 ti.attention_kernel_fallback_total.series()}
    lin = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
           "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4}
    net = kimi_linear(20480, 2304, lin, 32, 512, 128, 64, 128, 9216, 1024,
                      256, 8, routed_scaling_factor=2.446, layers=[1, 8],
                      ep_size=32, remat=True)
    net.initialize(init=mx.initializer.Zero())
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": 1e-5, "multi_precision": True}, kvstore="tpu_dist")
    step = gluon.TrainStep(net, None, trainer, n_data=1)
    compiled = _compiled_step(step, one_chip,
                              mx.np.zeros((2, 8192), dtype="int32"))
    text = compiled.as_text()
    assert "f64[" not in text
    calls = [l for l in text.splitlines() if "tpu_custom_call" in l
             and " custom-call(" in l]
    scans = [l for l in calls if "kda_scan" in l]
    assert len(scans) == 3 and all("kda.scan" in l for l in scans)
    assert len([l for l in scans if "kda_scan_bwd" in l]) == 1
    flash = [l for l in calls if "flash_attention" in l]
    assert len(flash) == 2 and all("/mla/" in l for l in flash)
    assert len([l for l in calls if "mla_heads_" in l]) == 6
    assert "mla.rope" not in text and "mla.heads" in text
    assert fallbacks == {k: c.value for k, c in
                         ti.attention_kernel_fallback_total.series()}
    assert ti._mla_heads_sites == [1, 1]
    assert ti._kda_scan_calls == {"kernel": 1, "composition": 0}
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("kda", "dense"): 1, ("mla", "moe"): 1}
    m = compiled.memory_analysis()
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 10 * 2 ** 30
    pa._plan.cache_clear()
    pk._shared.cache_clear()
    ti.decoder_layers.clear()


# -- the whole step of a conv + BatchNorm net: XLA alone, and no f64 --------


@pytest.mark.parametrize("optimizer,params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4,
             "multi_precision": True}),
    ("adam", {"learning_rate": 1e-3, "wd": 0.01, "multi_precision": True}),
], ids=["sgd", "adam"])
def test_whole_step_compiles_for_v5e_without_kernels_or_f64(
        one_chip, optimizer, params):
    """BatchNorm's statistics and the optimizer ladder reach the chip as
    plain XLA (nothing for them is a Pallas call), and the package's
    64-bit contract leaks no f64 tensor into the compiled step (D10)."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp, gluon
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Conv2D(64, 3, padding=1, layout="NHWC", use_bias=False),
            nn.BatchNorm(axis=-1), nn.Activation("relu"),
            nn.MaxPool2D(2, layout="NHWC"),
            nn.Conv2D(128, 3, padding=1, layout="NHWC", use_bias=False),
            nn.BatchNorm(axis=-1), nn.Activation("relu"),
            nn.GlobalAvgPool2D(layout="NHWC"), nn.Flatten(), nn.Dense(10))
    net.initialize()
    x = mx.np.zeros((32, 32, 32, 3))
    y = mx.np.zeros((32,), dtype="int32")
    net(x)
    amp.convert_hybrid_block(net, target_dtype="bfloat16")
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), optimizer, params,
                            kvstore="tpu_dist")
    step = gluon.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                           trainer)
    text = _compiled_step(step, one_chip, x, y).as_text()
    assert "convolution" in text and "f64[" not in text
    assert "tpu_custom_call" not in text


# -- the two expert cells' toy steps: no f64 in what is lowered (D10) -------

@pytest.mark.parametrize("config,builder", [
    ("toy_sdar_moe", "sdar_moe"), ("toy_deepseek_v3", "deepseek_v3"),
], ids=["sdar", "kanana2"])
def test_the_expert_cells_toy_whole_step_lowers_without_f64(config, builder):
    """The guard the looped and the hybrid cell's steps carry, on SDAR's
    and kanana-2's: the benchmark's toy configuration as its adapter
    builds it, the whole step lowered where it stands (nothing compiled
    for the chip, nothing run)."""
    import importlib
    import json
    import sys

    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench")
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    sys.path.insert(0, bench)
    try:
        wmod = importlib.import_module("weights")
        model = importlib.import_module("models." + builder)
        ref = importlib.import_module("reference." + builder)
    finally:
        sys.path.remove(bench)
    weights = wmod.make_weights(ref.param_specs(cfg), 7, cfg["dtype"])
    batch = wmod.make_batches(ref.input_specs(cfg, 2), 7, 1)[0]
    net = model.build(mx, cfg, weights, mx.cpu())
    opt = cfg["optimizer"]
    trainer = gluon.Trainer(
        net.collect_params(), opt["name"],
        {k: v for k, v in opt.items() if k != "name"}, kvstore="tpu_dist")
    step = gluon.TrainStep(net, None, trainer, n_data=model.loss(mx, cfg)[1])
    text = _lowered_step(step, *[mx.nd.array(a) for a in batch]).as_text(
        dialect="hlo")
    # Before XLA folds them a Python float is still an `f64[]` operand of
    # its convert: no f64 TENSOR is what the lowered text can show.  One
    # is known (ROADMAP D10): `parallel.moe.dropless_moe` divides and
    # stacks an expert layer's two or three counters as float64 before it
    # casts them.  Anything wider is new.
    assert "bf16[" in text
    assert set(re.findall(r"f64\[(\d[\d,]*)\]", text)) <= {"1", "2", "3"}
