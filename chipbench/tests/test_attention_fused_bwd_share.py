"""``attention_fused_bwd_share.train`` on the CPU: the reader on the gauge
set by the plans of the three transformer cells' signatures and by one
that does not fit the core's fast memory, on a program without the gauge,
and before any plan was built; and ``attention_maskfree_share.train``
beside it, which keeps reading the schedule's share on a fused plan."""
import pytest

import run as harness

NAME = "attention_fused_bwd_share.train"

# (query heads, key-value heads, key width, value width, mask)
CELLS = {
    "sdar": (32, 4, 128, 128, {"block_diffusion": (4, 4096)}),
    "kanana2": (32, 32, 192, 128, {"causal": True}),
    "ouro": (16, 16, 128, 128, {"causal": True}),
}


@pytest.fixture
def program(monkeypatch):
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(ti, "_attention_plans", [0, 0])
    gauges = ti.attention_maskfree_share, ti.attention_fused_backward_share
    pa._plan.cache_clear()
    gauges[0].clear()
    yield pa, ti
    pa._plan.cache_clear()
    for gauge in gauges:
        gauge.clear()


def _read(name=NAME):
    return harness._load_reader(name).read({}, {})


def _plan(pa, cell, s_len=8192):
    h, kv, dk, dv, mask = CELLS[cell]
    tile = pa._choose_tile(s_len, dk, dv, 2)
    return pa._plan((2, h, s_len, dk), (2, kv, s_len, dk), (2, kv, s_len, dv),
                    "bfloat16", mask.get("causal", False), tile, tile, None,
                    mask.get("block_diffusion"))


@pytest.mark.parametrize("cell,maskfree", [
    ("sdar", 50.0), ("kanana2", 100.0 * 28 / 36), ("ouro", 100.0 * 28 / 36)])
def test_a_cells_signature_fuses(program, cell, maskfree):
    """8192 positions at the tiles the op chooses: every cell's backward
    is the fused kernel, and the mask-free share, a mean over the two
    series a fused plan sets (forward, backward), stays the schedule's."""
    pa, ti = program
    assert _read() is None                      # no plan was built yet
    assert _plan(pa, cell).fused
    assert _read() == pytest.approx(100.0)
    assert sorted(k[0] for k, _ in ti.attention_maskfree_share.series()) \
        == ["flash_attention_bwd", "flash_attention_fwd"]
    assert _read("attention_maskfree_share.train") == pytest.approx(maskfree)


def test_a_signature_that_does_not_fit_lowers_the_share(program):
    pa, _ = program
    _plan(pa, "kanana2")
    assert not _plan(pa, "kanana2", s_len=32768).fused
    assert _read() == pytest.approx(50.0)


def test_a_program_without_the_gauge_reads_none(program, monkeypatch):
    pa, ti = program
    _plan(pa, "ouro")
    monkeypatch.delattr(ti, "attention_fused_backward_share")
    assert _read() is None
