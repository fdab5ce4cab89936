"""``attention_maskfree_share.train`` on the CPU: the reader on gauges set
by the plans of the two sparse-expert cells' signatures, on a program
without the gauge, and before any plan was built."""
import pytest

import run as harness

NAME = "attention_maskfree_share.train"


@pytest.fixture
def program():
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.telemetry import instruments as ti

    pa._plan.cache_clear()
    ti.attention_maskfree_share.clear()
    yield pa, ti
    pa._plan.cache_clear()
    ti.attention_maskfree_share.clear()


def _read():
    return harness._load_reader(NAME).read({}, {})


@pytest.mark.parametrize("widths,mask,share", [
    ((128, 128), {"block_diffusion": (4, 4096)}, 50.0),     # the SDAR cell
    ((192, 128), {"causal": True}, 100.0 * 28 / 36),        # kanana-2
    ((64, 64), {}, 100.0),                                  # no mask
])
def test_the_share_of_a_cells_schedules(program, widths, mask, share):
    """8192 positions at the tiles the op chooses: 12 of 24 visited
    sub-tiles of 1024 x 1024 are whole under block diffusion, 28 of 36
    under the causal mask, in all three kernels."""
    pa, _ = program
    assert _read() is None                      # no plan was built yet
    q, v = (2, 32, 8192, widths[0]), (2, 32, 8192, widths[1])
    tile = pa._choose_tile(8192, *widths, 2)
    pa._plan(q, q, v, "bfloat16", mask.get("causal", False), tile, tile,
             None, mask.get("block_diffusion"))
    assert _read() == pytest.approx(share)


def test_a_program_without_the_gauge_reads_none(program, monkeypatch):
    _, ti = program
    monkeypatch.delattr(ti, "attention_maskfree_share")
    assert _read() is None
