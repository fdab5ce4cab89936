"""``blockdiff_pairs_visited_over_kept.train`` on the CPU: the reader on
the gauge pair the SDAR cell's plan sets, beside a plan of another mask,
on a program without the gauges, and before any plan was built."""
import pytest

import run as harness

NAME = "blockdiff_pairs_visited_over_kept.train"


@pytest.fixture
def program():
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.telemetry import instruments as ti

    def forget():
        pa._plan.cache_clear()
        for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
            g.clear()

    forget()
    yield pa, ti
    forget()


def _read(name=NAME):
    return harness._load_reader(name).read({}, {})


def test_the_sdar_cells_plan(program):
    """(4, 4096) over 8,192 positions at the tile the op chooses: the
    mask keeps 4096 x 4 pairs noisy -> noisy, 4096 x 4092 / 2 noisy ->
    clean and 4096 x 4100 / 2 clean -> clean a head; the reader gives the
    gauge pair's ratio, whatever the program's walk makes it (24 sub-tiles
    of 1024 x 1024 computed whole read 1.4985, 20 read 1.2488)."""
    pa, ti = program
    assert _read() is None                      # no plan was built yet
    q = (2, 32, 8192, 128)
    kv = (2, 4, 8192, 128)
    tile = pa._choose_tile(8192, 128, 128, 2)
    pa._plan(q, kv, kv, "bfloat16", True, tile, tile, None, None)
    assert _read() is None                      # a causal plan alone
    plan = pa._plan(q, kv, kv, "bfloat16", False, tile, tile, None,
                    (4, 4096))
    kept = 4096 * 4 + 4096 * 4092 // 2 + 4096 * 4100 // 2
    assert pa._pairs_kept(plan.codes) == kept
    visited = dict(ti.attention_pairs_visited.series())[
        ("block_diffusion",)].value
    assert visited in (20 * 1024 ** 2, 24 * 1024 ** 2)
    assert _read() == pytest.approx(visited / kept)
    # the window's reader reads its own label
    assert _read("window_pairs_visited_over_kept.train") is None


def test_a_program_without_the_gauges_reads_none(program, monkeypatch):
    pa, ti = program
    shape = (1, 2, 256, 16)
    pa._plan(shape, shape, shape, "float32", False, 64, 64, None, (4, 128))
    assert _read() is not None
    monkeypatch.delattr(ti, "attention_pairs_visited")
    assert _read() is None
