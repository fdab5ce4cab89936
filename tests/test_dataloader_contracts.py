"""DataLoader batching contracts (reference:
tests/python/unittest/test_gluon_data.py — last_batch modes, Pad/Stack
batchify, sampler exclusivity, nested-structure batching).
"""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon.data import batchify

rs = onp.random.RandomState(31)


def _ds(n=10):
    return gluon.data.SimpleDataset(
        [(onp.full((2,), i, "f"), i) for i in range(n)])


@pytest.mark.parametrize("mode,want_batches,last_size", [
    ("keep", 4, 1), ("discard", 3, 3), ("rollover", 3, 3)])
def test_last_batch_modes(mode, want_batches, last_size):
    loader = gluon.data.DataLoader(_ds(10), batch_size=3,
                                   last_batch=mode)
    batches = list(loader)
    assert len(batches) == want_batches
    assert batches[-1][0].shape[0] == last_size


def test_rollover_carries_remainder_to_next_epoch():
    loader = gluon.data.DataLoader(_ds(10), batch_size=3,
                                   last_batch="rollover")
    epoch1 = list(loader)        # 9 consumed, 1 rolls over
    epoch2 = list(loader)        # 1 + 10 = 11 -> 3 batches, 2 roll
    seen1 = sorted(int(v) for b in epoch1 for v in b[1].asnumpy())
    assert len(seen1) == 9
    seen2 = [int(v) for b in epoch2 for v in b[1].asnumpy()]
    assert len(seen2) == 9
    # the rolled-over sample from epoch 1 leads epoch 2
    leftover = set(range(10)) - set(seen1)
    assert seen2[0] in leftover


def test_pad_batchify_variable_length():
    data = [onp.arange(n, dtype="f") for n in (2, 5, 3)]
    out = batchify.Pad(val=-1)(data)
    assert out.shape == (3, 5)
    got = out.asnumpy()
    onp.testing.assert_array_equal(got[0], [0, 1, -1, -1, -1])
    onp.testing.assert_array_equal(got[1], [0, 1, 2, 3, 4])


def test_pad_axis_and_dtype():
    data = [onp.zeros((2, n), "f") for n in (1, 4)]
    out = batchify.Pad(axis=1, val=9, dtype="int32")(data)
    assert out.shape == (2, 2, 4)
    assert out.asnumpy().dtype == onp.int32
    assert (out.asnumpy()[0, :, 1:] == 9).all()


def test_group_batchify_in_loader():
    ds = gluon.data.SimpleDataset(
        [(onp.arange(n, dtype="f"), n) for n in (1, 2, 3, 4)])
    loader = gluon.data.DataLoader(
        ds, batch_size=2,
        batchify_fn=batchify.Group(batchify.Pad(), batchify.Stack()))
    xb, yb = next(iter(loader))
    assert xb.shape[0] == 2 and xb.shape[1] == 2  # padded to batch max
    assert yb.shape == (2,)


def test_batch_sampler_excludes_batch_size():
    sampler = gluon.data.BatchSampler(
        gluon.data.SequentialSampler(7), batch_size=3, last_batch="keep")
    with pytest.raises((ValueError, TypeError)):
        gluon.data.DataLoader(_ds(7), batch_size=3,
                              batch_sampler=sampler)
    loader = gluon.data.DataLoader(_ds(7), batch_sampler=sampler)
    sizes = [b[0].shape[0] for b in loader]
    assert sizes == [3, 3, 1]


def test_shuffle_covers_all_samples():
    loader = gluon.data.DataLoader(_ds(12), batch_size=4, shuffle=True)
    seen = sorted(int(v) for b in loader for v in b[1].asnumpy())
    assert seen == list(range(12))


def test_nested_dict_structure_batching():
    ds = gluon.data.SimpleDataset(
        [{"x": onp.full((3,), i, "f"), "y": i} for i in range(4)])
    loader = gluon.data.DataLoader(ds, batch_size=2)
    batch = next(iter(loader))
    assert isinstance(batch, dict)
    assert batch["x"].shape == (2, 3)
    assert batch["y"].shape == (2,)


def test_dict_sample_with_ndarray_not_forked(monkeypatch):
    """A dict sample holding device arrays must be classified NOT
    fork-safe (a forked child of a jax-initialized parent must stay off
    jax: the device belongs to the parent)."""
    ds = gluon.data.SimpleDataset(
        [{"x": mx.np.array([1.0, 2.0]), "y": 0} for _ in range(4)])
    loader = gluon.data.DataLoader(ds, batch_size=2, num_workers=2)
    assert loader._fork_safe() is False
    batch = next(iter(loader))  # falls back to a non-fork path, works
    assert batch["x"].shape == (2, 2)
