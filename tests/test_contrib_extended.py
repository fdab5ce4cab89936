"""Tests for the extended contrib surface: text (vocab/embedding),
tensorboard event writer, contrib.io DataLoaderIter, and the round-2
contrib op families (adaptive pooling, bilinear resize, fft, STE ops,
transformer fused projections, multi-tensor helpers, proposals,
PSROIPooling), plus the new gluon layers (PixelShuffle*, deformable
convolutions, BatchNormReLU).

Reference anchors: python/mxnet/contrib/text/, contrib/tensorboard.py,
contrib/io.py, src/operator/contrib/*.cc, gluon/nn/conv_layers.py.
"""
import collections
import os
import struct

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.contrib import ops as cops
from mxnet_tpu.contrib import text
from mxnet_tpu.gluon import nn


# --- contrib.text ---------------------------------------------------------

def test_vocabulary_basic():
    counter = collections.Counter(
        ["a", "b", "b", "c", "c", "c", "rare"])
    v = text.Vocabulary(counter, min_freq=2, unknown_token="<unk>",
                        reserved_tokens=["<pad>"])
    assert v.to_indices("<unk>") == 0
    assert v.to_indices("<pad>") == 1
    # frequency order: c (3), b (2); 'a'/'rare' dropped by min_freq
    assert v.to_tokens([2, 3]) == ["c", "b"]
    assert v.to_indices("zzz") == 0  # unknown
    assert len(v) == 4


def test_vocabulary_most_freq_count():
    counter = collections.Counter({"x": 5, "y": 4, "z": 3})
    v = text.Vocabulary(counter, most_freq_count=2)
    assert len(v) == 3  # unk + 2
    assert "z" not in v.token_to_idx


def test_count_tokens_from_str():
    c = text.utils.count_tokens_from_str("a b b\nc a", to_lower=False)
    assert c == collections.Counter({"a": 2, "b": 2, "c": 1})


def test_custom_embedding_and_composite(tmp_path):
    p = tmp_path / "emb.txt"
    p.write_text("hello 1.0 2.0 3.0\nworld 4.0 5.0 6.0\n")
    emb = text.embedding.CustomEmbedding(str(p))
    assert emb.vec_len == 3
    v = emb.get_vecs_by_tokens("world").asnumpy()
    onp.testing.assert_allclose(v, [4.0, 5.0, 6.0])
    # unknown token gets the zero init vector
    u = emb.get_vecs_by_tokens("absent").asnumpy()
    onp.testing.assert_allclose(u, [0.0, 0.0, 0.0])
    # update vectors
    emb.update_token_vectors("hello", mx.np.array([9.0, 9.0, 9.0]))
    onp.testing.assert_allclose(
        emb.get_vecs_by_tokens("hello").asnumpy(), [9.0, 9.0, 9.0])
    # composite over an explicit vocabulary
    vocab = text.Vocabulary(collections.Counter(["hello", "world"]))
    comp = text.embedding.CompositeEmbedding(
        vocab, [text.embedding.CustomEmbedding(str(p)),
                text.embedding.CustomEmbedding(str(p))])
    assert comp.vec_len == 6
    onp.testing.assert_allclose(
        comp.get_vecs_by_tokens("world").asnumpy(),
        [4.0, 5.0, 6.0, 4.0, 5.0, 6.0])


def test_embedding_registry():
    assert "glove" in text.embedding.get_pretrained_file_names()
    names = text.embedding.get_pretrained_file_names("glove")
    assert "glove.6B.50d.txt" in names
    with pytest.raises(FileNotFoundError):
        text.embedding.create("glove",
                              pretrained_file_name="glove.6B.50d.txt",
                              embedding_root="/nonexistent")


# --- contrib.tensorboard --------------------------------------------------

def test_summary_writer_tfrecord_framing(tmp_path):
    from mxnet_tpu.contrib.tensorboard import SummaryWriter, _masked_crc

    w = SummaryWriter(str(tmp_path))
    w.add_scalar("loss", 0.5, global_step=3)
    w.flush()
    w.close()
    files = [f for f in os.listdir(tmp_path) if "tfevents" in f]
    assert len(files) == 1
    buf = (tmp_path / files[0]).read_bytes()
    # walk the TFRecord frames, verifying both CRCs per record
    pos, n = 0, 0
    while pos < len(buf):
        header = buf[pos:pos + 8]
        (length,) = struct.unpack("<Q", header)
        (hcrc,) = struct.unpack("<I", buf[pos + 8:pos + 12])
        assert hcrc == _masked_crc(header)
        data = buf[pos + 12:pos + 12 + length]
        (dcrc,) = struct.unpack(
            "<I", buf[pos + 12 + length:pos + 16 + length])
        assert dcrc == _masked_crc(data)
        pos += 16 + length
        n += 1
    assert n == 2  # version header + one scalar
    assert b"loss" in buf


def test_log_metrics_callback(tmp_path):
    from mxnet_tpu.contrib.tensorboard import LogMetricsCallback

    cb = LogMetricsCallback(str(tmp_path), prefix="train")
    metric = gluon.metric.Accuracy()
    metric.update(mx.np.array([1, 1]), mx.np.array([[0.1, 0.9],
                                                    [0.8, 0.2]]))
    param = type("P", (), {"eval_metric": metric, "epoch": 1})()
    cb(param)
    files = [f for f in os.listdir(tmp_path) if "tfevents" in f]
    assert files and b"train-accuracy" in (
        tmp_path / files[0]).read_bytes()


# --- contrib.io -----------------------------------------------------------

def test_dataloader_iter():
    from mxnet_tpu.contrib.io import DataLoaderIter

    X = onp.random.rand(10, 3).astype("f")
    Y = onp.arange(10).astype("f")
    ds = gluon.data.ArrayDataset(X, Y)
    loader = gluon.data.DataLoader(ds, batch_size=4, last_batch="keep")
    it = DataLoaderIter(loader)
    assert it.batch_size == 4
    assert it.provide_data[0].name == "data"
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 2  # 10 = 4+4+2
    it.reset()
    assert len(list(it)) == 3


# --- contrib ops ----------------------------------------------------------

def test_adaptive_avg_pooling():
    x = mx.np.array(onp.random.rand(2, 3, 8, 8).astype("f"))
    out = cops.adaptive_avg_pooling(x, 2)
    assert out.shape == (2, 3, 2, 2)
    # 2x2 over 8x8 = mean of each 4x4 quadrant
    expect = x.asnumpy()[:, :, :4, :4].mean(axis=(2, 3))
    onp.testing.assert_allclose(out.asnumpy()[:, :, 0, 0], expect,
                                rtol=1e-5)
    # output_size=1 == global average
    g = cops.adaptive_avg_pooling(x, 1).asnumpy()
    onp.testing.assert_allclose(
        g[:, :, 0, 0], x.asnumpy().mean(axis=(2, 3)), rtol=1e-5)


def test_bilinear_resize_matches_torch():
    torch = pytest.importorskip("torch")
    x = onp.random.rand(2, 3, 5, 7).astype("f")
    out = cops.bilinear_resize_2d(mx.np.array(x), 10, 14).asnumpy()
    ref = torch.nn.functional.interpolate(
        torch.tensor(x), size=(10, 14), mode="bilinear",
        align_corners=True).numpy()
    onp.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_fft_ifft_roundtrip():
    x = onp.random.rand(3, 8).astype("f")
    f = cops.fft(mx.np.array(x))
    assert f.shape == (3, 16)
    # real part interleaved at even positions matches numpy fft
    ref = onp.fft.fft(x, axis=-1)
    onp.testing.assert_allclose(f.asnumpy()[:, 0::2], ref.real,
                                rtol=1e-4, atol=1e-4)
    # reference ifft is unnormalized: ifft(fft(x)) == d * x
    rt = cops.ifft(f).asnumpy()
    onp.testing.assert_allclose(rt, x * 8, rtol=1e-4, atol=1e-4)


def test_ste_ops_gradients():
    a = mx.np.array(onp.array([1.4, -0.6, 2.5], "f"))
    a.attach_grad()
    with autograd.record():
        out = cops.round_ste(a)
    out.backward()
    onp.testing.assert_allclose(out.asnumpy(), [1.0, -1.0, 2.0])
    onp.testing.assert_allclose(a.grad.asnumpy(), [1.0, 1.0, 1.0])
    b = mx.np.array(onp.array([0.3, -0.2], "f"))
    b.attach_grad()
    with autograd.record():
        out = cops.sign_ste(b)
    out.backward()
    onp.testing.assert_allclose(out.asnumpy(), [1.0, -1.0])
    onp.testing.assert_allclose(b.grad.asnumpy(), [1.0, 1.0])


def test_gradient_multiplier_and_reversal():
    g = mx.np.array(onp.ones((2, 2), "f"))
    g.attach_grad()
    with autograd.record():
        out = cops.gradientmultiplier(g, 2.5).sum()
    out.backward()
    onp.testing.assert_allclose(g.grad.asnumpy(), 2.5 * onp.ones((2, 2)))
    with autograd.record():
        out = cops.gradientreversal(g, 1.0).sum()
    out.backward()
    onp.testing.assert_allclose(g.grad.asnumpy(), -onp.ones((2, 2)))


def test_interleaved_matmul_selfatt():
    L, B, H, D = 5, 2, 4, 6
    qkv = onp.random.rand(L, B, H * 3 * D).astype("f")
    scores = cops.interleaved_matmul_selfatt_qk(mx.np.array(qkv), H)
    assert scores.shape == (B * H, L, L)
    # manual: per head h, q = qkv[l, b, h*3D : h*3D+D]
    ref_q = qkv.reshape(L, B, H, 3, D)[:, :, :, 0]
    ref_k = qkv.reshape(L, B, H, 3, D)[:, :, :, 1]
    ref = onp.einsum("lbhd,mbhd->bhlm", ref_q, ref_k) / onp.sqrt(D)
    onp.testing.assert_allclose(
        scores.asnumpy(), ref.reshape(B * H, L, L), rtol=1e-4, atol=1e-5)
    out = cops.interleaved_matmul_selfatt_valatt(
        mx.np.array(qkv), scores, H)
    assert out.shape == (L, B, H * D)


def test_interleaved_matmul_encdec():
    Lq, Lk, B, H, D = 4, 7, 2, 3, 5
    q = onp.random.rand(Lq, B, H * D).astype("f")
    kv = onp.random.rand(Lk, B, H * 2 * D).astype("f")
    s = cops.interleaved_matmul_encdec_qk(mx.np.array(q),
                                          mx.np.array(kv), H)
    assert s.shape == (B * H, Lq, Lk)
    out = cops.interleaved_matmul_encdec_valatt(mx.np.array(kv), s, H)
    assert out.shape == (Lq, B, H * D)


def test_div_sqrt_dim():
    x = onp.random.rand(2, 16).astype("f")
    out = cops.div_sqrt_dim(mx.np.array(x)).asnumpy()
    onp.testing.assert_allclose(out, x / 4.0, rtol=1e-6)


def test_multi_tensor_helpers():
    a = mx.np.array(onp.array([1.0, 2.0], "f"))
    b = mx.np.array(onp.array([[3.0], [4.0]], "f"))
    ss = cops.multi_sum_sq(a, b).asnumpy()
    onp.testing.assert_allclose(ss, [5.0, 25.0])
    z = mx.np.array(onp.ones((3,), "f"))
    cops.reset_arrays(z)
    assert z.asnumpy().sum() == 0.0
    lrs = cops.multi_lars(
        mx.np.array([0.1, 0.1]), mx.np.array([4.0, 0.0]),
        mx.np.array([1.0, 1.0]), mx.np.array([0.0, 0.0]),
        eta=1.0, eps=0.0).asnumpy()
    onp.testing.assert_allclose(lrs, [0.2, 0.1], rtol=1e-5)  # 0.1*2/1; passthrough


def test_dynamic_reshape():
    x = mx.np.array(onp.random.rand(2, 6).astype("f"))
    out = cops.dynamic_reshape(x, mx.np.array([3, 4]))
    assert out.shape == (3, 4)


def test_psroi_pooling():
    # one ROI covering the full map, G=P=2, output_dim=2, C=2*2*2=8
    x = onp.arange(1 * 8 * 4 * 4, dtype="f").reshape(1, 8, 4, 4)
    rois = onp.array([[0, 0, 0, 3, 3]], "f")
    out = cops.psroi_pooling(mx.np.array(x), mx.np.array(rois),
                             spatial_scale=1.0, output_dim=2,
                             pooled_size=2)
    assert out.shape == (1, 2, 2, 2)
    # bin (0,0) of out channel 0 averages input channel 0 over rows/cols 0..1
    expect = x[0, 0, 0:2, 0:2].mean()
    onp.testing.assert_allclose(out.asnumpy()[0, 0, 0, 0], expect,
                                rtol=1e-5)


def test_proposal():
    rs = onp.random.RandomState(0)
    A = 3
    cls = rs.rand(2, 2 * A, 4, 5).astype("f")
    bp = ((rs.rand(2, 4 * A, 4, 5) - 0.5) * 0.1).astype("f")
    im = onp.array([[64, 80, 1.0], [64, 80, 1.0]], "f")
    out = cops.proposal(mx.np.array(cls), mx.np.array(bp),
                        mx.np.array(im), scales=(8,),
                        ratios=(0.5, 1, 2), rpn_post_nms_top_n=10,
                        rpn_min_size=4)
    assert out.shape == (2, 10, 5)
    o = out.asnumpy()
    assert (o[0, :, 0] == 0).all() and (o[1, :, 0] == 1).all()
    # boxes are inside the image
    assert (o[:, :, 1] >= 0).all() and (o[:, :, 3] <= 79).all()
    out2, scores = cops.proposal(
        mx.np.array(cls), mx.np.array(bp), mx.np.array(im), scales=(8,),
        ratios=(0.5, 1, 2), rpn_post_nms_top_n=10, rpn_min_size=4,
        output_score=True)
    assert scores.shape == (2, 10, 1)


# --- new gluon layers -----------------------------------------------------

def test_pixel_shuffle_layers():
    torch = pytest.importorskip("torch")
    x = onp.random.rand(2, 8, 3, 4).astype("f")
    out = nn.PixelShuffle2D(2)(mx.np.array(x)).asnumpy()
    ref = torch.nn.functional.pixel_shuffle(torch.tensor(x), 2).numpy()
    onp.testing.assert_allclose(out, ref, rtol=1e-6)
    assert nn.PixelShuffle1D(2)(
        mx.np.array(onp.random.rand(2, 6, 5).astype("f"))).shape \
        == (2, 3, 10)
    assert nn.PixelShuffle3D(2)(
        mx.np.array(onp.random.rand(1, 16, 2, 3, 4).astype("f"))).shape \
        == (1, 2, 4, 6, 8)


def test_batchnorm_relu():
    bnr = nn.BatchNormReLU()
    bnr.initialize()
    x = mx.np.array(onp.random.randn(2, 4, 5, 5).astype("f"))
    out = bnr(x)
    assert float(out.min().asnumpy()) >= 0.0


def test_deformable_convolution_zero_offset_equals_conv():
    torch = pytest.importorskip("torch")
    import torch.nn.functional as F

    dc = nn.DeformableConvolution(6, (3, 3), padding=(1, 1))
    dc.initialize()
    x = mx.np.array(onp.random.rand(2, 4, 8, 8).astype("f"))
    out = dc(x).asnumpy()  # offset conv is zero-init => plain conv
    ref = F.conv2d(torch.tensor(x.asnumpy()),
                   torch.tensor(dc.weight.data().asnumpy()),
                   torch.tensor(dc.bias.data().asnumpy()),
                   padding=1).numpy()
    onp.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-4)


def test_modulated_deformable_convolution():
    mdc = nn.ModulatedDeformableConvolution(6, (3, 3), padding=(1, 1))
    mdc.initialize()
    x = mx.np.array(onp.random.rand(2, 4, 8, 8).astype("f"))
    out = mdc(x)
    assert out.shape == (2, 6, 8, 8)
    # gradient flows through offsets, mask and weight
    x.attach_grad()
    with autograd.record():
        loss = mdc(x).sum()
    loss.backward()
    assert onp.isfinite(x.grad.asnumpy()).all()


def test_contrib_namespace_exports():
    from mxnet_tpu import contrib

    for name in ("text", "tensorboard", "io", "nd", "symbol",
                 "quantization"):
        assert hasattr(contrib, name), name
    for op in ("AdaptiveAvgPooling2D", "BilinearResize2D", "Proposal",
               "PSROIPooling", "fft", "round_ste"):
        assert hasattr(contrib.nd, op), op


# --- review regressions ---------------------------------------------------

def test_new_contrib_ops_are_taped():
    """interleaved matmuls / resize / pooling / fft must participate in
    autograd (review finding: NDArray(out) bypassed the tape)."""
    L, B, H, D = 4, 2, 2, 3
    qkv = mx.np.array(onp.random.rand(L, B, H * 3 * D).astype("f"))
    qkv.attach_grad()
    with autograd.record():
        s = cops.interleaved_matmul_selfatt_qk(qkv, H)
        out = cops.interleaved_matmul_selfatt_valatt(qkv, s, H)
        loss = out.sum()
    loss.backward()
    g = qkv.grad.asnumpy()
    assert onp.isfinite(g).all() and (g != 0).any()

    x = mx.np.array(onp.random.rand(1, 2, 4, 4).astype("f"))
    x.attach_grad()
    with autograd.record():
        loss = (cops.adaptive_avg_pooling(x, 2).sum()
                + cops.bilinear_resize_2d(x, 8, 8).sum()
                + cops.div_sqrt_dim(x).sum()
                + cops.fft(x).sum())
    loss.backward()
    assert (x.grad.asnumpy() != 0).all()

    # psroi gradient
    d = mx.np.array(onp.random.rand(1, 8, 4, 4).astype("f"))
    d.attach_grad()
    rois = mx.np.array(onp.array([[0, 0, 0, 3, 3]], "f"))
    with autograd.record():
        loss = cops.psroi_pooling(d, rois, 1.0, 2, 2).sum()
    loss.backward()
    assert onp.isfinite(d.grad.asnumpy()).all()


def test_custom_embedding_1d_vectors(tmp_path):
    p = tmp_path / "emb1d.txt"
    p.write_text("a 0.5\nb 0.25\n")
    emb = text.embedding.CustomEmbedding(str(p))
    assert emb.vec_len == 1
    onp.testing.assert_allclose(
        emb.get_vecs_by_tokens("b").asnumpy(), [0.25])


def test_dataloader_iter_one_shot_iterable():
    """Batch 0 must not be dropped for generator-style loaders."""
    from mxnet_tpu.contrib.io import DataLoaderIter

    class OneShot:
        def __init__(self):
            self._gen = ((onp.full((2, 3), i, "f"), onp.zeros((2,), "f"))
                         for i in range(3))

        def __iter__(self):
            return self._gen

    it = DataLoaderIter(OneShot())
    batches = list(it)
    assert len(batches) == 3
    assert float(batches[0].data[0].asnumpy()[0, 0]) == 0.0  # batch 0 kept


# --- DGL graph ops (reference: src/operator/contrib/dgl_graph.cc) ----------

def _ref_graph():
    from mxnet_tpu.ndarray import sparse

    data = onp.arange(1, 21, dtype=onp.int64)
    indices = onp.array([1, 2, 3, 4, 0, 2, 3, 4, 0, 1, 3, 4,
                         0, 1, 2, 4, 0, 1, 2, 3], dtype=onp.int64)
    indptr = onp.array([0, 4, 8, 12, 16, 20], dtype=onp.int64)
    return sparse.csr_matrix((data, indices, indptr), shape=(5, 5))


def test_edge_id():
    from mxnet_tpu.contrib import dgl
    from mxnet_tpu.ndarray import sparse

    x = sparse.csr_matrix(
        (onp.array([1, 2, 3], onp.int64), onp.array([0, 1, 2], onp.int64),
         onp.array([0, 1, 2, 3], onp.int64)), shape=(3, 3))
    out = dgl.edge_id(x, mx.np.array([0, 0, 1, 1, 2, 2]),
                      mx.np.array([0, 1, 1, 2, 0, 2]))
    onp.testing.assert_allclose(out.asnumpy(), [1, -1, 2, -1, -1, 3])


def test_dgl_adjacency():
    from mxnet_tpu.contrib import dgl

    adj = dgl.dgl_adjacency(_ref_graph())
    dense = adj.todense().asnumpy()
    assert dense.dtype == onp.float32
    assert set(onp.unique(dense)) <= {0.0, 1.0}
    assert dense.sum() == 20  # every edge present as a 1


def test_dgl_neighbor_sample():
    from mxnet_tpu.contrib import dgl

    a = _ref_graph()
    seed = mx.np.array([0, 1, 2, 3, 4], dtype="int64")
    v, sub, layers = dgl.dgl_csr_neighbor_uniform_sample(
        a, seed, num_hops=1, num_neighbor=2, max_num_vertices=5)
    vn = v.asnumpy()
    assert vn.shape == (6,) and vn[-1] == 5  # all 5 vertices sampled
    dense = sub.todense().asnumpy()
    assert (dense > 0).sum() == 10  # 2 sampled edges per vertex
    # sampled values are real parent edge ids
    parent = a.todense().asnumpy()
    nz = onp.nonzero(dense)
    assert (dense[nz] == parent[nz]).all()
    assert (layers.asnumpy() == 0).all()  # seeds are layer 0


def test_dgl_neighbor_sample_non_uniform():
    from mxnet_tpu.contrib import dgl

    a = _ref_graph()
    prob = mx.np.array([0.1, 0.4, 0.3, 0.1, 0.1])
    seed = mx.np.array([0], dtype="int64")
    out = dgl.dgl_csr_neighbor_non_uniform_sample(
        a, prob, seed, num_hops=2, num_neighbor=2, max_num_vertices=5)
    v, sub, probs, layers = out
    cnt = int(v.asnumpy()[-1])
    assert 1 <= cnt <= 5
    assert probs.shape == (5,)


def test_dgl_subgraph_and_compact():
    from mxnet_tpu.contrib import dgl

    a = _ref_graph()
    sub, mapping = dgl.dgl_subgraph(
        a, mx.np.array([0, 1, 2], dtype="int64"), return_mapping=True)
    sd = sub.todense().asnumpy()
    md = mapping.todense().asnumpy()
    assert sd.shape == (3, 3)
    # subgraph edge ids renumbered 1..E; mapping holds parent edge ids
    assert sorted(sd[sd > 0]) == list(range(1, (sd > 0).sum() + 1))
    parent = a.todense().asnumpy()[:3, :3]
    assert ((md > 0) == (parent > 0)).all()
    assert (md[md > 0] == parent[parent > 0]).all()

    seed = mx.np.array([0, 1], dtype="int64")
    v, g, _ = dgl.dgl_csr_neighbor_uniform_sample(
        a, seed, num_hops=1, num_neighbor=2, max_num_vertices=4)
    n = int(v.asnumpy()[-1])
    comp = dgl.dgl_graph_compact(g, graph_sizes=mx.np.array([n]))
    assert comp.shape == (n, n)


# --- mx.rtc (reference: python/mxnet/rtc.py) -------------------------------

def test_rtc_pallas_module():
    import mxnet_tpu.rtc as rtc

    with pytest.raises(NotImplementedError):
        rtc.CudaModule("__global__ void k() {}")

    def add_one(x_ref, o_ref):
        o_ref[...] = x_ref[...] + 1.0

    mod = rtc.PallasModule({"add_one": add_one})
    k = mod.get_kernel("add_one")
    x = mx.np.array(onp.arange(8, dtype="f").reshape(2, 4))
    y = k.launch([x], out_shape=(2, 4))
    onp.testing.assert_allclose(y.asnumpy(), x.asnumpy() + 1.0)
    with pytest.raises(KeyError):
        mod.get_kernel("missing")


def test_rtc_kernel_is_named_after_itself(monkeypatch):
    from jax.experimental import pallas as pl

    from mxnet_tpu import rtc

    seen = []
    real = pl.pallas_call

    def spy(body, **kw):
        seen.append(kw.get("name"))
        return real(body, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    kern = rtc.PallasModule(double).get_kernel("double")
    out = kern.launch([mx.np.ones((8, 128))], (8, 128), "float32")
    assert seen == ["double"]
    onp.testing.assert_allclose(out.asnumpy(), 2.0)


def test_dgl_non_uniform_sparse_probability():
    """Review regression: fewer positive-prob neighbors than num_neighbor
    must not crash rng.choice."""
    from mxnet_tpu.contrib import dgl

    a = _ref_graph()
    prob = mx.np.array([0.0, 0.0, 0.9, 0.0, 0.0])
    out = dgl.dgl_csr_neighbor_non_uniform_sample(
        a, prob, mx.np.array([0], dtype="int64"), num_hops=1,
        num_neighbor=3, max_num_vertices=5)
    v = out[0].asnumpy()
    assert v[-1] >= 1


def test_dgl_graph_compact_return_mapping():
    from mxnet_tpu.contrib import dgl

    a = _ref_graph()
    v, g, _ = dgl.dgl_csr_neighbor_uniform_sample(
        a, mx.np.array([0, 1], dtype="int64"), num_hops=1,
        num_neighbor=2, max_num_vertices=4)
    n = int(v.asnumpy()[-1])
    comp, mapping = dgl.dgl_graph_compact(
        g, graph_sizes=mx.np.array([n]), return_mapping=True)
    cd = comp.todense().asnumpy()
    md = mapping.todense().asnumpy()
    assert cd.shape == (n, n) and md.shape == (n, n)
    # compacted graph renumbers edges 1..E; mapping holds parent edge ids
    assert sorted(cd[cd > 0]) == list(range(1, (cd > 0).sum() + 1))
    assert ((md > 0) == (cd > 0)).all()


# --- finite-difference gradient checks for the round-2 differentiable
# ops (reference test strategy: check_numeric_gradient oracle) --------------

def test_numeric_gradients_round2_ops():
    from mxnet_tpu.test_utils import check_numeric_gradient

    rs = onp.random.RandomState(0)
    x = rs.rand(1, 2, 4, 4).astype("f")
    check_numeric_gradient(
        lambda a: cops.adaptive_avg_pooling(a, 2), [x])
    check_numeric_gradient(
        lambda a: cops.bilinear_resize_2d(a, 6, 6), [x])
    check_numeric_gradient(lambda a: cops.div_sqrt_dim(a), [x])
    qkv = rs.rand(3, 1, 2 * 3 * 2).astype("f") * 0.5
    check_numeric_gradient(
        lambda a: cops.interleaved_matmul_selfatt_qk(a, 2), [qkv])


def test_numeric_gradient_sldwin():
    from mxnet_tpu.test_utils import check_numeric_gradient

    rs = onp.random.RandomState(1)
    B, L, H, D, w = 1, 4, 1, 3, 1
    q = rs.rand(B, L, H, D).astype("f") * 0.5
    k = rs.rand(B, L, H, D).astype("f") * 0.5
    dil = mx.np.array([1])
    check_numeric_gradient(
        lambda a, b: cops.sldwin_atten_score(a, b, dil, w=w), [q, k])


def test_numeric_gradient_psroi():
    from mxnet_tpu.test_utils import check_numeric_gradient

    rs = onp.random.RandomState(2)
    x = rs.rand(1, 4, 4, 4).astype("f")
    rois = mx.np.array(onp.array([[0, 0, 0, 3, 3]], "f"))
    check_numeric_gradient(
        lambda a: cops.psroi_pooling(a, rois, 1.0, 1, 2), [x])
