"""io iterators, control flow, estimator, recordio tests
(reference: test_io.py, test_contrib_control_flow.py, estimator tests)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, io, npx, np, recordio
from mxnet_tpu.test_utils import assert_almost_equal


def test_ndarray_iter_pad():
    it = io.NDArrayIter(onp.arange(20).reshape(10, 2).astype("f"),
                        onp.arange(10).astype("f"), batch_size=4,
                        last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 2
    assert batches[0].data[0].shape == (4, 2)


def test_ndarray_iter_discard():
    it = io.NDArrayIter(onp.zeros((10, 2), "f"), batch_size=4,
                        last_batch_handle="discard")
    assert len(list(it)) == 2


def test_ndarray_iter_roll_over():
    it = io.NDArrayIter(onp.arange(10).astype("f"), batch_size=4,
                        last_batch_handle="roll_over", shuffle=False)
    epoch1 = list(it)
    assert len(epoch1) == 2  # remainder withheld
    it.reset()
    epoch2 = list(it)
    # first batch of epoch2 starts with the held-over samples [8, 9]
    first = epoch2[0].data[0].asnumpy()
    assert first.shape == (4,)
    assert first[0] == 8.0 and first[1] == 9.0


def test_csv_iter(tmp_path):
    path = tmp_path / "data.csv"
    onp.savetxt(path, onp.arange(12).reshape(6, 2), delimiter=",")
    it = io.CSVIter(str(path), data_shape=(2,), batch_size=3)
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (3, 2)


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "data.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(5):
        w.write(f"record-{i}".encode())
    w.close()
    r = recordio.MXRecordIO(path, "r")
    items = []
    while True:
        item = r.read()
        if item is None:
            break
        items.append(item)
    assert items == [f"record-{i}".encode() for i in range(5)]


def test_indexed_recordio_and_pack(tmp_path):
    path = str(tmp_path / "data.rec")
    w = recordio.MXIndexedRecordIO(str(tmp_path / "data.idx"), path, "w")
    for i in range(4):
        header = recordio.IRHeader(0, float(i), i, 0)
        w.write_idx(i, recordio.pack(header, f"payload{i}"))
    w.close()
    r = recordio.MXIndexedRecordIO(str(tmp_path / "data.idx"), path, "r")
    assert len(r) == 4
    header, payload = recordio.unpack(r.read_idx(2))
    assert header.label == 2.0
    assert payload == b"payload2"



def test_estimator_fit_and_validate(tmp_path):
    mx.seed(0)
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    est = gluon.contrib.estimator.Estimator(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        trainer=gluon.Trainer(net.collect_params(), "sgd",
                              {"learning_rate": 0.1}))
    ds = gluon.data.dataset.ArrayDataset(
        mx.np.array(np.random.uniform(size=(4, 3)).astype("float32")),
        mx.np.array([0, 1, 0, 1]))
    data = gluon.data.DataLoader(ds, batch_size=4)
    est.fit(data, val_data=data, epochs=2)
    result = est.evaluate(data)
    assert "val_accuracy" in result


def test_estimator_requires_one_stop_criterion():
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    est = gluon.contrib.estimator.Estimator(
        net, gluon.loss.SoftmaxCrossEntropyLoss())
    with pytest.raises(ValueError, match="exactly one"):
        est.fit([], epochs=None, batches=None)


def test_checkpoint_handler_best_not_rotated(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import CheckpointHandler
    from mxnet_tpu.gluon.metric import Accuracy

    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()

    class _Est:
        pass

    est = _Est()
    est.net = net
    est.trainer = None
    metric = Accuracy()
    metric.update(np.array([1]), np.array([[0.0, 1.0]]))
    h = CheckpointHandler(str(tmp_path), save_best=True, monitor=metric,
                          max_checkpoints=2, mode="max")
    import os

    for _ in range(5):
        h.epoch_end(est)
    # saves are async through the engine; block like any reader would
    from mxnet_tpu._checkpoint_io import wait_for_path

    wait_for_path(str(tmp_path / "model-best.params"))
    assert os.path.exists(str(tmp_path / "model-best.params"))


def test_dataloader_process_workers():
    """Multiprocessing worker mode (reference default,
    dataloader.py:123-305): fork workers batchify numpy into shared
    memory; parent converts to device arrays; order preserved."""
    import numpy as onp

    from mxnet_tpu.gluon.data import DataLoader

    class NumpyDataset:
        def __len__(self):
            return 32

        def __getitem__(self, i):
            return (onp.full((3,), i, dtype="float32"),
                    onp.int64(i % 4))

    loader = DataLoader(NumpyDataset(), batch_size=8, num_workers=2)
    seen = []
    for x, y in loader:
        assert x.shape == (8, 3)
        seen.extend(x.asnumpy()[:, 0].astype(int).tolist())
    assert seen == list(range(32))


def test_dataloader_process_workers_ndarray_fallback():
    """Datasets yielding device arrays must NOT fork (jax is not
    fork-safe): the loader's workers are threads."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.data import ArrayDataset, DataLoader

    ds = ArrayDataset(mx.np.ones((16, 4)), mx.np.zeros((16,)))
    loader = DataLoader(ds, batch_size=4, num_workers=2)
    assert loader._thread_bound()
    batches = list(loader)
    assert len(batches) == 4


def test_batch_processor_custom():
    """Custom BatchProcessor drives the inner loop (reference:
    estimator/batch_processor.py)."""
    import numpy as onp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    from mxnet_tpu.gluon.contrib.estimator.batch_processor import (
        BatchProcessor,
    )

    calls = {"fit": 0, "eval": 0}

    class Doubler(BatchProcessor):
        def fit_batch(self, estimator, batch, batch_axis=0):
            calls["fit"] += 1
            return super().fit_batch(estimator, batch, batch_axis)

        def evaluate_batch(self, estimator, batch, batch_axis=0):
            calls["eval"] += 1
            return super().evaluate_batch(estimator, batch, batch_axis)

    net = gluon.nn.Dense(3)
    net.initialize()
    rs = onp.random.RandomState(0)
    ds = gluon.data.ArrayDataset(rs.rand(12, 4).astype("f"),
                                 (rs.rand(12) * 3).astype("i"))
    loader = gluon.data.DataLoader(ds, batch_size=4)
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    batch_processor=Doubler())
    est.fit(loader, val_data=loader, epochs=2)
    assert calls["fit"] == 6 and calls["eval"] == 6


def test_estimator_val_net_and_loss():
    """Separate validation net/loss sharing parameters (reference:
    estimator.py val_net/val_loss)."""
    import numpy as onp

    from mxnet_tpu.gluon.contrib.estimator import Estimator

    net = gluon.nn.Dense(3)
    net.initialize()
    calls = {"val": 0}

    class ValWrapper(gluon.nn.HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner

        def forward(self, x):
            calls["val"] += 1
            return self.inner(x)

    rs = onp.random.RandomState(0)
    ds = gluon.data.ArrayDataset(rs.rand(8, 4).astype("f"),
                                 (rs.rand(8) * 3).astype("i"))
    loader = gluon.data.DataLoader(ds, batch_size=4)
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    val_net=ValWrapper(net),
                    val_loss=gluon.loss.SoftmaxCrossEntropyLoss())
    est.fit(loader, val_data=loader, epochs=1)
    assert calls["val"] == 2  # val runs through the wrapper


def test_gradient_update_handler_owns_the_step():
    """The optimizer step runs through GradientUpdateHandler (reference:
    event_handler.py:722, default-added by fit) — a custom replacement
    with a different priority can reorder or suppress updates."""
    import numpy as onp

    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.contrib.estimator import (Estimator,
                                                   GradientUpdateHandler)

    mx.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(4, in_units=6), gluon.nn.Dense(2))
    net.initialize()
    loss = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    est = Estimator(net, loss=loss,
                    train_metrics=gluon.metric.Accuracy(),
                    trainer=trainer)
    rs = onp.random.RandomState(0)
    ds = gluon.data.ArrayDataset(
        mx.np.array(rs.rand(32, 6).astype("f")),
        mx.np.array(rs.randint(0, 2, (32,))))
    loader = gluon.data.DataLoader(ds, batch_size=8)

    w0 = net[0].weight.data().asnumpy().copy()
    est.fit(loader, epochs=1)
    w1 = net[0].weight.data().asnumpy()
    assert onp.abs(w1 - w0).max() > 0  # default handler stepped

    class NoStep(GradientUpdateHandler):
        def batch_end(self, estimator, *args, **kwargs):
            return None  # suppress updates entirely

    est2 = Estimator(net, loss=loss,
                     train_metrics=gluon.metric.Accuracy(),
                     trainer=trainer)
    w1c = net[0].weight.data().asnumpy().copy()
    est2.fit(loader, epochs=1, event_handlers=[NoStep()])
    w2 = net[0].weight.data().asnumpy()
    onp.testing.assert_allclose(w2, w1c)  # custom handler suppressed step
