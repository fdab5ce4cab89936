"""Misc API parity: callbacks, monitor, model checkpoints, name/attr
scopes, visualization (reference: python/mxnet/{callback,monitor,model,
name,attribute,visualization}.py)."""
import logging

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import callback, gluon
from mxnet_tpu import symbol as sym


def test_speedometer_and_log_metric(caplog):
    m = gluon.metric.Accuracy()
    m.update(mx.np.array([0, 1]), mx.np.array([[0.9, 0.1], [0.2, 0.8]]))
    sp = callback.Speedometer(batch_size=32, frequent=2)
    lg = callback.log_train_metric(period=2)
    with caplog.at_level(logging.INFO):
        for nbatch in range(1, 5):
            param = callback.BatchEndParam(epoch=0, nbatch=nbatch,
                                           eval_metric=m, locals=None)
            sp(param)
            lg(param)
    assert any("Speed" in r.message for r in caplog.records)
    assert any("Train-accuracy" in r.message for r in caplog.records)


def test_do_checkpoint(tmp_path):
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    cb = callback.do_checkpoint(str(tmp_path / "model"), period=2)
    cb(0, net=net)   # epoch 0: not a multiple
    cb(1, net=net)   # epoch 1: (1+1) % 2 == 0 -> saves
    import os

    assert not os.path.exists(str(tmp_path / "model-0001.params"))
    assert os.path.exists(str(tmp_path / "model-0002.params"))


def test_monitor_records_block_outputs():
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(4, in_units=3), gluon.nn.Activation("relu"))
    net.initialize()
    mon = mx.Monitor(interval=1, pattern=".*").install(net)
    mon.tic()
    net(mx.np.ones((2, 3)))
    rows = mon.toc()
    assert len(rows) >= 2  # Dense + Activation outputs
    names = {r[1] for r in rows}
    assert any("Dense" in n for n in names)
    assert all(np.isfinite(r[2]) for r in rows)


def test_model_checkpoint_roundtrip(tmp_path):
    x = sym.var("data")
    w = sym.var("w")
    out = sym.op.FullyConnected(x, w, no_bias=True, num_hidden=4)
    arg = {"w": mx.np.random.normal(0, 1, size=(4, 3))}
    aux = {"stat": mx.np.ones((2,))}
    prefix = str(tmp_path / "ck")
    mx.model.save_checkpoint(prefix, 7, out, arg, aux)
    s2, arg2, aux2 = mx.model.load_checkpoint(prefix, 7)
    assert s2 is not None
    assert np.allclose(arg2["w"].asnumpy(), arg["w"].asnumpy())
    assert np.allclose(aux2["stat"].asnumpy(), 1.0)
    res = s2.eval(data=mx.np.ones((2, 3)), w=arg2["w"])
    assert res[0].shape == (2, 4)


def test_name_manager_and_prefix():
    nm = mx.name.NameManager()
    assert nm.get(None, "dense") == "dense0"
    assert nm.get(None, "dense") == "dense1"
    assert nm.get("explicit", "dense") == "explicit"
    with mx.name.Prefix("resnet_"):
        got = mx.name.current().get(None, "conv")
        assert got.startswith("resnet_conv")


def test_attr_scope_nesting():
    with mx.AttrScope(group="backbone"):
        a = mx.attribute.current().get()
        assert a["group"] == "backbone"
        with mx.AttrScope(lr_mult="0.1"):
            b = mx.attribute.current().get({"name": "x"})
            assert b["group"] == "backbone"
            assert b["lr_mult"] == "0.1"
            assert b["name"] == "x"
    assert "group" not in mx.attribute.current().get()


def test_name_prefix_applies_to_symbols():
    with mx.name.Prefix("net_"):
        s = sym.op.Activation(sym.var("x"), "relu")
    assert s.name.startswith("net_activation")


def test_attr_scope_applies_to_symbols():
    with mx.AttrScope(lr_mult="0.1"):
        s = sym.op.Activation(sym.var("x"), "relu")
    assert s.attr("lr_mult") == "0.1"
    s2 = sym.op.Activation(sym.var("x"), "relu")
    assert s2.attr("lr_mult") is None


def test_attr_scope_reuse_not_corrupted():
    sc = mx.AttrScope(a="1")
    with mx.AttrScope(b="2"):
        with sc:
            assert sc.get() == {"b": "2", "a": "1"}
    assert sc.get() == {"a": "1"}  # exiting restored the scope's own attrs


def test_monitor_reinstall_no_double_count():
    net = gluon.nn.Dense(2, in_units=2)
    net.initialize()
    mon = mx.Monitor(interval=1)
    mon.install(net)
    mon.install(net)  # must replace, not stack
    mon.tic()
    net(mx.np.ones((1, 2)))
    rows = mon.toc()
    assert len(rows) == 1
    mon.uninstall()
    mon.tic()
    net(mx.np.ones((1, 2)))
    assert mon.toc() == []


def test_print_summary_and_plot(capsys):
    x = sym.var("data")
    w = sym.var("w")
    out = sym.op.Activation(
        sym.op.FullyConnected(x, w, no_bias=True, num_hidden=4), "relu")
    txt = mx.print_summary(out, shape={"data": (2, 3), "w": (4, 3)})
    assert "Layer (type)" in txt
    assert "fullyconnected" in txt.lower()
    dot = mx.plot_network(out)
    src = dot if isinstance(dot, str) else dot.source
    assert "digraph" in src and "->" in src


def test_env_var_registry():
    """Typed env registry (reference: env_var.md + dmlc::GetEnv point
    reads)."""
    import mxnet_tpu as mx

    assert mx.env.get("MXNET_ENGINE_TYPE") in (
        "ThreadedEnginePerDevice", "NaiveEngine")
    assert isinstance(mx.env.get("MXTPU_DISABLE_NATIVE"), bool)
    assert mx.env.get("MXTPU_SERVE_MAX_BATCH") == 32
    d = mx.env.doc()
    assert "MXNET_ENGINE_TYPE" in d and "MXTPU_DISABLE_NATIVE" in d
    assert len(mx.env.all_vars()) >= 12
    # typed override
    import os
    os.environ["MXTPU_SERVE_MAX_BATCH"] = "128"
    try:
        assert mx.env.get("MXTPU_SERVE_MAX_BATCH") == 128
    finally:
        del os.environ["MXTPU_SERVE_MAX_BATCH"]
