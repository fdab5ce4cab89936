"""``correct`` at a toy size on the CPU: a sound run passes, the control
(the reference in the next precision down) fails, and a run whose timed
path is broken underneath comes out as not correct."""
import json

import pytest

import control
import run as harness
from drivers import train_step


def _run(capsys, monkeypatch, workload, seed=1, seconds=1.0):
    monkeypatch.setenv("PYTHONHASHSEED", "0")     # no re-exec inside a test
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    checks = {l["check"]: l for l in lines if "check" in l}
    return rc, lines[-1], checks


# The whole-step programs that the toy ResNet's drivers ran, by driver:
# the loader driver must run the very program of the pool driver's cell.
_RESNET_STEPS = {}


def _step_program():
    import hashlib

    from mxnet_tpu.diagnostics import introspect

    return {hashlib.sha256(json.dumps(sorted(e["op_scopes"].items())
                                      ).encode()).hexdigest()[:16]
            for k, e in introspect.compile_registry().items()
            if k[0] == "whole_step"}


@pytest.mark.parametrize("workload", ["toy_train", "toy_train_bert",
                                      "toy_train_loader"])
def test_sound_run_is_correct_and_shaped_like_the_contract(
        capsys, monkeypatch, workload):
    from mxnet_tpu.diagnostics import introspect

    introspect.reset()
    rc, result, checks = _run(capsys, monkeypatch, workload, seconds=2.0)
    assert rc == 0 and result["correct"] is True, checks
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    assert result["attempted"] > 0 and result["failed"] == 0
    # every number compared, beside its limit, under the result's last key
    assert list(result)[-1] == "checks"
    assert result["checks"] == {n: [c["value"], c["limit"]]
                                for n, c in checks.items()}
    if workload != "toy_train_bert":
        _RESNET_STEPS[workload] = prog = _step_program()
        assert len(prog) == 1 and set(map(frozenset, _RESNET_STEPS.values())
                                      ) == {frozenset(prog)}
    if workload == "toy_train_loader":
        assert {"batch_bits_differing", "batches_out_of_order",
                "samples_not_consumed"} <= set(checks)
        assert result["attempted"] + 9 == int(
            checks["batches_out_of_order"]["note"].split()[0])


def test_cell_1s_step_is_the_one_its_driver_built_before_the_split():
    """``Program`` builds, from cell 1's own files at its own batch, the
    step the driver built inline before its parts were factored out: one
    lowered text (lowered on the CPU, never compiled or run)."""
    import hashlib
    import importlib
    import types

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import NDArray

    wl = harness._load_json("workloads", "resnet50.train.b256.json")
    cfg = harness._load_json("configs", wl["config"] + ".json")
    ref = importlib.import_module("reference." + cfg["builder"])
    model = importlib.import_module("models." + cfg["builder"])
    weights = {n: jnp.zeros(s, jnp.float32)
               for n, s, *_ in ref.param_specs(cfg)}
    batch = [NDArray(jnp.zeros(s, jnp.float32 if k == "uniform"
                               else jnp.int32))
             for s, k, *_ in ref.input_specs(
                 cfg, wl["traffic_params"]["batch"])]

    class Lowered(Exception):
        pass

    def lowered(step):
        jitted = step._jitted

        def intercept(donate):
            def lower_only(*a):
                raise Lowered(jitted(donate).lower(*a).as_text())
            return lower_only

        step._jitted = intercept
        with pytest.raises(Lowered) as e:
            step(*batch)
        return hashlib.sha256(e.value.args[0].encode()).hexdigest()

    opt = cfg["optimizer"]          # the driver's run() before PR 28
    net = model.build(mx, cfg, weights, mx.tpu(0))
    loss_fn, n_data = model.loss(mx, cfg)
    trainer = gluon.Trainer(
        net.collect_params(), opt["name"],
        {k: v for k, v in opt.items() if k != "name"}, kvstore="tpu_dist")
    before = lowered(gluon.TrainStep(net, loss_fn, trainer, n_data=n_data))
    h = types.SimpleNamespace(cfg=cfg, model=model, mx=mx, jax=jax)
    assert lowered(train_step.Program(h, weights).step) == before


@pytest.mark.parametrize("workload, names", [
    ("toy_train", {"enqueue_step", "fetch_loss"}),
    ("toy_train_loader", {"next_batch", "enqueue_step", "fetch_loss"})])
def test_the_windows_annotations(capsys, monkeypatch, workload, names):
    """What the loop writes into a trace, which ``idle_gaps`` is read
    from: the pool driver's window names what it named before the loader
    driver shared it, and only the loader's feed adds ``next_batch``."""
    seen = set()
    annotate = harness.Harness.annotate
    monkeypatch.setattr(
        harness.Harness, "annotate",
        lambda self, name: (seen.add(name), annotate(self, name))[1])
    rc, result, checks = _run(capsys, monkeypatch, workload, seconds=2.0)
    assert rc == 0 and result["correct"] is True, checks
    assert result["attempted"] >= train_step.FETCH_EVERY    # a fetch was due
    assert seen == names


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_bert.json")
    cfg = harness._load_json("configs", wl["config"] + ".json")
    nums = control.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap.weights_median", "dw_norm_gap",
                        "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "dw_norm_gap.weights_median" in over, nums


@pytest.mark.parametrize("fault", ["frozen_state", "half_batch"])
@pytest.mark.parametrize("workload", ["toy_train", "toy_train_bert"])
def test_a_broken_training_step_is_not_correct(capsys, monkeypatch,
                                               workload, fault):
    import jax.numpy as jnp

    from mxnet_tpu import gluon
    from mxnet_tpu.ndarray.ndarray import NDArray

    class Broken(gluon.TrainStep):
        def __call__(self, *batch, **kw):
            if fault == "half_batch":       # half of the rows left out
                h = batch[0].shape[0] // 2
                batch = tuple(NDArray(jnp.concatenate(
                    [a._data[:h], a._data[:h]])) for a in batch)
                return super().__call__(*batch, **kw)
            params = list(self._net.collect_params().values())
            before = [jnp.copy(p.data()._data) for p in params]
            loss = super().__call__(*batch, **kw)
            for i, p in enumerate(self._trainer._params):
                st = self._trainer._states[i]   # (master, inner) or inner
                if isinstance(st, tuple) and isinstance(st[0], NDArray) \
                        and st[0].shape == p.shape \
                        and st[0].dtype != p.data().dtype:
                    st[0]._data = before[params.index(p)].astype(
                        st[0]._data.dtype)
            for p, w in zip(params, before):    # the state comes back as it was
                p.data()._data = w
            return loss

    monkeypatch.setattr(gluon, "TrainStep", Broken)
    rc, result, checks = _run(capsys, monkeypatch, workload)
    assert rc == 0 and result["correct"] is False
    failed = [n for n, c in checks.items() if not c["ok"]]
    if fault == "frozen_state":
        assert "dw_norm_gap" in failed, checks
    else:
        assert failed, checks


@pytest.mark.parametrize("fault", ["repeats_a_batch", "out_of_order",
                                   "no_flip"])
def test_a_broken_loader_is_not_correct(capsys, monkeypatch, fault):
    """The loader broken underneath the timed path: a batch delivered
    twice or out of the sampler's order fails by its labels, a dropped
    flip by the bits of the batches recomputed."""
    from drivers import train_loader

    class Broken(train_loader.DataLoader):
        def __iter__(self):
            it = super().__iter__()
            if fault == "repeats_a_batch":
                for k, batch in enumerate(it):
                    yield batch
                    if k == 8:          # inside the window
                        yield batch
            elif fault == "out_of_order":
                for batch in it:
                    yield next(it)
                    yield batch
            else:
                yield from it

    monkeypatch.setattr(train_loader, "DataLoader", Broken)
    if fault == "no_flip":
        monkeypatch.setattr(train_loader.imagedata, "transform",
                            lambda image, flip, mean, std, _t=train_loader
                            .imagedata.transform: _t(image, False, mean, std))
    rc, result, checks = _run(capsys, monkeypatch, "toy_train_loader")
    assert rc == 0 and result["correct"] is False
    failed = {n for n, c in checks.items() if not c["ok"]}
    if fault == "no_flip":
        assert "batch_bits_differing" in failed, checks
        assert "batches_out_of_order" not in failed
    else:
        assert "batches_out_of_order" in failed, checks


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_recomputed_batches_follow_the_sampler(seed):
    """The plain recomputation against the dataset it recomputes, with no
    loader between them: item by item, for the sampler's first batches."""
    import numpy as onp

    import imagedata
    from drivers import train_loader

    wl = harness._load_json("workloads", "toy_train_loader.json")
    cfg = harness._load_json("configs", wl["config"] + ".json")
    tp = wl["traffic_params"]
    pool = train_loader.make_pool(cfg, tp, seed)
    ds = train_loader.ImagePool(pool, seed, tp)
    onp.random.seed(imagedata.numpy_seed(seed))
    order = onp.random.permutation(len(ds))
    assert onp.array_equal(order, imagedata.sampler_order(seed, len(ds)))
    b = tp["loader"]["batch_size"]
    flips = 0
    for k, (x, y) in enumerate(train_loader.reference_batches(
            cfg, tp, seed, 3)):
        rows = [ds[int(i)] for i in order[k * b:(k + 1) * b]]
        assert onp.array_equal(x, onp.stack([r[0] for r in rows]))
        assert onp.array_equal(y, onp.stack([r[1] for r in rows]))
        assert x.dtype == onp.float32 and y.dtype == onp.int32
        flips += sum(imagedata.flipped(seed, i, tp["flip_p"])
                     for i in order[k * b:(k + 1) * b])
    assert 0 < flips < 3 * b        # some rows mirrored, not all


@pytest.mark.parametrize("cell", ["resnet50.train.b256",
                                  "resnet50.train.dataloader"])
def test_a_cell_refuses_the_cpu(capsys, monkeypatch, cell):
    monkeypatch.setenv("PYTHONHASHSEED", "0")
    rc = harness.main(["--workload", cell, "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and "needs a TPU" in out.err
    assert not [l for l in out.out.splitlines() if l.startswith("{")]
