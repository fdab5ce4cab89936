"""``correct`` at a toy size on the CPU: a sound run passes, the control
(the reference in the next precision down) fails, and a run whose timed
path is broken underneath comes out as not correct."""
import json

import pytest

import control
import run as harness


def _run(capsys, monkeypatch, workload, seed=1, seconds=1.0):
    monkeypatch.setenv("PYTHONHASHSEED", "0")     # no re-exec inside a test
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", "0"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    checks = {l["check"]: l for l in lines if "check" in l}
    return rc, lines[-1], checks


@pytest.mark.parametrize("workload", ["toy_train", "toy_train_bert"])
def test_sound_run_is_correct_and_shaped_like_the_contract(
        capsys, monkeypatch, workload):
    rc, result, checks = _run(capsys, monkeypatch, workload, seconds=2.0)
    assert rc == 0 and result["correct"] is True, checks
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert "setup_s" in result["metrics"] and len(result["metrics"]) >= 2
    assert result["attempted"] > 0 and result["failed"] == 0


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


def test_a_cell_refuses_the_cpu(capsys, monkeypatch):
    monkeypatch.setenv("PYTHONHASHSEED", "0")
    rc = harness.main(["--workload", "resnet50.train.b256", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and "needs a TPU" in out.err
    assert not [l for l in out.out.splitlines() if l.startswith("{")]
