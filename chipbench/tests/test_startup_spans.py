"""The set-up readers on the program's start-up timeline: on a small
hand-made ring (ring_startup_small.json), on a ring recorded here, and on
a program that has none of it."""
import json
import os
import time

import pytest

import run as harness
import startup_spans

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("startup_import_s", "state_build_s", "step_trace_s",
           "step_lower_s", "step_cache_load_s", "step_programs_obtained",
           "compile_capture_s", "other_programs_s", "other_programs",
           "setup_accounted_pct")


def _read(name, run):
    return harness._load_reader(name).read({}, run)


@pytest.fixture
def small(monkeypatch):
    with open(os.path.join(HERE, "ring_startup_small.json")) as f:
        d = json.load(f)
    from mxnet_tpu.diagnostics import spans

    monkeypatch.setattr(spans, "records", lambda: list(d["records"]))
    return d


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_small_ring(small, name):
    # set-up is [100, 120): import 3.0; block.initialize [106, 107] +
    # amp.convert [107, 108] + train_step.build [108, 109] with
    # trainer.create_states inside it = 3.0 as a union (3.8 as a sum);
    # whole_step traced 4.0 (flash_attention_fwd's 1.0 inside it), lowered
    # 1.0 + 0.5 by the capture, loaded 2.8 + 0.1 by the capture: obtained
    # twice; the capture 1.0; the others _make 0.8 + cast_params 0.7 +
    # create_states 0.5 + grad_norms 0.5 = 2.5 in four executables;
    # covered: [101, 104] + [105, 105.8] + [106, 118.55] + [118.6, 119.1]
    # + [119.2, 119.5] = 17.15 of 20.  ref_step came after the window
    assert _read(name, small["run"]) == pytest.approx(small["expect"][name])


def test_set_up_ends_where_the_window_begins(small):
    recs, begin, end = startup_spans.of_setup(small["run"])
    assert (begin, end) == (100.0, 120.0)
    assert not any(r.get("fun") == "ref_step" for r in recs)
    assert sum(r["name"] == "train_step" for r in recs) == 2
    # one step of three in the window: the second belongs to set-up
    one = dict(small["run"], steps=1, setup_s=20.5)
    assert startup_spans.of_setup(one)[2] == 120.5
    assert _read("setup_accounted_pct", one) == pytest.approx(
        100 * 17.65 / 20.5)
    # a run that knows no step count or set-up time reads nothing
    assert startup_spans.of_setup({"steps": 0, "setup_s": 20.0}) is None
    assert startup_spans.of_setup({"steps": 2}) is None


def test_a_step_that_was_built_loaded_nothing(small):
    for r in small["records"]:
        if r["name"] == "xla.backend" and r.get("fun") == "whole_step":
            r["how"] = "built"
    small["records"][:] = [
        r for r in small["records"]
        if not (r["name"] == "xla.cache_load"
                and r.get("fun") == "whole_step")]
    assert _read("step_cache_load_s", small["run"]) == 0.0
    assert _read("step_programs_obtained", small["run"]) == 2


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_on_a_program_without_the_records(
        monkeypatch, small, name):
    """The parent of the PR that added them: it has the `train_step`
    spans and `train_step.compile_capture` too, and no `startup.import`,
    no `xla.*`; no reader reports a half-timeline, none raises."""
    from mxnet_tpu.diagnostics import spans

    parents = [{k: v for k, v in r.items() if k != "backdated"}
               for r in small["records"]
               if not r.get("backdated")
               and not r["name"].startswith(("compile_capture.",
                                             "startup.", "block.", "amp.",
                                             "trainer.create"))
               and r["name"] != "train_step.build"]
    assert any(r["name"] == "train_step.compile_capture" for r in parents)
    monkeypatch.setattr(spans, "records", lambda: parents)
    assert _read(name, small["run"]) is None
    # and on the bare records of test_program_spans' parent
    monkeypatch.setattr(spans, "records", lambda: [
        {"name": "whole_step", "dur": 0.05}])
    assert _read(name, small["run"]) is None


def test_readers_on_a_ring_recorded_here():
    """A toy TrainStep through the program's real spans and JAX's real
    events: every reader finds its records, and the parts fit the whole."""
    import mxnet_tpu as mx
    from mxnet_tpu.diagnostics import spans
    from mxnet_tpu.gluon import Trainer, TrainStep, nn

    # this process imported the package long ago (and an earlier test may
    # have emptied the ring): the import as it would stand in a fresh one
    t_process = time.perf_counter()
    time.sleep(0.002)
    spans.record("startup.import", "startup", t_process, 0.002)
    net = nn.HybridSequential()
    net.add(nn.Dense(23, activation="relu"), nn.Dense(7))
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05, "momentum": 0.9})
    step = TrainStep(net, lambda out: (out * out).sum(axis=-1), trainer)
    x = mx.np.ones((4, 23))
    step(x, batch_size=4)
    setup_s = time.perf_counter() - t_process
    for _ in range(3):
        step(x, batch_size=4)
    run = {"steps": 3, "setup_s": setup_s}
    got = {n: _read(n, run) for n in READERS}
    assert all(v is not None for v in got.values()), got
    assert got["startup_import_s"] == 0.002     # not an older process's
    assert got["step_programs_obtained"] >= 1
    assert got["step_trace_s"] > 0 and got["step_lower_s"] > 0
    assert got["compile_capture_s"] > 0 and got["state_build_s"] > 0
    assert got["other_programs"] >= 1 and got["other_programs_s"] > 0
    assert 0 < got["setup_accounted_pct"] <= 100.0 + 1e-6
    first = [r for r in startup_spans.of_setup(run)[0]
             if r["name"] == "train_step"][0]
    assert got["step_trace_s"] + got["step_lower_s"] \
        + got["compile_capture_s"] <= first["dur"]
