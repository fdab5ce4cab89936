"""The looped cell's own pieces on the CPU: its counts against counts by
hand, its four readers on a small hand-made trace (with the loop's own
``while`` events, which must not be counted), and ``correct`` at a toy
size (a sound run passes, the fp8 control does not)."""
import importlib
import json
import math
import os

import pytest

import control_large
import flops
import kernel_counts
import kernel_counts_looped
import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "ouro_2_6b.train.looped.b1s8192"
READERS = ("device_loop_ms.train", "device_exit_head_ms.train",
           "loop_flash_roofline_pct.train", "looped_stack_copies")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg(name):
    return harness._load_json("configs", name + ".json")


# -- counts by hand ------------------------------------------------------------

def test_forward_flops_of_the_cell_by_hand():
    cfg = _cfg("ouro_2_6b_l6")
    s = 8192
    proj = 2 * s * 4 * 2048 * 2048              # q, k, v, o: 16 heads of 128
    mlp = 2 * s * 3 * 2048 * 5632
    attn = 2 * (s * (s + 1) // 2) * (128 + 128) * 16
    head = 2 * (s - 1) * 2048 * 49152
    assert kernel_counts_looped.applications(cfg) == 24
    want = 24 * (proj + mlp + attn) + 4 * head
    assert kernel_counts_looped.forward(cfg) == want
    assert flops.forward_flops(cfg) == want
    # ISSUE 43: a layer application 0.842 + 0.275 = 1.117 TFLOP, an exit's
    # head 1.649, a step 33.4 forward and 100.2 trained; 80% the loop
    assert 0.841e12 < proj + mlp < 0.843e12 and 0.274e12 < attn < 0.276e12
    assert 1.648e12 < head < 1.650e12
    assert 33.3e12 < want < 33.5e12
    assert 100.1e12 < flops.train_flops(cfg) < 100.4e12
    assert 0.80 < 24 * (proj + mlp + attn) / want < 0.81


def test_the_flash_kernels_counts_by_hand():
    cfg = _cfg("ouro_2_6b_l6")
    fl, by = kernel_counts_looped.attention_kernels(cfg, 1)
    pairs = 8192 * 8193 // 2
    assert fl == 3 * (2 * pairs * 256 * 16) * 24        # fwd + 2x bwd
    assert 19.7e12 < fl < 19.9e12
    tensor = 16 * 8192 * 128                            # q, k, v, o alike
    # q, k, v: read by 3 kernels; dQ, dK, dV, o written; dO read by 2
    assert by == 2 * (3 * 3 + 4 + 2) * tensor * 24
    peaks = flops.peaks("TPU v5 lite")
    assert kernel_counts.roofline_seconds(fl, by, peaks) == fl / 197e12


def test_the_parameters_add_up_to_the_issues_count():
    """509,661,185 parameters, 8.15 GB at 16 B (ISSUE 43)."""
    cfg = _cfg("ouro_2_6b_l6")
    ref = importlib.import_module("reference." + cfg["builder"])
    specs = {name: shape for name, shape, *_ in ref.param_specs(cfg)}
    trained = sum(math.prod(s) for n, s in specs.items() if ref.trainable(n))
    layer = sum(math.prod(s) for n, s in specs.items()
                if n.startswith("model.layers.0."))
    assert layer == 51_388_416
    assert trained == 6 * layer + 2 * 49152 * 2048 + 2048 + 2049 \
        == 509_661_185
    assert 8.15e9 < 16 * trained < 8.16e9
    assert [n for n in specs if not ref.trainable(n)] == [
        "exit_loss.running_exit_mass"]


# -- the readers on a small trace ------------------------------------------------

@pytest.fixture
def small(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    with open(os.path.join(HERE, "trace_ouro_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": dict(d["op_scopes"]))
    trace = trace_reduce.reduce(devices, [], kernels=d["kernels"])
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "batch": 1,
           "device_kind": "TPU v5 lite", "cfg": d["cfg"]}
    gauge = ti.looped_stack_copies
    gauge.set(1)
    yield trace, run, d
    gauge.clear()


def test_readers_on_the_small_trace(small):
    trace, run, d = small
    read = lambda name: harness._load_reader(name).read(trace, run)  # noqa: E731
    # a step, inside the loop's body: norm 1 (its op_name starts at the
    # scope) + q 2 + rotary 0.5 + flash 4 + mlp 3 us forward, 6 + 8 us
    # backward; the two while events (10.7 and 14.2 us) stay out
    assert read("device_loop_ms.train") == pytest.approx(24.5e-3)
    whiles = [n for n, s in d["op_scopes"].items() if s.endswith("/while")]
    assert len(whiles) == 4 and all(trace["op_s"][n] > 0 for n in whiles)
    # the exits: head 2 + 1 (no prefix) forward, 3 backward; gate and loss
    # 0.5 each; the head's own two while events (3.2 and 3.1 us, scoped
    # lm_head/while) stay out
    assert read("device_exit_head_ms.train") == pytest.approx(7e-3)
    fl, by = kernel_counts_looped.attention_kernels(run["cfg"], 1)
    assert read("loop_flash_roofline_pct.train") == pytest.approx(
        100 * (fl / 197e12) * 2 / 36e-6)     # flash kernels alone: 4 + 6 + 8
    assert read("looped_stack_copies") == 1
    # the two together stay under the step's device time
    step_ms = trace["window_s"] * 1e3 / 2
    assert read("device_loop_ms.train") + read(
        "device_exit_head_ms.train") < step_ms
    # the accepted readers see the same program their own way
    assert read("device_attention_ms.train") == pytest.approx(18e-3)
    assert read("device_qk_prep_ms.train") == pytest.approx(0.5e-3)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(small, monkeypatch,
                                                    name):
    """The parent's program: no scopes and no gauge; and a program that
    never ran the model: scopes of another."""
    from mxnet_tpu.telemetry import instruments as ti

    trace, run, _d = small
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    monkeypatch.delattr(ti, "looped_stack_copies")
    bare = dict(trace, kernels=[])
    assert harness._load_reader(name).read(bare, run) is None
    monkeypatch.setattr(
        program_spans, "op_scopes", lambda block="whole_step": {
            k: "jit(whole_step)/jvp(forward)/lm_head/dot_general"
            for k in bare["op_s"]})
    other = dict(run, cfg={k: v for k, v in run["cfg"].items()
                           if k != "total_ut_steps"})
    assert harness._load_reader(name).read(bare, other) is None


def test_a_program_that_traced_no_looped_stack_reads_no_copies(small):
    from mxnet_tpu.telemetry import instruments as ti

    trace, run, _d = small
    ti.looped_stack_copies.clear()
    assert harness._load_reader("looped_stack_copies").read(trace, run) is None


# -- correct, at a toy size ------------------------------------------------------

def _run(capsys, monkeypatch, workload, seed, trace=0):
    monkeypatch.setenv("PYTHONHASHSEED", "0")     # no re-exec inside a test
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1.0", "--trace", str(trace)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return rc, lines, {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_the_toy_preset_is_correct_through_run_py(capsys, monkeypatch, seed):
    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_ouro", seed)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True, checks
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert checks["retraces_in_window"]["value"] == 0
    assert {"setup_s", "train_samples_s"} <= set(result["metrics"])


def test_a_traced_toy_run_reads_the_cells_scopes_and_gauges(capsys,
                                                            monkeypatch):
    """An unlisted workload reports every reader that finds something:
    this cell's among them, beside the accepted ones."""
    from mxnet_tpu.telemetry import instruments as ti

    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_ouro", 3,
                             trace=1)
    result = lines[-1]
    assert rc == 0, checks
    metrics = result["metrics"]
    # device time by scope is the chip's to give: here the names alone
    assert {"device_loop_ms.train", "device_exit_head_ms.train",
            "looped_stack_copies"} <= set(metrics)
    assert "loop_flash_roofline_pct.train" not in metrics   # no kernel here
    assert metrics["looped_stack_copies"]["value"] == 1
    assert metrics["host_scalar_operands.train"]["value"] == 4
    assert ti.ut_steps.value == 4
    mass = ti.flush_exit_mass()
    assert len(mass) == 4 and sum(mass) == pytest.approx(1.0, abs=1e-5)
    assert 0.01 < min(mass) and max(mass) < 0.9


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_ouro.json")
    cfg = _cfg(wl["config"])
    nums = control_large.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap", "grad_norm_gap.weights_median",
                        "dw_norm_gap", "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "grad_norm_gap" in over, nums
    assert "dw_norm_gap.weights_median" in over, nums


def test_the_cells_files_say_the_cut():
    cfg = _cfg("ouro_2_6b_l6")
    wl = harness._load_json("workloads", CELL + ".json")
    with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as f:
        rows = [json.loads(l) for l in f if '"Ouro-2.6B"' in l]
    for row in rows:            # every key of the catalog's config
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert row["source_url"] in cfg["source"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert (cfg["num_hidden_layers"], cfg["total_ut_steps"]) == (6, 4)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"]) == (
                2048, 16, 16, 128, 5632, 49152)
    assert "no layer is divided" in cfg["deployment"]
    assert "seven further pipeline stages" in cfg["deployment"]
    assert len(cfg["source"]) < 200
    assert (wl["driver"], wl["chips"], wl["traffic_params"]) == (
        "train_step_large", 1, {"batch": 1, "pool": 4})
    assert (cfg["seq"], cfg["remat"], cfg["dtype"]) == (8192, True,
                                                        "bfloat16")
    limits = cfg["limits"]["train_step"]
    assert "read on the chip" in limits["reason"]
    for words in ("batch 1 sequence of 8192 tokens",
                  "drawn uniformly from the 49,152 rows",
                  "pool of 4 seeded resident batches",
                  "loss fetched every 10th step", "ONE rolled loop",
                  "80% of it the looped stack", "would be 3%",
                  "six layers make the host's share larger",
                  "8.15 GB at 16 B"):
        assert words in wl["why"], words
    for key in ("entropy_beta", "biases", "loop", "gate", "optimizer",
                "weights", "seq", "remat"):
        assert key in cfg["assumed"], key
