"""The SDAR cell's own pieces on the CPU: its counts against counts by
hand, its five readers on a small hand-made trace, and ``correct`` at a
toy size (a sound run passes, the fp8 control does not)."""
import json
import os

import pytest

import control_large
import flops
import kernel_counts
import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _cfg(name):
    return harness._load_json("configs", name + ".json")


# -- counts by hand ------------------------------------------------------------

def test_unmasked_pairs_by_enumeration():
    """Every (query, key) of 2 * seq positions, by the mask's rule."""
    for seq, blen in ((8, 4), (12, 2), (16, 16)):
        n = 0
        for i in range(2 * seq):
            for j in range(2 * seq):
                qn, kn = i < seq, j < seq
                qb, kb = (i % seq) // blen, (j % seq) // blen
                n += (qn and kn and qb == kb) or (qn and not kn and kb < qb) \
                    or (not qn and not kn and kb <= qb)
        assert kernel_counts.block_diffusion_pairs(seq, blen) == n
        assert n == seq * blen + seq * seq


def test_forward_flops_of_the_cell_by_hand():
    cfg = _cfg("sdar_30b_a3b_ep8")
    # a layer, one sequence of 8192 positions:
    proj = 8192 * 2 * 2048 * (4096 + 512 + 512 + 4096)   # q, k, v, o
    attn = 4 * (4096 * 4 + 4096 ** 2) * 128 * 32
    router = 8192 * 2 * 2048 * 128
    rows = 8192 * 8 * 16 / 128                           # 8192 rows
    experts = rows * 3 * 2 * 2048 * 768
    head = 4096 * 2 * 2048 * 18992
    want = 6 * (proj + attn + router + experts) + head
    assert kernel_counts.expected_rows(cfg) == 8192
    assert kernel_counts.sdar_forward(cfg) == want
    assert flops.forward_flops(cfg) == want
    assert 4.30e12 < want < 4.33e12                      # ISSUE 34: ~4.3 TFLOP
    assert 25.8e12 < flops.train_flops(cfg) * 2 < 26.0e12


def test_the_two_kernel_counts_by_hand():
    cfg = _cfg("sdar_30b_a3b_ep8")
    fl, by = kernel_counts.attention_kernels(cfg, 2)
    pairs = 4096 * 4 + 4096 ** 2
    assert fl == 3 * (4 * pairs * 128 * 32) * 2 * 6      # fwd + 2x bwd
    q, kv = 2 * 32 * 8192 * 128, 2 * 4 * 8192 * 128
    assert by == 2 * 6 * (q + kv) * 6                    # 6 passes, 6 layers
    assert kernel_counts.roofline_seconds(fl, by, PEAKS) == fl / 197e12
    fl, by = kernel_counts.expert_kernels(cfg, 6 * 16384)
    assert fl == 3 * (6 * 16384) * 3 * 2 * 2048 * 768
    weights = 3 * 16 * 2048 * 768 * 6
    assert by == 2 * (3 * weights + 3 * 6 * 16384 * (2 * 2048 + 3 * 768))
    # few rows: the weights' bytes bound it; many: the operations do
    few = kernel_counts.expert_kernels(cfg, 6 * 256)
    assert kernel_counts.roofline_seconds(*few, PEAKS) == few[1] / 819e9
    assert kernel_counts.roofline_seconds(fl, by, PEAKS) == fl / 197e12


# -- the readers on a small trace ------------------------------------------------

@pytest.fixture
def small(monkeypatch):
    with open(os.path.join(HERE, "trace_sdar_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": dict(d["op_scopes"]))
    trace = trace_reduce.reduce(devices, [], kernels=d["kernels"])
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "batch": 2,
           "device_kind": "TPU v5 lite", "cfg": d["cfg"],
           "reference_held_rows": 48.0,
           "moe_load": {"model.layers.0.mlp": (40.0, 1.5),
                        "model.layers.1.mlp": (56.0, 1.25)}}
    return trace, run, d["cfg"]


def test_readers_on_the_small_trace(small):
    trace, run, cfg = small
    read = lambda name: harness._load_reader(name).read(trace, run)  # noqa: E731
    # a step: attention scope 1 + 4 + 6 us; expert layer 0.5 + 0.5 + 1 us
    # scoped and 3 + 5 us of ragged-dot kernels
    assert read("device_attention_ms.train") == pytest.approx(11e-3)
    assert read("device_moe_ms.train") == pytest.approx(10e-3)
    fl, by = kernel_counts.attention_kernels(cfg, 2)
    least = max(fl / 197e12, by / 819e9)
    assert read("attention_roofline_pct.train") == pytest.approx(
        100 * least * 2 / 20e-6)                # kernels alone: 4 + 6 us
    fl, by = kernel_counts.expert_kernels(cfg, 48.0)
    least = max(fl / 197e12, by / 819e9)
    assert read("moe_experts_roofline_pct.train") == pytest.approx(
        100 * least * 2 / 16e-6)
    assert read("moe_load_max_over_mean.train") == pytest.approx(1.375)


@pytest.mark.parametrize("name", [
    "device_attention_ms.train", "device_moe_ms.train",
    "attention_roofline_pct.train", "moe_experts_roofline_pct.train",
    "moe_load_max_over_mean.train"])
def test_a_reader_with_nothing_to_read_returns_none(small, monkeypatch,
                                                    name):
    """The parent's program: no scopes, no counters, no rows noted."""
    trace, run, _cfg_ = small
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    bare = {k: v for k, v in run.items()
            if k not in ("reference_held_rows", "moe_load")}
    trace = dict(trace, kernels=[], op_s={
        k: v for k, v in trace["op_s"].items() if "ragged" not in k})
    assert harness._load_reader(name).read(trace, bare) is None


# -- correct, at a toy size ------------------------------------------------------

def _run(capsys, monkeypatch, workload, seed, trace=0):
    monkeypatch.setenv("PYTHONHASHSEED", "0")     # no re-exec inside a test
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1.0", "--trace", str(trace)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return rc, lines[-1], {l["check"]: l for l in lines if "check" in l}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_the_toy_preset_is_correct_through_run_py(capsys, monkeypatch, seed):
    rc, result, checks = _run(capsys, monkeypatch, "toy_train_sdar", seed)
    assert rc == 0 and result["correct"] is True, checks
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert checks["retraces_in_window"]["value"] == 0
    assert {"setup_s", "train_samples_s"} <= set(result["metrics"])


def test_a_traced_toy_run_reads_the_cells_counters(capsys, monkeypatch):
    """An unlisted workload reports every reader that finds something:
    the program's load counter and the scoped device time among them."""
    rc, result, checks = _run(capsys, monkeypatch, "toy_train_sdar", 3,
                              trace=1)
    assert rc == 0 and result["correct"] is True, checks
    metrics = result["metrics"]
    assert 1.0 <= metrics["moe_load_max_over_mean.train"]["value"] <= 4.0
    # device time by scope is the chip's to give: here the names alone
    assert {"device_moe_ms.train", "device_attention_ms.train"} <= set(
        metrics)
    assert metrics["host_scalar_operands.train"]["value"] == 4


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_sdar.json")
    cfg = _cfg(wl["config"])
    nums = control_large.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap", "grad_norm_gap.weights_median",
                        "dw_norm_gap", "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "grad_norm_gap.weights_median" in over, nums
    assert "dw_norm_gap.weights_median" in over, nums


def test_the_cells_files_say_the_cut():
    cfg = _cfg("sdar_30b_a3b_ep8")
    wl = harness._load_json(
        "workloads", "sdar_30b_a3b.train.blockdiff.b2s4096.json")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert cfg["num_experts"] * cfg["ep_size"] == cfg["router_width"] == 128
    assert cfg["vocab_size"] * 8 == 151936 and cfg["mask_token_id"] == 18991
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (2048, 128, 32, 4, 768, 8)
    assert cfg["num_hidden_layers"] >= 4 and "8 chips" in cfg["deployment"]
    assert (wl["driver"], wl["chips"], wl["traffic_params"]) == (
        "train_step_large", 1,
        {"batch": 2, "pool": 5, "pool_seed": 4600000100})
    assert (cfg["seq"], cfg["block_length"]) == (4096, 4)
    for words in ("batch 2 sequences", "L = 4096 clean tokens each",
                  "block length 4", "pool of 5 resident batches",
                  "loss fetched every 10th step", "~1,024 rows a step",
                  "eight times its share"):
        assert words in wl["why"], words
