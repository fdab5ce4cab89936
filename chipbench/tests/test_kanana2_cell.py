"""The latent-attention cell's own pieces on the CPU: its counts against
counts by hand, its four readers on a small hand-made trace, and
``correct`` at a toy size (a sound run passes, the fp8 control does
not)."""
import json
import os

import pytest

import control_large
import flops
import kernel_counts_mla
import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kanana2_30b_a3b.train.causal.b2s8192"
READERS = ("device_mla_ms.train", "mla_flash_roofline_pct.train",
           "device_moe_shared_ms.train", "device_moe_routed_ms.train")


def _cfg(name):
    return harness._load_json("configs", name + ".json")


# -- counts by hand ------------------------------------------------------------

def test_causal_pairs_by_enumeration():
    for seq in (1, 7, 32):
        assert kernel_counts_mla.causal_pairs(seq) == sum(
            j <= i for i in range(seq) for j in range(seq))


def test_forward_flops_of_the_cell_by_hand():
    cfg = _cfg("kanana2_30b_a3b_ep8")
    s = 8192
    # every layer, one sequence: q, latent down, latent up, out
    proj = 2 * s * (2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
                    + 32 * 128 * 2048)
    attn = 2 * (s * (s + 1) // 2) * (192 + 128) * 32
    dense = 2 * s * 3 * 2048 * 6144
    rows = s * 6 * 16 / 128                              # 6144 rows
    sparse = (2 * s * 2048 * 128 + 2 * s * 3 * 2048 * 1536
              + rows * 2 * 3 * 2048 * 768)
    head = 2 * (s - 1) * 2048 * 16032
    want = 5 * (proj + attn) + dense + 4 * sparse + head
    assert kernel_counts_mla.expected_rows(cfg) == 6144
    assert kernel_counts_mla.forward(cfg) == want
    assert flops.forward_flops(cfg) == want
    assert 7.60e12 < want < 7.63e12          # ISSUE 38: 15.2 TFLOP a step
    assert 45.6e12 < flops.train_flops(cfg) * 2 < 45.8e12


def test_the_flash_kernels_counts_by_hand():
    cfg = _cfg("kanana2_30b_a3b_ep8")
    fl, by = kernel_counts_mla.attention_kernels(cfg, 2)
    pairs = 8192 * 8193 // 2
    assert fl == 3 * (2 * pairs * 320 * 32) * 2 * 5     # fwd + 2x bwd
    assert 20.5e12 < fl < 20.7e12                       # ISSUE 38: 20.6
    wide, narrow = 2 * 32 * 8192 * 192, 2 * 32 * 8192 * 128
    # q, k: read by 3 kernels, dQ, dK written; v: read by 3, dV, o
    # written, dO read by 2
    assert by == 2 * ((3 + 3 + 1 + 1) * wide + (3 + 1 + 1 + 2) * narrow) * 5
    peaks = flops.peaks("TPU v5 lite")
    import kernel_counts
    assert kernel_counts.roofline_seconds(fl, by, peaks) == fl / 197e12


# -- the readers on a small trace ------------------------------------------------

@pytest.fixture
def small(monkeypatch):
    with open(os.path.join(HERE, "trace_kanana2_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": dict(d["op_scopes"]))
    trace = trace_reduce.reduce(devices, [], kernels=d["kernels"])
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "batch": 2,
           "device_kind": "TPU v5 lite", "cfg": d["cfg"]}
    return trace, run, d["cfg"]


def test_readers_on_the_small_trace(small):
    trace, run, cfg = small
    read = lambda name: harness._load_reader(name).read(trace, run)  # noqa: E731
    # a step, under mla: q 1 + latent 0.5 + rope 1.5 + out 1 us and the
    # three kernels 4 + 6 + 8 us
    assert read("device_mla_ms.train") == pytest.approx(22e-3)
    # shared expert: 2 us forward, 1 us backward
    assert read("device_moe_shared_ms.train") == pytest.approx(3e-3)
    # routed: router 0.5 + sort 0.5 + combine 1 us scoped, 3 + 5 us of
    # ragged-dot kernels; the conditional's own 5 us is not summed
    assert read("device_moe_routed_ms.train") == pytest.approx(10e-3)
    fl, by = kernel_counts_mla.attention_kernels(cfg, 2)
    least = max(fl / 197e12, by / 819e9)
    assert read("mla_flash_roofline_pct.train") == pytest.approx(
        100 * least * 2 / 36e-6)            # kernels alone: 4 + 6 + 8 us
    # the accepted readers see the same program their own way
    assert read("device_attention_ms.train") == pytest.approx(18e-3)
    assert read("device_moe_ms.train") == pytest.approx(13e-3)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(small, monkeypatch,
                                                    name):
    """The parent's program: no scopes; and a program that never ran the
    model: scopes of another."""
    trace, run, _cfg_ = small
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    bare = dict(trace, kernels=[], op_s={
        k: v for k, v in trace["op_s"].items() if "ragged" not in k})
    assert harness._load_reader(name).read(bare, run) is None
    monkeypatch.setattr(
        program_spans, "op_scopes", lambda block="whole_step": {
            k: "jit(whole_step)/jvp(forward)/Conv2D_0/conv" for k in bare[
                "op_s"]})
    assert harness._load_reader(name).read(bare, run) is None


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
    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_kanana2", seed)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True, checks
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert checks["retraces_in_window"]["value"] == 0
    assert {"setup_s", "train_samples_s"} <= set(result["metrics"])


def test_a_traced_toy_run_reads_the_cells_scopes_and_gauge(capsys,
                                                           monkeypatch):
    """An unlisted workload reports every reader that finds something:
    the four of this cell among them, beside the accepted ones."""
    from mxnet_tpu.telemetry import instruments as ti

    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_kanana2", 3,
                             trace=1)
    result, notes = lines[-1], lines[-2]["notes"]
    assert rc == 0 and result["correct"] is True, checks
    metrics = result["metrics"]
    # device time by scope is the chip's to give: here the names alone
    # (and the shared expert's few CPU thunks need not carry their scope)
    assert {"device_mla_ms.train",
            "device_moe_routed_ms.train", "device_moe_ms.train",
            "moe_load_max_over_mean.train",
            "moe_buffer_rows_over_routed.train"} <= set(metrics)
    assert "mla_flash_roofline_pct.train" not in metrics    # no kernel here
    assert metrics["host_scalar_operands.train"]["value"] == 4
    # the sparse layers alone count rows, two of this preset's three
    assert sorted(notes["moe_load"]) == [
        "model.layers.1.mlp", "model.layers.2.mlp"]
    assert len(notes["reference_held_rows"][0]) == 2
    # the seeded bias is live: it moved some of the last step's choices
    moved = dict(ti.moe_bias_moved_share.series())
    assert set(moved) == {("model.layers.1.mlp",), ("model.layers.2.mlp",)}
    assert all(0.0 < g.value < 1.0 for g in moved.values())


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_kanana2.json")
    cfg = _cfg(wl["config"])
    nums = control_large.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap", "grad_norm_gap.weights_median",
                        "dw_norm_gap", "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "grad_norm_gap.weights_median" in over, nums
    assert "dw_norm_gap.weights_median" in over, nums


def test_the_cells_files_say_the_cut():
    cfg = _cfg("kanana2_30b_a3b_ep8")
    wl = harness._load_json("workloads", CELL + ".json")
    with open("/opt/skills/guides/model-configs/architectures.jsonl") \
            if os.path.exists("/opt/skills/guides/model-configs/"
                              "architectures.jsonl") \
            else open(os.devnull) as f:
        rows = [json.loads(l) for l in f if "kanana-2-30b-a3b" in l]
    for row in rows:            # every number of the catalog's config
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert cfg["n_routed_experts"] * cfg["ep_size"] \
        == cfg["router_width"] == 128
    assert cfg["vocab_size"] * 8 == 128256
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) == (
                2048, 32, 512, 128, 64, 128, 6144, 768, 6, 2)
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert "8 chips" in cfg["deployment"]
    assert (wl["driver"], wl["chips"], wl["traffic_params"]) == (
        "train_step_large", 1, {"batch": 2, "pool": 4})
    assert cfg["seq"] == 8192
    for words in ("batch 2 sequences of 8192 tokens", "16,384 a step",
                  "drawn uniformly from the slice's 16,032",
                  "pool of 4 seeded resident batches",
                  "loss fetched every 10th step", "768 rows a step",
                  "eight times its share", "five layers"):
        assert words in wl["why"], words


def test_the_parameters_add_up_to_the_issues_count():
    """575,955,968 trained parameters, 9.22 GB at 16 B (ISSUE 38)."""
    import importlib
    import math

    cfg = _cfg("kanana2_30b_a3b_ep8")
    ref = importlib.import_module("reference." + cfg["builder"])
    trained = sum(math.prod(shape) for name, shape, *_ in
                  ref.param_specs(cfg) if ref.trainable(name))
    bias = 4 * cfg["router_width"]       # counted by the issue, not trained
    assert trained + bias == 575_955_968
    assert 9.21e9 < 16 * trained < 9.22e9
