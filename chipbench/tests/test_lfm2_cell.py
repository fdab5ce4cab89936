"""The hybrid cell's own pieces on the CPU: its counts against counts by
hand, its six readers on a small hand-made trace, and ``correct`` at a toy
size (a sound run passes, the fp8 control does not)."""
import json
import os

import pytest

import control_large
import flops
import kernel_counts
import kernel_counts_hybrid
import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "lfm2_24b_a2b.train.causal.b2s8192"
READERS = ("device_short_conv_ms.train", "short_conv_mix_roofline_pct.train",
           "hybrid_flash_roofline_pct.train", "device_hybrid_moe_ms.train",
           "hybrid_moe_experts_roofline_pct.train",
           "hybrid_qk_prep_kernel_share.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg(name):
    return harness._load_json("configs", name + ".json")


# -- counts by hand ------------------------------------------------------------

def test_forward_flops_of_the_cell_by_hand():
    cfg = _cfg("lfm2_24b_a2b_ep8")
    s = 8192
    conv = 2 * s * (2048 * 6144 + 2048 * 2048)          # in, out
    proj = 2 * s * 2048 * (2048 + 512 + 512 + 2048)     # q, k, v, o
    pairs = 2 * (s * (s + 1) // 2) * (64 + 64) * 32
    dense = 2 * s * 3 * 2048 * 11776
    rows = s * 4 * 8 / 64                               # 4096 a sequence
    sparse = 2 * s * 2048 * 64 + rows * 2 * 3 * 2048 * 1536
    head = 2 * (s - 1) * 2048 * 8192
    want = 5 * conv + 2 * (proj + pairs) + dense + 6 * sparse + head
    assert kernel_counts_hybrid.layer_kinds(cfg) == (5, 2, 1, 6)
    assert kernel_counts_hybrid.expected_rows(cfg) == 4096
    assert kernel_counts_hybrid.forward(cfg) == want
    assert flops.forward_flops(cfg) == want
    # ISSUE 47: 513.3 MFLOP a token, 8.41 TFLOP a step of 16,384, 25.2 trained
    assert 513.2e6 < want / s < 513.4e6
    assert 8.40e12 < 2 * want < 8.42e12
    assert 25.1e12 < flops.train_flops(cfg) * 2 < 25.3e12
    # and its split: 33 / 8 / 13 / 28 / 11 / 7 per cent
    shares = [round(100 * part / want) for part in (
        5 * conv, 2 * proj, 2 * pairs, dense, 6 * sparse, head)]
    assert shares == [33, 8, 13, 28, 11, 7]


def test_the_kernels_counts_by_hand():
    cfg = _cfg("lfm2_24b_a2b_ep8")
    fl, by = kernel_counts_hybrid.attention_kernels(cfg, 2)
    pairs = 8192 * 8193 // 2
    assert fl == 3 * (2 * pairs * 128 * 32) * 2 * 2     # fwd + 2x bwd, 2 layers
    q, kv = 2 * 32 * 8192 * 64, 2 * 8 * 8192 * 64
    # q: read by 3, dQ and o written, dO read by 2; k, v: read by 3 each,
    # dK and dV written
    assert by == 2 * ((3 + 1 + 1 + 2) * q + (3 + 3 + 1 + 1) * kv) * 2
    peaks = flops.peaks("TPU v5 lite")
    assert kernel_counts.roofline_seconds(fl, by, peaks) == fl / 197e12
    # the grouped products on 6 x 8,192 rows (an even routing's step)
    rows = 6 * 8192
    fl, by = kernel_counts_hybrid.expert_kernels(cfg, rows)
    assert fl == 3 * rows * 3 * 2 * 2048 * 1536
    weights = 3 * 8 * 2048 * 1536 * 6
    assert by == 2 * (3 * weights + 3 * rows * (2 * 2048 + 3 * 1536))
    # the mix: (3 + 1) forward and (3 + 1 + 3) backward tensors of T x D
    assert kernel_counts_hybrid.mix_bytes(cfg, 2) == (
        2 * 16384 * 2048 * 11 * 5)
    assert 4.4e-3 < kernel_counts_hybrid.mix_bytes(cfg, 2) / 819e9 < 4.6e-3


# -- the readers on a small trace ------------------------------------------------

@pytest.fixture
def small(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    with open(os.path.join(HERE, "trace_lfm2_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": dict(d["op_scopes"]))
    # one attention layer's two sites, both on the composition
    ti.qk_prep_kernel_share.set(0.0)
    trace = trace_reduce.reduce(devices, [], kernels=d["kernels"])
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "batch": 2,
           "device_kind": "TPU v5 lite", "cfg": d["cfg"],
           "reference_held_rows": 200.0}
    return trace, run, d["cfg"]


def test_readers_on_the_small_trace(small):
    trace, run, cfg = small
    read = lambda name: harness._load_reader(name).read(trace, run)  # noqa: E731
    # a step, under short_conv: in 1 + mix 0.5 + out 1 us forward, the
    # replayed mix 0.5, the mix's backward 2 + 1 and in_proj's 2 us
    assert read("device_short_conv_ms.train") == pytest.approx(8e-3)
    # the mix alone: 0.5 + 0.5 + 2 + 1 us a step
    least = kernel_counts_hybrid.mix_bytes(cfg, 2) / 819e9
    assert read("short_conv_mix_roofline_pct.train") == pytest.approx(
        100 * least * 2 / 8e-6)
    fl, by = kernel_counts_hybrid.attention_kernels(cfg, 2)
    assert read("hybrid_flash_roofline_pct.train") == pytest.approx(
        100 * max(fl / 197e12, by / 819e9) * 2 / 24e-6)     # 4 + 8 us
    # router 0.5 + sort 0.5 + combine 1 us scoped, 3 + 5 us of ragged-dot
    # kernels; the conditional's own 4 us is not summed
    assert read("device_hybrid_moe_ms.train") == pytest.approx(10e-3)
    fl, by = kernel_counts_hybrid.expert_kernels(cfg, 200.0)
    assert read("hybrid_moe_experts_roofline_pct.train") == pytest.approx(
        100 * max(fl / 197e12, by / 819e9) * 2 / 16e-6)
    assert read("hybrid_qk_prep_kernel_share.train") == 0.0
    # the accepted readers see the same program their own way
    assert read("device_attention_ms.train") == pytest.approx(12e-3)
    assert read("device_moe_ms.train") == pytest.approx(10e-3)
    assert read("device_qk_prep_ms.train") == pytest.approx(1.5e-3)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(small, monkeypatch,
                                                    name):
    """The parent's program: no scopes and no gauge; a program that never
    ran the model: scopes of another."""
    from mxnet_tpu.telemetry import instruments as ti

    trace, run, _cfg_ = small
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    monkeypatch.delattr(ti, "qk_prep_kernel_share")
    bare = dict(trace, kernels=[], op_s={
        k: v for k, v in trace["op_s"].items() if "ragged" not in k})
    assert harness._load_reader(name).read(bare, run) is None
    monkeypatch.setattr(
        program_spans, "op_scopes", lambda block="whole_step": {
            k: "jit(whole_step)/jvp(forward)/Conv2D_0/conv" for k in bare[
                "op_s"]})
    assert harness._load_reader(name).read(bare, run) is None


@pytest.mark.parametrize("name", [n for n in READERS if "short_conv" not in n])
def test_a_reader_named_hybrid_reads_no_other_configuration(small, name):
    """What another cell's accepted reader already reads (the expert
    layers' time, the flash kernels' roofline at that cell's widths, the
    preparation's share) is this cell's only under a configuration that
    chooses its layers one by one; the two ``short_conv`` readers need no
    such gate, because no other program has the scope."""
    trace, run, _cfg_ = small
    other = dict(run, cfg=_cfg("kanana2_30b_a3b_ep8"))
    assert harness._load_reader(name).read(trace, other) is None


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
    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_lfm2_moe", seed)
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

    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_lfm2_moe", 3,
                             trace=1)
    result, notes = lines[-1], lines[-2]["notes"]
    assert rc == 0 and result["correct"] is True, checks
    metrics = result["metrics"]
    # device time by scope is the chip's to give: here the names alone
    # (and the convolution's few CPU thunks need not carry their scope)
    assert {"device_hybrid_moe_ms.train", "device_moe_ms.train",
            "hybrid_qk_prep_kernel_share.train",
            "moe_load_max_over_mean.train"} <= set(metrics)
    # rooflines are a TPU's
    assert not [m for m in metrics if "roofline" in m]
    assert metrics["hybrid_qk_prep_kernel_share.train"]["value"] == 0.0
    assert metrics["host_scalar_operands.train"]["value"] == 4
    # the sparse layers alone count rows, three of this preset's four
    assert sorted(notes["moe_load"]) == [
        f"model.layers.{i}.feed_forward" for i in (1, 2, 3)]
    assert len(notes["reference_held_rows"][0]) == 3
    # the gauges of the traced stack: three convolution layers, four kinds
    assert ti.short_conv_sites.value == 3
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("conv", "dense"): 1, ("attention", "moe"): 1, ("conv", "moe"): 2}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_lfm2_moe.json")
    cfg = _cfg(wl["config"])
    nums = control_large.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap", "grad_norm_gap.weights_median",
                        "dw_norm_gap", "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "grad_norm_gap.weights_median" in over, nums
    assert "dw_norm_gap.weights_median" in over, nums


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "lfm2_moe.py")) as f:
        text = f.read()
    assert "mxnet_tpu" not in text and "import models" not in text


def test_the_cells_files_say_the_cut():
    cfg = _cfg("lfm2_24b_a2b_ep8")
    wl = harness._load_json("workloads", CELL + ".json")
    with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as f:
        rows = [json.loads(l) for l in f if '"LFM2-24B-A2B"' in l]
    for row in rows:            # every key of the catalog's config
        assert cfg["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in cfg["reduced"] and key != "layer_types":
                assert cfg[key] == value, key
        # the layers held, in the published order: layer 0, then 2 to 7
        published = row["config"]["layer_types"]
        assert cfg["layer_types"] == [published[0]] + published[2:8]
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts", "vocab_size"]
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 40, "num_dense_layers": 2, "num_experts": 64,
        "vocab_size": 65536}
    assert cfg["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 7
    assert cfg["num_experts"] * cfg["ep_size"] == cfg["router_width"] == 64
    assert cfg["vocab_size"] * 8 == 65536
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["conv_L_cache"], cfg["norm_eps"]) == (
                2048, 32, 8, 11776, 1536, 4, 3, 1e-5)
    assert cfg["tie_word_embeddings"] is True
    assert cfg["weights_seed"] == 4700000001
    assert "8 chips" in cfg["deployment"]
    assert list(cfg["assumed"])[0] == "tie_word_embeddings"
    assert (wl["driver"], wl["chips"], wl["traffic_params"]) == (
        "train_step_large", 1,
        {"batch": 2, "pool": 5, "pool_seed": 4700000100})
    assert cfg["seq"] == 8192
    for words in ("batch 2 sequences of 8192 tokens", "16,384 a step",
                  "drawn uniformly from the slice's 8,192 rows",
                  "ONE stated pool of 5 resident batches",
                  "loss fetched every 10th step", "513.3 MFLOP a token",
                  "33%", "8%", "13%", "28%", "11%", "7%",
                  "two in forty would be 3%", "2 layers in 7 against 1 in 4",
                  "1,024 rows a step", "eight times their share",
                  "seven layers make the host's share larger",
                  "10.37 GB", "PR 45", "PR 46"):
        assert words in wl["why"], words


def test_the_parameters_add_up_to_the_issues_count():
    """647,819,520 trained parameters, 10.37 GB at 16 B (ISSUE 47)."""
    import importlib
    import math

    cfg = _cfg("lfm2_24b_a2b_ep8")
    ref = importlib.import_module("reference." + cfg["builder"])
    sizes = {name: math.prod(shape) for name, shape, *_ in
             ref.param_specs(cfg) if ref.trainable(name)}
    assert sum(sizes.values()) == 647_819_520
    assert 10.36e9 < 16 * sum(sizes.values()) < 10.37e9
    layer = lambda i: sum(v for k, v in sizes.items()  # noqa: E731
                          if k.startswith(f"model.layers.{i}."))
    assert [layer(i) for i in range(7)] == [
        89_139_200, 86_118_528, 92_416_000, 92_416_000, 92_416_000,
        86_118_528, 92_416_000]
    assert "lm_head.weight" not in sizes            # tied
