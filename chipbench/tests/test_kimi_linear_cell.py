"""The delta / latent hybrid cell's own pieces on the CPU: its counts
against counts by hand, its six readers on a small hand-made trace, and
``correct`` at a toy size (a sound run passes, the fp8 control does
not)."""
import json
import os

import pytest

import control_large
import flops
import kernel_counts
import kernel_counts_kda
import kernel_counts_mla
import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimi_linear_48b_a3b.train.causal.b2s8192"
READERS = ("device_kda_ms.train", "device_kda_scan_ms.train",
           "device_kda_prep_ms.train", "kda_scan_roofline_pct.train",
           "kda_scan_kernel_share.train",
           "nope_mla_flash_roofline_pct.train")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg(name):
    return harness._load_json("configs", name + ".json")


@pytest.fixture(autouse=True)
def _expert_counters_start_and_end_empty():
    """The expert layers' staged counters and gauges are the process's:
    what a toy run here leaves, another file's traced run would read."""
    from mxnet_tpu.telemetry import instruments as ti

    def clear():
        ti._staged_moe_load.clear()
        for g in (ti.moe_rows_routed_here, ti.moe_expert_load_max_over_mean,
                  ti.moe_buffer_rows, ti.moe_bias_moved_share,
                  ti.decoder_layers):
            g.clear()
    clear()
    yield
    clear()


# -- counts by hand ------------------------------------------------------------

def test_forward_flops_of_the_cell_by_hand():
    cfg = _cfg("kimi_linear_48b_a3b_ep32")
    s, d = 8192, 2304
    kda_proj = 2 * s * (3 * d * 4096 + 4096 * d            # q, k, v, o
                        + 2 * (d * 128 + 128 * 4096)       # decay's, gate's
                        + d * 32)                          # beta
    scan = 6 * 128 * 128 * 32 * s
    mla_proj = 2 * s * (d * 32 * 192 + d * 576 + 512 * 32 * 256
                        + 32 * 128 * d)
    pairs = 2 * (s * (s + 1) // 2) * (192 + 128) * 32
    dense = 2 * s * 3 * d * 9216
    shared = 2 * s * 3 * d * 1024
    rows = s * 8 * 8 / 256                              # 2,048 a sequence
    sparse = 2 * s * d * 256 + shared + rows * 2 * 3 * d * 1024
    head = 2 * (s - 1) * d * 20480
    want = 4 * (kda_proj + scan) + mla_proj + pairs + dense + 4 * sparse \
        + head
    assert kernel_counts_kda.layer_kinds(cfg) == (4, 1, 1, 4)
    assert kernel_counts_kda.expected_rows(cfg) == 2048
    assert kernel_counts_kda.forward(cfg) == want
    assert flops.forward_flops(cfg) == want
    # 12.6 TFLOP a step forward, 37.7 trained
    assert 12.5e12 < 2 * want < 12.65e12
    assert 37.6e12 < 2 * flops.train_flops(cfg) < 37.8e12
    # and its split, as the workload's file says it
    routed = 4 * rows * 2 * 3 * d * 1024
    shares = [round(100 * part / want, 1) for part in (
        4 * kda_proj, 4 * scan, mla_proj, pairs, dense, head, 4 * shared,
        routed, 4 * 2 * s * d * 256)]
    assert shares == [41.1, 1.6, 7.6, 10.9, 16.6, 12.3, 7.4, 1.8, 0.6]
    # the nine projection matrices: 316 MFLOP a token over four layers
    assert round(4 * kda_proj / s / 1e6) == 316


def test_the_kernels_counts_by_hand():
    cfg = _cfg("kimi_linear_48b_a3b_ep32")
    fl, by = kernel_counts_kda.scan_kernels(cfg, 2)
    tokens = 2 * 8192 * 32
    assert fl == 3 * (6 * 128 * 128 * 32 * 8192) * 2 * 4
    # q, k, v (2 B), g (4 B) a channel and beta (4 B): read by the forward,
    # by the backward's own forward and... no: read twice (forward,
    # backward), the gradients written once; o written, dO read
    operands = tokens * (128 * (2 + 2 + 2 + 4) + 4)
    assert by == (3 * operands + 2 * tokens * 128 * 2) * 4
    peaks = flops.peaks("TPU v5 lite")
    # the bytes bound it: 11.2 ms a step at the memory's peak, 3.1 at the
    # MXU's
    assert kernel_counts.roofline_seconds(fl, by, peaks) == by / 819e9
    assert 11.1e-3 < by / 819e9 < 11.3e-3
    assert 3.1e-3 < fl / 197e12 < 3.2e-3
    # the latent layer's flash kernels: kanana-2's count on one layer
    one = kernel_counts_kda.mla_cfg(cfg)
    assert one["num_hidden_layers"] == 1
    fl_a, by_a = kernel_counts_mla.attention_kernels(one, 2)
    assert fl_a == 3 * (2 * (8192 * 8193 // 2) * 320 * 32) * 2
    assert 20.9e-3 < fl_a / 197e12 < 21.0e-3
    kanana = _cfg("kanana2_30b_a3b_ep8")
    fl_k, by_k = kernel_counts_mla.attention_kernels(kanana, 2)
    assert (fl_k, by_k) == (5 * fl_a, 5 * by_a)         # the same signature
    assert kernel_counts_kda.applies(cfg)
    for other in ("lfm2_24b_a2b_ep8", "kanana2_30b_a3b_ep8",
                  "sdar_30b_a3b_ep8", "ouro_2_6b_l6", "resnet50_v1",
                  "trinity_mini_26b_a3b_ep16"):
        assert not kernel_counts_kda.applies(_cfg(other))


# -- the readers on a small trace ------------------------------------------------

LAYER = "jit(whole_step)/{}/KimiLinearModel_model/KimiLinearDecoderLayer_{}/"
KDA = LAYER + "KimiDeltaAttention_self_attn/kda/"
MLA = LAYER + "MultiHeadLatentAttention_self_attn/mla/"
FWD, BWD = "jvp(forward)", "transpose(jvp(forward))"
# (name, ns a step, scope): a delta layer (1) and the latent one (4)
OPS = (
    ("fusion.1", 4000, KDA.format(FWD, 1) + "kda.proj/Dense_q_proj/dot"),
    ("fusion.2", 600, KDA.format(FWD, 1) + "kda.conv/short_conv.taps/mul"),
    ("fusion.3", 400, KDA.format(FWD, 1) + "kda.gate/kda_decay/exp"),
    ("kda_scan_fwd.4", 5000, KDA.format(FWD, 1)
     + "kda.scan/kernel_fwd/pallas_call"),
    ("fusion.5", 300, KDA.format(FWD, 1) + "kda.scan/transpose"),
    ("fusion.6", 1500, KDA.format(FWD, 1) + "kda.out/Dense_o_proj/dot"),
    ("mla_heads_q_fwd.7", 700, MLA.format(FWD, 4)
     + "mla.heads/heads_fwd_call/pallas_call"),
    ("flash_attention_fwd.8", 8000, MLA.format(FWD, 4)
     + "attention/flash_fwd_call/pallas_call"),
    ("flash_attention_bwd.9", 16000, MLA.format(BWD, 4)
     + "attention/flash_bwd_call/pallas_call"),
    ("kda_scan_fwd.10", 5000, KDA.format(BWD, 1)
     + "checkpoint/kda.scan/kernel_fwd/pallas_call"),      # replayed
    ("kda_scan_bwd.11", 12000, KDA.format(BWD, 1)
     + "kda.scan/kernel_bwd/pallas_call"),
    ("fusion.12", 900, KDA.format(BWD, 1) + "kda.conv/short_conv.taps/mul"),
    ("fusion.13", 2000, KDA.format(BWD, 1) + "kda.out/Dense_o_proj/dot"),
    ("fusion.14", 6000, KDA.format(BWD, 1) + "kda.proj/Dense_q_proj/dot"),
)
KERNELS = [name for name, _ns, _scope in OPS
           if name.split(".")[0] in ("kda_scan_fwd", "kda_scan_bwd",
                                     "mla_heads_q_fwd", "flash_attention_fwd",
                                     "flash_attention_bwd")]


@pytest.fixture
def small(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    events, t = [], 0
    for _step in range(2):
        for name, ns, _scope in OPS:
            events.append((name, t, ns))
            t += ns
    monkeypatch.setattr(
        program_spans, "op_scopes",
        lambda block="whole_step": {n: s for n, _ns, s in OPS})
    ti.kda_scan_calls.clear()
    ti.kda_scan_calls.labels("kernel").set(4)
    trace = trace_reduce.reduce({"/device:TPU:0": events}, [],
                                kernels=KERNELS)
    cfg = _cfg("toy_kimi_linear")
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "batch": 2,
           "device_kind": "TPU v5 lite", "cfg": cfg}
    yield trace, run, cfg
    ti.kda_scan_calls.clear()


def test_readers_on_the_small_trace(small):
    from mxnet_tpu.telemetry import instruments as ti

    trace, run, cfg = small
    read = lambda name: harness._load_reader(name).read(trace, run)  # noqa: E731
    # everything under kda, a step
    assert read("device_kda_ms.train") == pytest.approx(37.7e-3)
    # the op alone: its kernels 5 + 5 + 12 and the XLA op beside them 0.3
    assert read("device_kda_scan_ms.train") == pytest.approx(22.3e-3)
    # conv 0.6 + 0.9, gate 0.4; the output projection's products are not
    # preparation
    assert read("device_kda_prep_ms.train") == pytest.approx(1.9e-3)
    # the kernels alone: 22 us a step against the floor
    fl, by = kernel_counts_kda.scan_kernels(cfg, 2)
    assert read("kda_scan_roofline_pct.train") == pytest.approx(
        100 * max(fl / 197e12, by / 819e9) * 2 / 44e-6)
    # the flash kernels alone, not the assembly's: 8 + 16 us a step
    fl, by = kernel_counts_mla.attention_kernels(
        kernel_counts_kda.mla_cfg(cfg), 2)
    assert read("nope_mla_flash_roofline_pct.train") == pytest.approx(
        100 * max(fl / 197e12, by / 819e9) * 2 / 48e-6)
    assert read("kda_scan_kernel_share.train") == 100.0
    ti.kda_scan_calls.labels("composition").set(12)
    assert read("kda_scan_kernel_share.train") == 25.0
    # the accepted readers see the same program their own way
    assert read("device_mla_ms.train") == pytest.approx(24.7e-3)
    assert read("mla_flash_roofline_pct.train") is not None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(small, monkeypatch,
                                                    name):
    """The parent's program: no scopes and no gauge; a program of another
    model: scopes of another kind, a configuration without delta
    layers."""
    from mxnet_tpu.telemetry import instruments as ti

    trace, run, _cfg_ = small
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    monkeypatch.delattr(ti, "kda_scan_calls")
    bare = dict(trace, kernels=[])
    assert harness._load_reader(name).read(bare, run) is None
    monkeypatch.undo()
    monkeypatch.setattr(
        program_spans, "op_scopes", lambda block="whole_step": {
            k: "jit(whole_step)/jvp(forward)/short_conv/short_conv.mix/mul"
            for k in trace["op_s"]})
    other = dict(run, cfg=_cfg("lfm2_24b_a2b_ep8"))
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
    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_kimi_linear",
                             seed)
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

    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_kimi_linear",
                             3, trace=1)
    result, notes = lines[-1], lines[-2]["notes"]
    assert rc == 0 and result["correct"] is True, checks
    metrics = result["metrics"]
    assert {"device_kda_ms.train", "device_kda_scan_ms.train",
            "kda_scan_kernel_share.train", "mla_heads_kernel_share.train",
            "device_moe_ms.train", "moe_load_max_over_mean.train"} \
        <= set(metrics)
    # rooflines are a TPU's, and off one the scan runs the composition
    assert not [m for m in metrics if "roofline" in m]
    assert metrics["kda_scan_kernel_share.train"]["value"] == 0.0
    assert metrics["host_scalar_operands.train"]["value"] == 4
    text = "\n".join(program_spans.op_scopes().values())
    for scope in ("/kda/kda.proj/", "/kda/kda.conv/", "/kda/kda.gate/",
                  "/kda/kda.scan/", "/kda/kda.out/", "/mla/mla.heads/",
                  "/mla/attention/"):
        assert scope in text, scope
    assert "/mla.rope/" not in text
    assert sorted(notes["moe_load"]) == [
        f"model.layers.{i}.mlp" for i in (1, 2, 3, 4)]
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("kda", "dense"): 1, ("kda", "moe"): 3, ("mla", "moe"): 1}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_kimi_linear.json")
    cfg = _cfg(wl["config"])
    nums = control_large.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap", "grad_norm_gap.weights_median",
                        "dw_norm_gap", "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "grad_norm_gap.weights_median" in over, nums


def test_the_reference_imports_nothing_of_the_program_and_walks_positions():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "kimi_linear.py")) as f:
        text = f.read()
    assert "mxnet_tpu" not in text and "import models" not in text
    # the delta rule is the recurrence: a scan over positions, no chunk
    # algebra (no triangular solve, no cumulative decay)
    assert "lax.scan(step" in text
    for word in ("solve", "cumsum", "tril", "chunk_"):
        assert word not in text, word


def test_the_cells_files_say_the_cut():
    cfg = _cfg("kimi_linear_48b_a3b_ep32")
    wl = harness._load_json("workloads", CELL + ".json")
    with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as f:
        rows = [json.loads(l) for l in f
                if '"Kimi-Linear-48B-A3B-Instruct"' in l]
    for row in rows:            # every key of the catalog's config
        assert cfg["source"].startswith(row["source_url"])
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert cfg["layers_held"] == [1, 5, 6, 7, 8]
    assert len(cfg["layers_held"]) == cfg["num_hidden_layers"] == 5
    assert cfg["num_experts"] * cfg["ep_size"] == cfg["router_width"] == 256
    assert cfg["vocab_size"] * 8 == 163840
    assert cfg["weights_seed"] == 5400000001
    assert "32 chips" in cfg["deployment"] \
        and "602,434,432" in cfg["deployment"] \
        and "9.64 GB" in cfg["deployment"]
    assert {"kda", "decay_init", "conv", "mla", "router", "router_bias",
            "auxiliary_loss", "optimizer", "weights", "seq", "remat",
            "loss"} <= set(cfg["assumed"])
    assert (wl["driver"], wl["chips"], wl["traffic_params"]) == (
        "train_step_large", 1,
        {"batch": 2, "pool": 5, "pool_seed": 5400000100})
    assert cfg["seq"] == 8192
    for words in ("batch 2 sequences of 8,192 tokens",
                  "drawn uniformly from the slice's 20,480 rows",
                  "ONE stated pool of 5 resident batches",
                  "loss fetched every 10th step", "12.6 TFLOP",
                  "41.1%", "1.6%", "7.6%", "10.9%", "16.6%", "12.3%", "7.4%",
                  "1.8%", "0.6%", "one in 27", "512 rows a step",
                  "9.64 GB", "PR 45", "PR 46"):
        assert words in wl["why"], words
    assert len(cfg["limits"]["train_step"]["reason"]) > 200
