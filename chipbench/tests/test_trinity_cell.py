"""The window / global cell's own pieces on the CPU: its counts against
counts by hand, its six readers on a small hand-made trace, and
``correct`` at a toy size (a sound run passes, the fp8 control does
not)."""
import json
import os

import pytest

import control_large
import flops
import kernel_counts
import kernel_counts_window
import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "trinity_mini.train.causal.b1s16384"
READERS = ("device_window_attention_ms.train",
           "window_flash_roofline_pct.train",
           "global_flash_roofline_pct.train",
           "window_pairs_visited_over_kept.train",
           "device_attention_gate_ms.train",
           "window_qk_prep_kernel_share.train")
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

def test_the_pairs_a_band_keeps_by_hand():
    """W (W + 1) / 2 + (S - W) W, against a count over the dense rule; a
    window over the sequence keeps the causal pairs."""
    for seq, window in ((48, 10), (64, 64), (33, 1), (40, 100)):
        dense = sum(1 for i in range(seq) for j in range(seq)
                    if 0 <= i - j < window)
        assert kernel_counts_window.band_pairs(seq, window) == dense
    assert kernel_counts_window.band_pairs(16384, 2048) == 31_458_304
    assert kernel_counts_window.band_pairs(16384, 16384) == 134_225_920
    assert kernel_counts_window.band_pairs(8192, 2048) == 14_681_088


def test_forward_flops_of_the_cell_by_hand():
    cfg = _cfg("trinity_mini_26b_a3b_ep16")
    s = 16384
    proj = 2 * s * 2048 * (4096 + 512 + 512 + 4096 + 4096)  # q k v gate o
    band = 2 * 31_458_304 * (128 + 128) * 32
    full = 2 * (s * (s + 1) // 2) * (128 + 128) * 32
    dense = 2 * s * 3 * 2048 * 6144
    shared = 2 * s * 3 * 2048 * 1024
    rows = s * 8 * 8 / 128                              # 8,192 a sequence
    sparse = 2 * s * 2048 * 128 + shared + rows * 2 * 3 * 2048 * 1024
    head = 2 * (s - 1) * 2048 * 25024
    want = 5 * proj + 4 * band + full + dense + 4 * sparse + head
    assert kernel_counts_window.layer_kinds(cfg) == (4, 1, 1, 4)
    assert kernel_counts_window.expected_rows(cfg) == 8192
    assert kernel_counts_window.forward(cfg) == want
    assert flops.forward_flops(cfg) == want
    # ISSUE 51: 12.9 TFLOP a step forward, 38.7 trained
    assert 12.85e12 < want < 12.95e12
    assert 38.6e12 < flops.train_flops(cfg) < 38.8e12
    # and its split: 34.6 / 17.0 / 16.0 / 13.0 / 9.6 / 6.4 / 3.2 per cent
    routed = 4 * rows * 2 * 3 * 2048 * 1024
    shares = [round(100 * part / want, 1) for part in (
        5 * proj, full, 4 * band, head, dense, 4 * shared, routed)]
    assert shares == [34.6, 17.0, 16.0, 13.0, 9.6, 6.4, 3.2]
    # the window at this length: 23% of the causal pairs, 44% at 8,192
    assert round(100 * 31_458_304 / 134_225_920) == 23
    assert round(100 * 14_681_088 / (8192 * 8193 // 2)) == 44


def test_the_kernels_counts_by_hand():
    cfg = _cfg("trinity_mini_26b_a3b_ep16")
    q, kv = 32 * 16384 * 128, 4 * 16384 * 128
    # q: read by 3, dQ and o written, dO read by 2; k, v: read by 3 each,
    # dK and dV written
    moved = 2 * ((3 + 1 + 1 + 2) * q + (3 + 3 + 1 + 1) * kv)
    fl, by = kernel_counts_window.attention_kernels(cfg, 1, sliding=True)
    assert fl == 3 * (2 * 31_458_304 * 256 * 32) * 4    # fwd + 2x bwd, 4 layers
    assert by == moved * 4
    fl_g, by_g = kernel_counts_window.attention_kernels(cfg, 1, sliding=False)
    assert fl_g == 3 * (2 * 134_225_920 * 256 * 32)
    assert by_g == moved
    peaks = flops.peaks("TPU v5 lite")
    # both bound by the MXU: 31.4 and 33.5 ms a step at the peak
    assert kernel_counts.roofline_seconds(fl, by, peaks) == fl / 197e12
    assert 31.3e-3 < fl / 197e12 < 31.5e-3
    assert 33.4e-3 < fl_g / 197e12 < 33.6e-3
    assert kernel_counts_window.applies(cfg)
    for other in ("lfm2_24b_a2b_ep8", "kanana2_30b_a3b_ep8",
                  "sdar_30b_a3b_ep8", "ouro_2_6b_l6", "resnet50_v1"):
        assert not kernel_counts_window.applies(_cfg(other))


# -- the readers on a small trace ------------------------------------------------

LAYER = "jit(whole_step)/{}/AfmoeModel_model/AfmoeDecoderLayer_{}/" \
    "GroupedQueryAttention_self_attn/"
FWD, BWD = "jvp(forward)", "transpose(jvp(forward))"
# (name, ns a step, scope): a sliding layer (1) and the global one (4)
OPS = (
    ("rms_norm_rotary_fwd.1", 500, LAYER.format(FWD, 1) + "rms_norm_rotary"),
    ("flash_attention_fwd.2", 3000, LAYER.format(FWD, 1)
     + "attention/attention.window/flash_fwd_call/pallas_call"),
    ("fusion.3", 1000, LAYER.format(FWD, 1)
     + "attention.gate/Dense_gate_proj/dot_general"),
    ("fusion.4", 500, LAYER.format(FWD, 1) + "attention.gate/mul"),
    ("fusion.5", 2000, LAYER.format(FWD, 1) + "Dense_o_proj/dot_general"),
    ("flash_attention_fwd.6", 8000, LAYER.format(FWD, 4)
     + "attention/attention.global/flash_fwd_call/pallas_call"),
    ("fusion.7", 500, LAYER.format(FWD, 4) + "attention.gate/mul"),
    ("flash_attention_bwd.8", 16000, LAYER.format(BWD, 4)
     + "attention/attention.global/flash_bwd_call/pallas_call"),
    ("fusion.9", 1500, LAYER.format(BWD, 4) + "attention.gate/mul"),
    ("flash_attention_bwd.10", 7000, LAYER.format(BWD, 1)
     + "attention/attention.window/flash_bwd_call/pallas_call"),
    ("fusion.11", 1000, LAYER.format(BWD, 1)
     + "attention/attention.window/reduce_sum"),        # delta, an XLA op
    ("fusion.12", 2500, LAYER.format(BWD, 1)
     + "attention.gate/Dense_gate_proj/dot_general"),
)
KERNELS = [name for name, _ns, _scope in OPS if "_attention_" in name
           or name.startswith("rms_norm")]


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
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()
    ti.set_attention_pairs("window", 9 * 64 * 64, 24640)
    ti.set_attention_pairs("causal", 10 * 64 * 64, 32896)
    ti.qk_prep_kernel_share.set(1.0)
    trace = trace_reduce.reduce({"/device:TPU:0": events}, [],
                                kernels=KERNELS)
    cfg = _cfg("toy_afmoe")
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "batch": 2,
           "device_kind": "TPU v5 lite", "cfg": cfg}
    yield trace, run, cfg
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept,
              ti.qk_prep_kernel_share):
        g.clear()


def test_readers_on_the_small_trace(small):
    trace, run, cfg = small
    read = lambda name: harness._load_reader(name).read(trace, run)  # noqa: E731
    # under attention.window, a step: the forward 3, the backward 7 and
    # the backward's XLA op 1 us
    assert read("device_window_attention_ms.train") == pytest.approx(11e-3)
    # the gate: projection 1 + product 0.5 + 0.5 forward, 1.5 + 2.5 back
    assert read("device_attention_gate_ms.train") == pytest.approx(6e-3)
    # the kernels alone: 3 + 7 us of the windows', 8 + 16 of the global
    fl, by = kernel_counts_window.attention_kernels(cfg, 2, sliding=True)
    assert read("window_flash_roofline_pct.train") == pytest.approx(
        100 * max(fl / 197e12, by / 819e9) * 2 / 20e-6)
    fl, by = kernel_counts_window.attention_kernels(cfg, 2, sliding=False)
    assert read("global_flash_roofline_pct.train") == pytest.approx(
        100 * max(fl / 197e12, by / 819e9) * 2 / 48e-6)
    assert read("window_pairs_visited_over_kept.train") == pytest.approx(
        9 * 64 * 64 / 24640)
    assert read("window_qk_prep_kernel_share.train") == 100.0
    # the accepted readers see the same program their own way: both kinds
    # of layer lie inside the ``attention`` scope
    assert read("device_attention_ms.train") == pytest.approx(35e-3)
    fl, by = kernel_counts_window.attention_kernels(cfg, 2, sliding=True)
    assert fl == 3 * 4 * (2 * kernel_counts_window.band_pairs(48, 16)
                          * 2 * 16 * 4) * 2


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_to_read_returns_none(small, monkeypatch,
                                                    name):
    """The parent's program: no scopes and no gauges; a program that never
    ran the model: scopes of another, no plan of a window."""
    from mxnet_tpu.telemetry import instruments as ti

    trace, run, _cfg_ = small
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    for gauge in ("qk_prep_kernel_share", "attention_pairs_visited",
                  "attention_pairs_kept"):
        monkeypatch.delattr(ti, gauge)
    bare = dict(trace, kernels=[])
    assert harness._load_reader(name).read(bare, run) is None
    monkeypatch.undo()
    monkeypatch.setattr(
        program_spans, "op_scopes", lambda block="whole_step": {
            k: "jit(whole_step)/jvp(forward)/attention/flash_fwd_call"
            for k in trace["op_s"]})
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()
    ti.set_attention_pairs("causal", 10, 5)
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
    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_trinity", seed)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True, checks
    assert result["attempted"] >= 10 and result["failed"] == 0
    assert checks["retraces_in_window"]["value"] == 0
    assert {"setup_s", "train_samples_s"} <= set(result["metrics"])


def test_a_traced_toy_run_reads_the_cells_scopes_and_gauges(capsys,
                                                            monkeypatch):
    """An unlisted workload reports every reader that finds something:
    this cell's among them, beside the accepted ones.  Off a TPU the flash
    call is its jnp twin, so no plan is built and the pair of gauges stays
    empty until one is (here by hand, at the toy's shapes)."""
    from mxnet_tpu.ops import pallas_attention as pa
    from mxnet_tpu.telemetry import instruments as ti

    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()
    rc, lines, checks = _run(capsys, monkeypatch, "toy_train_trinity", 3,
                             trace=1)
    result, notes = lines[-1], lines[-2]["notes"]
    assert rc == 0 and result["correct"] is True, checks
    metrics = result["metrics"]
    assert {"window_qk_prep_kernel_share.train", "device_moe_ms.train",
            "moe_load_max_over_mean.train"} <= set(metrics)
    # rooflines are a TPU's, and so is a plan
    assert not [m for m in metrics if "roofline" in m]
    assert "window_pairs_visited_over_kept.train" not in metrics
    assert metrics["window_qk_prep_kernel_share.train"]["value"] == 0.0
    assert metrics["host_scalar_operands.train"]["value"] == 4
    # every new scope is in the compiled step's map
    text = "\n".join(program_spans.op_scopes().values())
    for scope in ("/attention/attention.window/",
                  "/attention/attention.global/", "/attention.gate/"):
        assert scope in text, scope
    # the sparse layers alone count rows, four of this preset's five
    assert sorted(notes["moe_load"]) == [
        f"model.layers.{i}.mlp" for i in (1, 2, 3, 4)]
    assert {k: g.value for k, g in ti.decoder_layers.series()} == {
        ("window", "dense"): 1, ("window", "moe"): 3, ("global", "moe"): 1}
    # the gauge pair, once the toy's windowed signature is planned
    shape, kv = (2, 4, 48, 16), (2, 2, 48, 16)
    pa._plan(shape, kv, kv, "bfloat16", True, 48, 48, None, None, 16)
    read = harness._load_reader("window_pairs_visited_over_kept.train").read
    assert read({}, {}) == pytest.approx(
        48 * 48 / kernel_counts_window.band_pairs(48, 16))
    pa._plan.cache_clear()
    for g in (ti.attention_pairs_visited, ti.attention_pairs_kept):
        g.clear()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_fp8_control_fails_a_training_number(seed):
    wl = harness._load_json("workloads", "toy_train_trinity.json")
    cfg = _cfg(wl["config"])
    nums = control_large.control_numbers(wl, cfg, seed)
    limits = cfg["limits"]["train_step"]
    over = [n for n in ("grad_norm_gap", "grad_norm_gap.weights_median",
                        "dw_norm_gap", "dw_norm_gap.weights_median")
            if nums[n] > limits[n]]
    assert "grad_norm_gap.weights_median" in over, nums


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(os.path.dirname(HERE), "reference",
                           "afmoe.py")) as f:
        text = f.read()
    assert "mxnet_tpu" not in text and "import models" not in text


def test_the_cells_files_say_the_cut():
    cfg = _cfg("trinity_mini_26b_a3b_ep16")
    wl = harness._load_json("workloads", CELL + ".json")
    with open(CATALOG if os.path.exists(CATALOG) else os.devnull) as f:
        rows = [json.loads(l) for l in f if '"Trinity-Mini"' in l]
    for row in rows:            # every key of the catalog's config
        assert cfg["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in cfg["reduced"] and key != "layer_types":
                assert cfg[key] == value, key
        # the layers held, in the published order: layer 0, then 4 to 7
        published = row["config"]["layer_types"]
        assert cfg["layer_types"] == [published[0]] + published[4:8]
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "num_experts", "vocab_size"]
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
        "vocab_size": 200192}
    assert cfg["layer_types"] == ["sliding_attention"] * 4 \
        + ["full_attention"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert cfg["num_experts"] * cfg["ep_size"] == cfg["router_width"] == 128
    assert cfg["vocab_size"] * 8 == 200192
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["route_scale"],
            cfg["rms_norm_eps"], cfg["rope_theta"]) == (
                2048, 32, 4, 128, 2048, 6144, 1024, 8, 1, 2.826, 1e-5, 10000)
    assert cfg["weights_seed"] == 5100000001
    assert "16 chips" in cfg["deployment"] and "8-way" in cfg["deployment"]
    assert {"attention_gate", "norms", "positions", "router", "mup",
            "expert_bias", "auxiliary_loss", "optimizer", "weights",
            "seq"} <= set(cfg["assumed"])
    assert (wl["driver"], wl["chips"], wl["traffic_params"]) == (
        "train_step_large", 1,
        {"batch": 1, "pool": 5, "pool_seed": 5100000100})
    assert cfg["seq"] == 16384
    for words in ("batch 1 sequence of 16,384 tokens",
                  "drawn uniformly from the slice's 25,024 rows",
                  "ONE stated pool of 5 resident batches",
                  "loss fetched every 10th step", "12.9 TFLOP",
                  "34.6%", "17.0%", "16.0%", "13.0%", "9.6%", "6.4%", "3.2%",
                  "two in thirty-two", "sixteen times its share",
                  "1,024 rows a step", "8.07 GB", "PR 45", "PR 46"):
        assert words in wl["why"], words
    assert len(cfg["limits"]["train_step"]["reason"]) > 200
