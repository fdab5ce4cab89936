"""``device_mla_rope_ms.train`` and ``mla_heads_kernel_share.train`` on
the CPU: the first on the small kanana-2 trace with the scope as separate
XLA ops (the trace as recorded: the parent's program) and with the fused
op's kernels beside them, the second on the program's gauge, on a program
without it and on another configuration's run."""
import json
import os

import pytest

import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROPE, SHARE = "device_mla_rope_ms.train", "mla_heads_kernel_share.train"
KANANA2 = "kanana2_30b_a3b.train.causal.b2s8192"
BLOCK = ("jit(whole_step)/{}/DeepseekV3Model_model/DeepseekV3DecoderLayer_1/"
         "MultiHeadLatentAttention_self_attn/mla/")
FWD, BWD = "jvp(forward)", "transpose(jvp(forward))/checkpoint"
KERNELS = [
    ("mla_heads_q_fwd.40", BLOCK.format(FWD)
     + "mla.rope/jit(heads_fwd_call)/mla_heads_q_fwd/pallas_call", 300),
    ("mla_heads_kv_fwd.41", BLOCK.format(FWD)
     + "mla.rope/jit(heads_fwd_call)/mla_heads_kv_fwd/pallas_call", 400),
    ("mla_heads_q_bwd.42", BLOCK.format(BWD)
     + "mla.rope/jit(heads_bwd_call)/mla_heads_q_bwd/pallas_call", 350),
    ("mla_heads_kv_bwd.43", BLOCK.format(BWD)
     + "mla.rope/jit(heads_bwd_call)/mla_heads_kv_bwd/pallas_call", 450),
    ("fusion.44", BLOCK.format(FWD) + "mla.q/Dense_q_proj/dot_general", 5000),
    ("fusion.45", BLOCK.format(FWD) + "attention/transpose", 700),
]


def _small(monkeypatch, extra=()):
    with open(os.path.join(HERE, "trace_kanana2_small.json")) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    scopes = dict(d["op_scopes"])
    (plane, events), = devices.items()
    end = max(start + ns for _, start, ns in events)
    for _ in range(2):                      # after the trace's last op
        for name, scope, ns in extra:
            events.append((name, end, ns))
            scopes[name] = scope
            end += ns
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": scopes)
    trace = trace_reduce.reduce({plane: sorted(events, key=lambda e: e[1])},
                                [], kernels=d["kernels"])
    return trace, {"steps": 2, "traced_steps": 2, "platform": "tpu",
                   "batch": 2, "device_kind": "TPU v5 lite", "cfg": d["cfg"]}


def _read(name, trace, run):
    return harness._load_reader(name).read(trace, run)


@pytest.mark.parametrize("extra,rope_ms,mla_ms", [
    ((), 1.5e-3, 22e-3), (KERNELS, 3e-3, 29.2e-3)],
    ids=["composition", "kernels"])
def test_the_scope_is_read_whatever_runs_it(monkeypatch, extra, rope_ms,
                                            mla_ms):
    """One concatenate fusion a step as recorded; with the four kernels'
    events a step beside it the scope holds them too.  The projections and
    the flash kernels' scope stay out of it and inside ``mla``."""
    trace, run = _small(monkeypatch, extra)
    assert _read(ROPE, trace, run) == pytest.approx(rope_ms)
    assert _read("device_mla_ms.train", trace, run) == pytest.approx(mla_ms)


@pytest.mark.parametrize("trace_file", ["trace_sdar_small.json",
                                        "trace_lfm2_small.json",
                                        "trace_ouro_small.json"])
def test_a_program_without_the_scope_reads_none(monkeypatch, trace_file):
    with open(os.path.join(HERE, trace_file)) as f:
        d = json.load(f)
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": dict(d["op_scopes"]))
    trace = trace_reduce.reduce(
        {k: [tuple(e) for e in v] for k, v in d["devices"].items()}, [],
        kernels=d["kernels"])
    run = {"steps": 2, "traced_steps": 2, "platform": "tpu", "cfg": d["cfg"]}
    assert _read(ROPE, trace, run) is None


def test_without_traced_steps_or_scopes_there_is_nothing_to_read(
        monkeypatch):
    trace, run = _small(monkeypatch)
    assert _read(ROPE, trace, dict(run, traced_steps=0)) is None
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    assert _read(ROPE, trace, run) is None


def test_the_share_reads_the_programs_gauge(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    _, run = _small(monkeypatch)
    monkeypatch.setattr(ti, "_mla_heads_sites", [0, 0])
    for kernels in (True, True, True, False):
        ti.record_mla_heads_site(kernels)
    assert _read(SHARE, {}, run) == pytest.approx(75.0)
    ti.mla_heads_kernel_share.clear()
    assert _read(SHARE, {}, run) == 0.0     # every site on the composition


def test_a_program_without_the_gauge_reads_none(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    _, run = _small(monkeypatch)
    monkeypatch.delattr(ti, "mla_heads_kernel_share")
    assert _read(SHARE, {}, run) is None


@pytest.mark.parametrize("config", ["sdar_30b_a3b_ep8", "ouro_2_6b_l6",
                                    "lfm2_24b_a2b_ep8", "resnet50_v1"])
def test_another_configurations_run_reads_no_share(config):
    cfg = harness._load_json("configs", config + ".json")
    assert _read(SHARE, {}, {"cfg": cfg}) is None


def test_the_benchmark_lists_both_for_the_latent_attention_cell_alone():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries[ROPE] == {
        "name": ROPE, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "train_samples_s", "workloads": [KANANA2]}
    assert entries[SHARE] == {
        "name": SHARE, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_samples_s", "workloads": [KANANA2]}
