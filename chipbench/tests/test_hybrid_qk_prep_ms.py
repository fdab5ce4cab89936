"""``device_hybrid_qk_prep_ms.train`` on the CPU: on the small hybrid
trace it reads what ``device_qk_prep_ms.train``'s predicate sums there,
whatever runs the preparation, and nothing on a configuration of another
kind, a program without scopes or a run without traced steps."""
import json
import os

import pytest

import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = "device_hybrid_qk_prep_ms.train"
BLOCK = ("jit(whole_step)/{}/LFM2MoEModel_model/LFM2DecoderLayer_1/"
         "GroupedQueryAttention_self_attn/")
FWD, BWD = "jvp(forward)", "transpose(jvp(forward))"
KERNELS = [
    ("rms_norm_rotary_fwd.40", BLOCK.format(FWD)
     + "jit(qk_prep_fwd_call)/rms_norm_rotary_fwd/pallas_call", 300),
    ("rms_norm_rotary_bwd.41", BLOCK.format(BWD)
     + "jit(qk_prep_bwd_call)/rms_norm_rotary_bwd/pallas_call", 450),
    ("fusion.42", BLOCK.format(FWD) + "Dense_q_proj/dot_general", 5000),
    ("fusion.43", BLOCK.format(FWD) + "attention/transpose", 700),
]


def _small(monkeypatch, extra=()):
    with open(os.path.join(HERE, "trace_lfm2_small.json")) as f:
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


@pytest.mark.parametrize("extra,ms", [((), 1.5e-3), (KERNELS, 2.25e-3)],
                         ids=["composition", "kernels"])
def test_it_reads_the_blocks_scope_as_the_accepted_reader_does(monkeypatch,
                                                               extra, ms):
    """The preparation as XLA ops (the trace as recorded) and with the
    fused kernels' two events a step beside them: the projections and the
    flash kernels' scope stay out either way."""
    trace, run = _small(monkeypatch, extra)
    assert _read(NAME, trace, run) == pytest.approx(ms)
    assert _read(NAME, trace, run) == _read("device_qk_prep_ms.train",
                                            trace, run)


@pytest.mark.parametrize("config", ["kanana2_30b_a3b_ep8",
                                    "sdar_30b_a3b_ep8", "ouro_2_6b_l6"])
def test_a_configuration_of_another_kind_reads_none(monkeypatch, config):
    trace, run = _small(monkeypatch)
    other = dict(run, cfg=harness._load_json("configs", config + ".json"))
    assert _read(NAME, trace, other) is None
    assert _read("device_qk_prep_ms.train", trace, other) is not None


def test_without_traced_steps_or_scopes_there_is_nothing_to_read(
        monkeypatch):
    trace, run = _small(monkeypatch)
    assert _read(NAME, trace, dict(run, traced_steps=0)) is None
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    assert _read(NAME, trace, run) is None


def test_the_benchmark_lists_it_for_the_hybrid_cell_alone():
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "train_samples_s",
        "workloads": ["lfm2_24b_a2b.train.causal.b2s8192"]}
    assert bench["per_layer"][-1] is entry
