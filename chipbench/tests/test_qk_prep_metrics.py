"""``device_qk_prep_ms.train`` and ``qk_prep_kernel_share.train`` on the
CPU: the first on the small SDAR trace with the scopes a program without
the fused op has (the parent's) and with the ones the op brings, the
second on the program's gauge and on a program without it."""
import json
import os

import pytest

import program_spans
import run as harness
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BLOCK = ("jit(whole_step)/{}/SDARModel_model/SDARDecoderLayer_0/"
         "GroupedQueryAttention_self_attn/")
FWD, BWD = "jvp(forward)", "transpose(jvp(forward))"

# what the block runs besides `attention` and its `Dense_*`: (instruction,
# scope, ns a step) as separate XLA ops, and as the op's two kernels
PROGRAMS = {
    "xla_ops": [
        ("fusion.20", BLOCK.format(FWD) + "mul", 700),           # q's norm
        ("fusion.21", BLOCK.format(FWD) + "concatenate", 900),   # rotation
        ("fusion.22", BLOCK.format(FWD) + "transpose", 400),
        ("fusion.23", BLOCK.format(BWD) + "reduce_sum", 1500),
        ("fusion.24", BLOCK.format(FWD) + "Dense_q_proj/dot_general", 5000),
    ],
    "kernels": [
        ("rms_norm_rotary_fwd.20", BLOCK.format(FWD)
         + "jit(qk_prep_fwd_call)/rms_norm_rotary_fwd/pallas_call", 300),
        ("rms_norm_rotary_bwd.21", BLOCK.format(BWD)
         + "jit(qk_prep_bwd_call)/rms_norm_rotary_bwd/pallas_call", 450),
        ("fusion.22", BLOCK.format(FWD) + "transpose", 400),     # v's
        ("fusion.24", BLOCK.format(FWD) + "Dense_q_proj/dot_general", 5000),
    ],
}


def _small(monkeypatch, extra):
    with open(os.path.join(HERE, "trace_sdar_small.json")) as f:
        d = json.load(f)
    events = [tuple(e) for e in d["devices"]["/device:TPU:0"]]
    scopes = dict(d["op_scopes"])
    for step_start in (24000, 54000):          # after each step's last op
        t = step_start
        for name, scope, ns in extra:
            events.append((name, t, ns))
            scopes[name] = scope
            t += ns
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": scopes)
    trace = trace_reduce.reduce({"/device:TPU:0": sorted(
        events, key=lambda e: e[1])}, [], kernels=d["kernels"])
    return trace, {"steps": 2, "traced_steps": 2, "platform": "tpu"}


def _read(name, trace, run):
    return harness._load_reader(name).read(trace, run)


@pytest.mark.parametrize("program,ms", [("xla_ops", 3.5e-3),
                                        ("kernels", 1.15e-3)])
def test_the_blocks_own_time_with_and_without_the_fused_op(monkeypatch,
                                                           program, ms):
    """Norms, rotation and transposes count, whatever runs them; the flash
    kernels' scope and the projections do not."""
    trace, run = _small(monkeypatch, PROGRAMS[program])
    assert _read("device_qk_prep_ms.train", trace, run) == pytest.approx(ms)
    # the scope beside it is not touched by what was added
    assert _read("device_attention_ms.train", trace, run) \
        == pytest.approx(11e-3)


def test_without_traced_steps_or_scopes_there_is_nothing_to_read(
        monkeypatch):
    trace, run = _small(monkeypatch, PROGRAMS["kernels"])
    assert _read("device_qk_prep_ms.train", trace,
                 dict(run, traced_steps=0)) is None
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    assert _read("device_qk_prep_ms.train", trace, run) is None


def test_the_share_reads_the_programs_gauge(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.setattr(ti, "_qk_prep_sites", [0, 0])
    for kernels in (True, True, True, False):
        ti.record_qk_prep_site(kernels)
    assert _read("qk_prep_kernel_share.train", {}, {}) == pytest.approx(75.0)
    ti.qk_prep_kernel_share.clear()


def test_a_program_without_the_gauge_reads_none(monkeypatch):
    from mxnet_tpu.telemetry import instruments as ti

    monkeypatch.delattr(ti, "qk_prep_kernel_share")
    assert _read("qk_prep_kernel_share.train", {}, {}) is None
