"""``attention_outside_kernels_ms.train`` on the CPU: the reader on a small
hand-made trace (the scope's time less its kernels'), on a program without
scopes, and through ``run.py`` on a traced toy run of every decoder
configuration (a number) and of ResNet (nothing to read)."""
import json

import pytest

import program_spans
import run as harness
import trace_reduce

NAME = "attention_outside_kernels_ms.train"
ATT = ("jit(whole_step)/{}/Model_model/Layer_0/SelfAttention_self_attn/"
       "attention/")
FWD, BWD = "jvp(forward)", "transpose(jvp(forward))"
# (instruction, ns a step, scope)
OPS = (
    ("flash_attention_fwd.1", 3000, ATT.format(FWD) + "flash_fwd_call"),
    ("slice.7", 400, ATT.format(FWD) + "squeeze"),
    ("flash_attention_bwd.1", 7000, ATT.format(BWD) + "flash_bwd_call"),
    ("fusion.3", 900, ATT.format(BWD) + "reduce_sum"),
    ("copy.5", 700, ATT.format(BWD) + "reshape"),
    ("fusion.9", 5000, "jit(whole_step)/jvp(forward)/Model_model/Layer_0/"
     "MLP_mlp/dot_general"),
)


@pytest.fixture
def small(monkeypatch):
    events, t = [], 0
    for _step in range(2):
        for name, ns, _scope in OPS:
            events.append((name, t, ns))
            t += ns
    monkeypatch.setattr(
        program_spans, "op_scopes",
        lambda block="whole_step": {n: s for n, _ns, s in OPS})
    trace = trace_reduce.reduce(
        {"/device:TPU:0": events}, [],
        kernels=[n for n, _ns, _s in OPS if n.startswith("flash")])
    return trace, {"steps": 2, "traced_steps": 2, "platform": "tpu"}


def _read(trace, run):
    return harness._load_reader(NAME).read(trace, run)


def test_the_scope_less_its_kernels(small):
    trace, run = small
    # 0.4 + 0.9 + 0.7 us a step around 3 + 7 us of kernels
    assert _read(trace, run) == pytest.approx(2.0e-3)
    assert harness._load_reader("device_attention_ms.train").read(
        trace, run) == pytest.approx(12.0e-3)
    # a program whose kernels carry the whole scope reads 0, not None
    only = dict(trace, op_s={k: v for k, v in trace["op_s"].items()
                             if k.startswith("flash") or k == "fusion.9"})
    assert _read(only, run) == pytest.approx(0.0)


def test_nothing_to_read(small, monkeypatch):
    trace, run = small
    assert _read(trace, dict(run, traced_steps=0)) is None
    monkeypatch.setattr(program_spans, "op_scopes",
                        lambda block="whole_step": None)
    assert _read(trace, run) is None
    # a program without attention: scopes, none of them the layer's
    monkeypatch.setattr(
        program_spans, "op_scopes", lambda block="whole_step": {
            "fusion.9": OPS[-1][2]})
    assert _read(trace, run) is None


def test_the_benchmark_lists_it_for_the_decoder_cells():
    bench = harness._load_json("..", "BENCHMARK.json")
    entry = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entry) == 1 and bench["per_layer"][-1] is entry[0]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry[0]["workloads"]) == cells - {"resnet50.train.b256"}
    assert entry[0]["moves"] == "train_samples_s"


@pytest.mark.parametrize("workload,reads", [
    ("toy_train_sdar", True), ("toy_train_kanana2", True),
    ("toy_train_ouro", True), ("toy_train_lfm2_moe", True),
    ("toy_train_trinity", True), ("toy_train", False)])
def test_a_traced_toy_run(capsys, monkeypatch, workload, reads):
    """Through ``run.py``: an unlisted workload reports every reader that
    finds something — a number wherever the step holds an ``attention``
    scope (off a TPU the flash call is its jnp twin and a CPU trace names
    few operations as the program does, so the number itself says nothing
    here), nothing on ResNet."""
    from mxnet_tpu.diagnostics import introspect

    monkeypatch.setenv("PYTHONHASHSEED", "0")     # no re-exec inside a test
    introspect.reset()      # the steps another test of the process compiled
    rc = harness.main(["--workload", workload, "--seed", str(2 ** 31 + 7),
                       "--seconds", "1.0", "--trace", "1"])
    result = [json.loads(l) for l in capsys.readouterr().out.splitlines()
              if l.startswith("{")][-1]
    assert rc == 0 and result["correct"] is True
    assert (NAME in result["metrics"]) == reads
    if reads:
        assert result["metrics"][NAME]["value"] >= 0.0
        assert result["metrics"][NAME]["unit"] == "ms"
