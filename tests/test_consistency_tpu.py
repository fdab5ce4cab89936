"""TPU-vs-CPU numeric oracle (reference: test_utils.check_consistency —
the CPU<->GPU comparison harness run by tests/python/gpu/test_operator_gpu.py).

The check bodies live in tests/_consistency_checks.py and run in ONE
child process without the CPU pin: the conftest pins this pytest process
to the CPU, under which `tpu(0)` resolves to the host and the
"cross-backend" comparison would silently alias to CPU-vs-CPU.  This
process never initialises a TPU backend, so the child is the only
process that asks for the chip.  The child is started from a
module-scoped fixture — never while the module is imported — and where
it finds no TPU the fixture skips the suite."""
import json
import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))


def _clean_env():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    # drop the CPU-mesh flag too: the subprocess should look like the
    # driver's bench environment, not the test harness
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" in flags:
        env["XLA_FLAGS"] = " ".join(
            f for f in flags.split()
            if "host_platform_device_count" not in f)
    return env


@pytest.fixture(scope="module")
def results():
    """Every check, run once in one child process."""
    out = subprocess.run(
        [sys.executable, os.path.join(_HERE, "_consistency_checks.py")],
        capture_output=True, timeout=900, text=True, env=_clean_env())
    assert out.returncode == 0, (
        f"consistency subprocess died: {out.stderr[-2000:]}")
    r = json.loads(out.stdout.strip().splitlines()[-1])
    if r["platform"] != "tpu":
        pytest.skip(f"no TPU (child ran on {r['platform']!r}): "
                    "cross-backend oracle skipped")
    return r


class TestTpuCpuConsistency:
    def test_backends_genuinely_distinct(self, results):
        assert results["devices_distinct"], (
            "tpu(0) aliased to the host — oracle would be vacuous")

    def test_matmul(self, results):
        assert results["matmul"] == "ok", results

    def test_conv_bn_relu(self, results):
        assert results["conv_bn_relu"] == "ok", results

    def test_softmax_reduce(self, results):
        assert results["softmax_reduce"] == "ok", results

    def test_bf16_matmul_tolerance(self, results):
        assert results["bf16_matmul_tolerance"] == "ok", results
