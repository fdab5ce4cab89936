"""Test fixtures (reference: conftest.py:61-127 — seeded repro + waitall).

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs multichip).
"""
import os

# Must be set before jax import: 8 virtual CPU devices, CPU platform.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests are hermetic on the CPU mesh whatever the caller's environment
# said: pin the config too, before any backend initialises.  (This pin is
# also what lets `mx.tpu(i)` in tests resolve to CPU devices — device.py.)
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Postmortem bundles (observability) default to CWD; in tests that would
# litter the repo root with mxtpu_blackbox.rank*.json every time a
# watchdog/crash path fires. Point them at a throwaway dir instead
# (tests that assert on bundle contents override this per-test).
import tempfile  # noqa: E402

os.environ.setdefault(
    "MXTPU_FLIGHTREC_DIR", tempfile.mkdtemp(prefix="mxtpu-test-blackbox-"))


# Quick-smoke subset (reference: pytest.ini marker families). The modules
# below together run in well under 3 minutes on the 1-core CPU box:
#   python -m pytest tests/ -m smoke -q
_SMOKE_MODULES = {
    "test_ndarray", "test_autograd", "test_native", "test_exc_handling",
    "test_np_dispatch", "test_image_record", "test_image_det_iter",
    "test_sparse_optimizer", "test_symbol", "test_symbol_register",
    "test_io_estimator", "test_custom_op", "test_resource",
    "test_op_aliases", "test_control_flow",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "smoke: fast subset (<3 min) for iteration — "
                   "see conftest._SMOKE_MODULES")
    config.addinivalue_line(
        "markers", "slow: heavyweight tests (large-tensor sweeps)")


def pytest_collection_modifyitems(config, items):  # noqa: ARG001
    for item in items:
        if item.module.__name__.rsplit(".", 1)[-1] in _SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)


@pytest.fixture(autouse=True)
def seed_rng():
    """Seed all framework RNGs per test (reference: module_scope_seed)."""
    import mxnet_tpu as mx

    mx.seed(0)
    yield


@pytest.fixture(autouse=True, scope="module")
def telemetry_switch_restored():
    """Hand the next module the telemetry switch as this one found it.
    Several modules end their tests with `telemetry.disable()` (the
    registry ships enabled), which turned every later counter assertion
    in the same xdist worker into `0 == n` — which modules share a worker
    changes whenever a test file is added."""
    from mxnet_tpu import telemetry

    was = telemetry.enabled()
    yield
    if was:
        telemetry.enable()
    else:
        telemetry.disable()


@pytest.fixture(autouse=True, scope="module")
def waitall_between_modules():
    """Sync between test modules so async failures attribute correctly
    (reference conftest autouse waitall)."""
    yield
    import mxnet_tpu as mx

    mx.waitall()
