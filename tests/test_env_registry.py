"""The env registry against the tree: every registered ``MXTPU_*`` name
is documented in docs/env_vars.md and is read by a file under
mxnet_tpu/, tools/, native/ or by chip_smoke.py, and a variable whose
subsystem was deleted changes nothing.
"""
import glob
import os
import re

import pytest

from mxnet_tpu import env

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

# Registered and documented, but read from os.environ past the registry
# (the two per-class objectives through one f-string).  A debt (ROADMAP
# D2), listed so that it cannot grow unseen: a new name belongs neither
# in this table nor in os.environ.  name -> (reading file, what it holds)
_OBS = os.path.join("mxnet_tpu", "observability")
_READ_PAST_REGISTRY = {
    "MXTPU_TRACE_SAMPLE": ("reqtrace.py", '_env_float("MXTPU_TRACE_SAMPLE"'),
    "MXTPU_TRACE_RING": ("reqtrace.py", 'environ.get("MXTPU_TRACE_RING")'),
    "MXTPU_SLO_TARGET": ("reqtrace.py", '_env_float("MXTPU_SLO_TARGET"'),
    "MXTPU_SLO_WINDOW_S": ("reqtrace.py", '_env_float("MXTPU_SLO_WINDOW_S"'),
    "MXTPU_SLO_BURN_MAX": ("reqtrace.py", '_env_float("MXTPU_SLO_BURN_MAX"'),
    "MXTPU_SLO_MIN_EVENTS": ("reqtrace.py",
                             '_env_float("MXTPU_SLO_MIN_EVENTS"'),
    "MXTPU_SLO_INTERACTIVE_MS": ("reqtrace.py",
                                 'f"MXTPU_SLO_{str(cls).upper()}_MS"'),
    "MXTPU_SLO_BATCH_MS": ("reqtrace.py",
                           'f"MXTPU_SLO_{str(cls).upper()}_MS"'),
    "MXTPU_FLIGHTREC": ("flight.py", 'environ.get("MXTPU_FLIGHTREC")'),
    "MXTPU_ELASTIC_GENERATION": (
        "flight.py", 'environ.get("MXTPU_ELASTIC_GENERATION")'),
    "MXTPU_FLIGHTREC_CRASHDUMP": (
        "__init__.py", 'environ.get("MXTPU_FLIGHTREC_CRASHDUMP"'),
}


@pytest.fixture(scope="module")
def sources():
    """{repo-relative path: text} of every file that may read a knob."""
    files = [f for f in glob.glob(os.path.join(REPO, "mxnet_tpu", "**",
                                               "*.py"), recursive=True)
             if os.path.relpath(f, REPO) != os.path.join("mxnet_tpu",
                                                         "env.py")]
    files += glob.glob(os.path.join(REPO, "tools", "*.py"))
    files += glob.glob(os.path.join(REPO, "native", "*.cc"))
    files.append(os.path.join(REPO, "chip_smoke.py"))
    out = {}
    for f in files:
        with open(f) as fh:
            out[os.path.relpath(f, REPO)] = fh.read()
    return out


@pytest.fixture(scope="module")
def documented():
    with open(os.path.join(REPO, "docs", "env_vars.md")) as f:
        return f.read()


@pytest.mark.parametrize(
    "name", sorted(n for n in env.all_vars() if n.startswith("MXTPU_")))
def test_registered_knob_is_documented_and_read(name, sources, documented):
    assert f"`{name}`" in env.doc()
    assert f"`{name}`" in documented, \
        "docs/env_vars.md is regenerated from the registry (env.doc())"
    if name in _READ_PAST_REGISTRY:
        reader, call = _READ_PAST_REGISTRY[name]
        assert call in sources[os.path.join(_OBS, reader)]
        return
    # env.get("NAME") under any alias of the module, one of the
    # `_env_get(name, default)` helpers that read the registry first,
    # or the native runtime's getenv("NAME")
    read = re.compile(r"\b(?:_?env(?:_get|\.get)|getenv)\(\s*[\"']%s[\"']"
                      % re.escape(name))
    readers = [f for f, text in sources.items() if read.search(text)]
    assert readers, f"{name} is registered and nothing reads it"


def test_docs_list_no_unregistered_knob(documented):
    listed = set(re.findall(r"^\* `([A-Z_0-9]+)`", documented, re.M))
    assert listed == set(env.all_vars())


# suffixes of the MXTPU_ variables whose subsystems were deleted (PR 31: the
# Pallas-kernel, layout-pass and BatchNorm-compute switches; PR 50: the
# jaxpr remat pass, the liveness model, dedup and the measurement plane),
# with a value that used to switch each on
_DELETED = {"KERNELS": "force", "KERNELS_INTERPRET": "1", "LAYOUT": "nhwc",
            "LAYOUT_MIN_BYTES": "0", "BN_COMPUTE": "bf16",
            "REMAT_POLICY": "full", "REMAT_BUDGET_MB": "1",
            "DIAG_MEMORY": "1", "GRAPH_DEDUP": "1", "MEASURE": "on_compile",
            "MEASURE_RUNS": "2", "MEASURE_WARMUP": "0",
            "COSTDB_PATH": "costdb.jsonl", "COSTDB_AUTOSAVE": "1",
            "COSTDB_DRIFT_MAX": "1.5"}


@pytest.mark.parametrize("suffix", sorted(_DELETED))
def test_deleted_variables_change_no_program(suffix, monkeypatch, tmp_path):
    """A deleted switch selected code that is gone: set, it raises nothing,
    leaves no file, and the training program of a BatchNorm block that the
    trace-to-compile seam builds is the one an empty environment gives."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import passes
    from mxnet_tpu.ops.nn import batch_norm

    x = jnp.ones((8, 6, 6, 16), jnp.bfloat16)
    c = jnp.ones((16,), jnp.float32)

    def loss(x, g, b):
        out, mean, var = batch_norm(x, g, b, c, c, training=True, axis=-1)
        return out.astype(jnp.float32).sum() + mean.sum() + var.sum()

    def program():
        ctx = passes.PassContext(label="deleted", kind="block",
                                 training=True)
        step = passes.apply(jax.grad(loss, argnums=(0, 1, 2)), ctx)
        return step.lower(x, c, c).as_text()

    plain = program()
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("MXTPU_" + suffix, _DELETED[suffix])
    assert program() == plain
    assert not list(tmp_path.iterdir())
    assert "MXTPU_" + suffix not in env.all_vars()
