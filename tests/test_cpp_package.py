"""cpp-package: compile + run the C++ frontend demo against libmxtpu.so
(reference coverage model: cpp-package CI example builds)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _build_and_run_embedded(tmp_path, src_name, ok_string,
                            build_timeout=180, run_timeout=300,
                            argv=()):
    """Compile a cpp-package example that embeds CPython and assert its
    OK marker — the one build recipe all embedded demos share."""
    import shutil
    import sysconfig

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    repo = REPO
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION")
    if not libdir or not ver or not os.path.exists(
            os.path.join(libdir, f"libpython{ver}.so")):
        pytest.skip("no shared libpython to embed")
    exe = str(tmp_path / src_name.replace(".cc", ""))
    build = subprocess.run(
        ["g++", "-O2", "-std=c++17",
         f"{repo}/cpp-package/example/{src_name}",
         f"-I{repo}/cpp-package/include", f"-I{inc}",
         f"-L{libdir}", f"-lpython{ver}", "-ldl", "-lm", "-o", exe],
        capture_output=True, text=True, timeout=build_timeout)
    assert build.returncode == 0, build.stderr
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    run = subprocess.run([exe, *argv], capture_output=True, text=True,
                         timeout=run_timeout, env=env)
    assert run.returncode == 0, run.stdout + run.stderr
    assert ok_string in run.stdout
    return run


@pytest.fixture(scope="module")
def libmxtpu():
    so = os.path.join(REPO, "native", "build", "libmxtpu.so")
    if not os.path.exists(so):
        subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                       check=True, capture_output=True)
    return so


def test_cpp_frontend_demo(libmxtpu, tmp_path):
    exe = str(tmp_path / "runtime_demo")
    build = subprocess.run(
        ["g++", "-O2", "-std=c++17",
         "-I" + os.path.join(REPO, "cpp-package", "include"),
         os.path.join(REPO, "cpp-package", "example", "runtime_demo.cc"),
         "-L" + os.path.dirname(libmxtpu), "-lmxtpu",
         "-Wl,-rpath," + os.path.dirname(libmxtpu),
         "-o", exe, "-pthread"],
        capture_output=True, text=True)
    assert build.returncode == 0, build.stderr
    run = subprocess.run([exe], capture_output=True, text=True, timeout=120)
    assert run.returncode == 0, run.stderr + run.stdout
    assert "all checks passed" in run.stdout


def test_packed_function_ffi_python_side():
    """capi.packed_invoke: one generic entry point reaching every
    registered op (reference: MXNET_REGISTER_API packed-function FFI)."""
    import json

    import numpy as onp

    from mxnet_tpu import capi

    ops = json.loads(capi.list_ops())
    assert "fully_connected" in ops and "relu" in ops
    x = onp.array([[1.0, -2.0]], "float32")
    blob, meta = capi.packed_invoke(
        "relu", x.tobytes(),
        json.dumps({"args": [{"shape": [1, 2], "dtype": "float32"}]}))
    out_meta = json.loads(meta)
    assert out_meta["outputs"][0]["shape"] == [1, 2]
    out = onp.frombuffer(blob, "float32").reshape(1, 2)
    onp.testing.assert_allclose(out, [[1.0, 0.0]])
    # attrs pass through (tuple conversion for lists)
    blob, meta = capi.packed_invoke(
        "pooling",
        onp.ones((1, 1, 4, 4), "float32").tobytes(),
        json.dumps({"args": [{"shape": [1, 1, 4, 4], "dtype": "float32"}],
                    "attrs": {"kernel": [2, 2], "pool_type": "avg"}}))
    assert json.loads(meta)["outputs"][0]["shape"] == [1, 1, 2, 2]


def test_packed_function_ffi_cpp_embed(tmp_path):
    """Build + run the embedded-interpreter C++ demo (reference analog:
    cpp-package C++ frontend over the op registry)."""
    import os
    import shutil
    import subprocess
    import sysconfig

    import pytest

    _build_and_run_embedded(tmp_path, "embed_demo.cc", "embed_demo OK",
                            run_timeout=180)


def test_generated_op_header_covers_registry():
    """op.h is generated from the registry (OpWrapperGenerator analog) —
    every op name must appear as a wrapper in the checked-in header."""
    import re

    from mxnet_tpu.ops import registry
    from mxnet_tpu.symbol import register as symreg

    symreg._generate()
    repo = __file__.rsplit("/tests/", 1)[0]
    src = open(f"{repo}/cpp-package/include/mxtpu/op.h").read()
    wrapped = set(re.findall(r'rt\.invoke\("([^"]+)"', src))
    missing = set(registry.list_ops()) - wrapped
    assert not missing, f"regenerate op.h: {sorted(missing)[:8]}"


def test_lenet_via_generated_wrappers(tmp_path):
    """Compile + run LeNet built purely from generated op.h wrappers
    (reference: cpp-package examples over mxnet-cpp/op.h)."""
    import os
    import shutil
    import subprocess
    import sysconfig

    import pytest

    _build_and_run_embedded(tmp_path, "lenet_generated_demo.cc",
                            "all checks passed", build_timeout=300)


def test_model_packed_python_side(tmp_path):
    """model_packed: the cpp-package training surface, driven from python
    (the C++ demo exercises the same entry point through the embedded
    interpreter)."""
    import json

    import numpy as onp

    from mxnet_tpu import capi

    _, meta = capi.model_packed(
        "", "create", b"",
        json.dumps({"args": [], "attrs": {"spec": {"mlp": [16],
                                                   "classes": 3}}}))
    h = json.loads(meta)["handle"]
    rs = onp.random.RandomState(0)
    x = rs.rand(24, 5).astype("f")
    y = (rs.rand(24) * 3).astype("i")
    blob = x.tobytes() + y.tobytes()
    args = [{"shape": [24, 5], "dtype": "float32"},
            {"shape": [24], "dtype": "int32"}]
    _, fit_meta = capi.model_packed(
        h, "fit", blob, json.dumps({"args": args,
                                    "attrs": {"lr": 0.1, "epochs": 5}}))
    losses = json.loads(fit_meta)["losses"]
    assert len(losses) == 5 and losses[-1] < losses[0]
    out_blob, out_meta = capi.model_packed(
        h, "predict", x.tobytes(),
        json.dumps({"args": [args[0]], "attrs": {}}))
    shape = json.loads(out_meta)["outputs"][0]["shape"]
    assert shape == [24, 3]
    path = str(tmp_path / "m.npz")
    capi.model_packed(h, "save", b"", json.dumps(
        {"args": [], "attrs": {"path": path}}))
    # new model, load, predictions match
    _, meta2 = capi.model_packed(
        "", "create", b"",
        json.dumps({"args": [], "attrs": {"spec": {"mlp": [16],
                                                   "classes": 3}}}))
    h2 = json.loads(meta2)["handle"]
    capi.model_packed(h2, "load", x.tobytes(), json.dumps(
        {"args": [args[0]], "attrs": {"path": path}}))
    out2, _ = capi.model_packed(
        h2, "predict", x.tobytes(),
        json.dumps({"args": [args[0]], "attrs": {}}))
    onp.testing.assert_allclose(
        onp.frombuffer(out_blob, "f"), onp.frombuffer(out2, "f"),
        rtol=1e-5)
    capi.model_packed(h, "free", b"", "{}")
    capi.model_packed(h2, "free", b"", "{}")


def test_cpp_training_demo(tmp_path):
    """Build + run the C++ training demo: full gluon training driven from
    C++ (reference analog: cpp-package FeedForward fit examples)."""
    _build_and_run_embedded(tmp_path, "train_demo.cc", "train_demo OK")


def test_cpp_lenet_training_demo(tmp_path):
    """Build + run the standalone C++ LeNet training example (reference
    analog: cpp-package/example/lenet.cpp) — conv net trained from C++
    to loss-decrease, holdout accuracy, save/load round-trip."""
    _build_and_run_embedded(tmp_path, "lenet_train_demo.cc",
                            "lenet_train_demo OK", run_timeout=600,
                            argv=[str(tmp_path / "ckpt.params")])
