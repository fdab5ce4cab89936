"""The arrows between the packages: what Gluon is built on does not import
Gluon, at module level or inside a function.  Read off the sources by
`ast`; no module is imported."""
import ast
import glob
import os

import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

# the packages under mxnet_tpu/ that gluon/ stands on
LOWER = ["ops", "parallel", "passes", "telemetry", "diagnostics", "ndarray",
         "optimizer"]

# The one stated exception: the MXNet alias shims `nd.image.*` and
# `nd.*_loss`-style names are thin calls into Gluon's transforms and
# losses, imported inside the two functions that serve them.
EXCEPTIONS = {os.path.join("mxnet_tpu", "ops", "aliases.py")}


def _imported(path):
    """Absolute dotted names a module imports, relative ones resolved
    against its place in the tree: (line, name) pairs."""
    rel = os.path.relpath(path, REPO)
    parts = rel[:-len(".py")].split(os.sep)
    package = parts[:-1]    # of a module, and of a package's __init__
    with open(path) as f:
        tree = ast.parse(f.read(), rel)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            base = ".".join(base + ([node.module] if node.module else []))
            for alias in node.names:
                yield node.lineno, f"{base}.{alias.name}"


def _is_gluon(name):
    return name == "mxnet_tpu.gluon" or name.startswith("mxnet_tpu.gluon.")


@pytest.mark.parametrize("package", LOWER)
def test_nothing_under_gluon_imports_gluon(package):
    files = glob.glob(os.path.join(REPO, "mxnet_tpu", package, "**", "*.py"),
                      recursive=True)
    assert files
    found = [f"{os.path.relpath(f, REPO)}:{line} imports {name}"
             for f in sorted(files)
             if os.path.relpath(f, REPO) not in EXCEPTIONS
             for line, name in _imported(f) if _is_gluon(name)]
    assert not found, "\n".join(found)


def test_the_stated_exception_is_still_one():
    """`ops/aliases.py` reaches up in two places; a third is a new debt."""
    (path,) = EXCEPTIONS
    lines = sorted({line for line, name in _imported(os.path.join(REPO, path))
                    if _is_gluon(name)})
    assert len(lines) == 2, lines
