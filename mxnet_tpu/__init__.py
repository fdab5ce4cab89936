"""mxnet_tpu — a TPU-native deep learning framework with MXNet's capabilities.

Ground-up JAX/XLA re-design of Apache MXNet (reference: Adnios/incubator-mxnet,
see SURVEY.md): imperative NDArray/NumPy frontends with an eager autograd tape,
Gluon Block/HybridBlock model authoring where hybridize() compiles traced
subgraphs with jax.jit (the CachedOp analog), `mx.tpu()` device contexts over
PJRT, optimizers as fused on-device update fns, and `kvstore='tpu_dist'`
data-parallel training over ICI via XLA collectives.

Usage mirrors the reference:

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, np, npx

    x = mx.np.ones((2, 3), device=mx.tpu(0))
    net = mx.gluon.nn.Dense(4)
    net.initialize()
    with autograd.record():
        y = net(x).sum()
    y.backward()
"""
from __future__ import annotations

import sys as _sys
import time as _time

# the span `startup.import` runs from here to the bottom of this file
_import_t0 = _time.perf_counter()
_jax_preloaded = "jax" in _sys.modules

__version__ = "0.1.0"

# 64-bit dtype contract (reference: mshadow DType dispatch supports real
# float64/int64 compute; shape_array returns int64 —
# src/operator/tensor/matrix_op.cc). Explicit 64-bit requests are honored;
# every creation default in this package stays float32/int32 like the
# reference's. fp64 is emulated (slow) on TPU — fine for CPU parity work,
# documented in docs/migration.md.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

from . import _jax_defaults as _jax_defaults_mod

_jax_defaults_mod.install()  # 32-bit defaults on dtype-less jax.random
_jax_defaults_mod.place_compile_cache()  # $JAX_COMPILATION_CACHE_DIR or .jax_cache

from .telemetry import instruments as _instruments

_instruments.install_compile_listener()  # what JAX compiles, counted

from . import autograd, base, device, engine
from . import env  # typed env-var registry (env_var.md analog)
from . import _random
from .base import MXNetError
from .device import (
    Context,
    Device,
    cpu,
    cpu_pinned,
    current_device,
    gpu,
    num_gpus,
    num_tpus,
    tpu,
)
from . import ndarray
from . import ndarray as nd
from . import numpy as np  # noqa: A004 - intentional: mx.np
from . import numpy_extension as npx
from .ndarray import NDArray

# random: stateful global seed + legacy mx.random namespace
from . import random  # noqa: E402  (module == mx.random attr)

# subpackages loaded lazily-ish but imported eagerly for API parity
from . import initializer  # noqa: E402
from . import optimizer  # noqa: E402
from . import lr_scheduler  # noqa: E402
from . import kvstore as kv  # noqa: E402
from . import kvstore  # noqa: E402
from . import io  # noqa: E402
from . import image  # noqa: E402
from . import attribute  # noqa: E402
from . import callback  # noqa: E402
from . import contrib  # noqa: E402
from . import library  # noqa: E402
from . import model  # noqa: E402
from . import monitor  # noqa: E402
from . import name  # noqa: E402
from . import onnx  # noqa: E402
from . import visualization  # noqa: E402
from .attribute import AttrScope  # noqa: E402
from .monitor import Monitor  # noqa: E402
from .name import NameManager  # noqa: E402
from .visualization import plot_network, print_summary  # noqa: E402
from . import operator  # noqa: E402
from .operator import Custom  # noqa: E402
from . import recordio  # noqa: E402
from . import resource  # noqa: E402
from . import rtc  # noqa: E402
from . import context  # noqa: E402
from . import dlpack  # noqa: E402
from . import error  # noqa: E402
from . import executor  # noqa: E402
from . import libinfo  # noqa: E402
from . import log  # noqa: E402
from . import registry  # noqa: E402
from . import gluon  # noqa: E402
from . import symbol  # noqa: E402
from . import symbol as sym  # noqa: E402
from . import storage  # noqa: E402
from . import contrib  # noqa: E402
from . import util  # noqa: E402
from . import runtime  # noqa: E402
from . import profiler  # noqa: E402
from . import telemetry  # noqa: E402  (runtime metrics; docs/telemetry.md)
from . import passes  # noqa: E402  (graph-pass pipeline; docs/passes.md)
from . import diagnostics  # noqa: E402  (spans/compile introspection/watchdog)
from . import test_utils  # noqa: E402  (mx.test_utils like the reference)
from . import amp  # noqa: E402  (mx.amp — reference: python/mxnet/amp/)
from . import serving  # noqa: E402  (batching inference engine; docs/serving.md)
from . import decode  # noqa: E402  (KV-cache autoregressive decode; docs/decode.md)
from . import checkpoint  # noqa: E402  (atomic snapshots; docs/checkpointing.md)
from . import sharding  # noqa: E402  (hybrid parallelism; docs/sharding.md)
from . import elastic  # noqa: E402  (topology-change survival; docs/elasticity.md)
from . import observability  # noqa: E402  (flight recorder + numerics + postmortems)

waitall = engine.waitall


def seed(s, ctx="all"):
    """Seed all framework RNGs (reference: mx.random.seed)."""
    _random.seed(s, ctx)


# Internal reference spellings (_npi_*, _contrib_*, _plus_scalar, ...)
# resolve onto the same registry entries as the public names.
from .ops.aliases import install_aliases as _install_aliases  # noqa: E402

_install_aliases()

diagnostics.spans.record(
    "startup.import", "startup", _import_t0,
    _time.perf_counter() - _import_t0, jax_preloaded=_jax_preloaded)

__all__ = [
    "NDArray",
    "MXNetError",
    "Context",
    "Device",
    "cpu",
    "cpu_pinned",
    "gpu",
    "tpu",
    "num_gpus",
    "num_tpus",
    "current_device",
    "autograd",
    "nd",
    "np",
    "npx",
    "ndarray",
    "gluon",
    "initializer",
    "optimizer",
    "lr_scheduler",
    "kvstore",
    "kv",
    "random",
    "seed",
    "waitall",
    "engine",
    "symbol",
    "sym",
    "storage",
    "contrib",
    "device",
    "base",
    "util",
    "runtime",
    "profiler",
    "telemetry",
    "diagnostics",
    "observability",
]
