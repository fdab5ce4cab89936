"""mxnet_tpu.passes — the NNVM-style graph-pass pipeline.

Owns the seam between trace and compile: every jit the framework builds
for a captured program (CachedOp variants, export, symbol lowering, the
whole-step train program) flows through :func:`apply`, which runs the
resolved passes jaxpr → jaxpr before XLA sees the graph.  Shipped
passes: :class:`AmpPass` (auto mixed precision), and by name the
numerics checker and the sharding pass.  docs/passes.md covers the
architecture and how to write a custom pass.
"""
from .manager import (  # noqa: F401
    GraphPass,
    PassContext,
    PassManager,
    apply,
    apply_pipeline,
    block_context,
    pipeline_enabled,
    register_named_pass,
    resolve_passes,
    retrace_flat,
    run_passes,
    trace_closed,
    wrap_forward,
)
from .amp_pass import AmpPass  # noqa: F401
from . import _state  # noqa: F401

register_named_pass("amp", AmpPass)


def _numerics_factory():
    # lazy: observability imports jax-heavy bits; only pay when named
    from ..observability.numerics import NumericsPass

    return NumericsPass()


register_named_pass("numerics", _numerics_factory)


def _sharding_factory():
    # lazy (sharding imports parallel.mesh); a force-named pass carries
    # no plan of its own — it stamps whatever plan the context holds
    from ..sharding.shard_pass import ShardingPass

    return ShardingPass()


register_named_pass("sharding", _sharding_factory)

__all__ = [
    "AmpPass",
    "GraphPass",
    "PassContext",
    "PassManager",
    "apply",
    "apply_pipeline",
    "block_context",
    "pipeline_enabled",
    "register_named_pass",
    "resolve_passes",
    "retrace_flat",
    "run_passes",
    "trace_closed",
    "wrap_forward",
]
