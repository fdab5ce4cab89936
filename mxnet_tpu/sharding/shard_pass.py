"""ShardingPass — stamps the plan's placement onto a captured program.

Placement in this stack is carried by the OPERANDS ("computation
follows data": params/grads/optimizer state live as NamedSharding
arrays after :meth:`ShardingPlan.apply`, batches are placed by
``shard_batch``/TrainStep's ``_whole``), and the whole-step mesh path
wraps its body in ``shard_map`` with the gradient psum already traced
in via ``collectives.psum_tree_flat_traced``.  What the captured jaxpr
itself lacks is the CONSTRAINT: nothing pins the program's inputs and
outputs to the plan, so a refactor that drops a device_put — or a
block seam that never sees TrainStep's placement code — silently
degrades to replicated transfers.

This pass closes that hole at the pass-pipeline seam.  At priority 30
it runs after layout (25) — specs describe logical dims, and this
program's params are already in their physical layout — and before the
numerics interposer.  For each seam kind it:

  * block / whole_step: records the plan on ``ctx.notes["sharding"]``
    (mesh shape, batch axis, rule count — what diagnose.py --passes
    and tests assert on) and, for block seams carrying batch-major
    inputs, stamps ``ctx.in_shardings``/``ctx.out_shardings`` so the
    ``jax.jit`` that compiles the rewritten program enforces the
    plan's placement instead of inheriting whatever the operands had;
  * the jaxpr itself is returned UNCHANGED — sharding is a placement
    property, not an equation rewrite, so the rewritten program stays
    structurally identical to the unsharded one (same retrace
    behavior).

The whole-step seam deliberately keeps ``in_shardings`` unset: its
argument list mixes host arrays (lrs/wds/ts) with pytrees, where
pjit's prefix-matching of shardings is version-fragile, and TrainStep
already places every operand explicitly in ``_whole``.  The stamp
there is the note + telemetry only, which is also what keeps
``mesh=None`` trivially bitwise: no plan, no pass, no note.
"""
from __future__ import annotations

from ..telemetry import instruments as _telemetry
from ..passes.manager import GraphPass

__all__ = ["ShardingPass"]


class ShardingPass(GraphPass):
    """Plan-placement stamp (see module docstring)."""

    name = "sharding"
    priority = 30
    kinds = ("block", "whole_step")

    def __init__(self, plan=None):
        # plan may be None when force-added via MXTPU_PASSES=sharding;
        # the context's plan (set by Trainer/TrainStep) wins when both
        # are present so one pass object serves multi-trainer processes
        self._plan = plan

    def applies(self, ctx):
        return super().applies(ctx) and \
            (ctx.plan is not None or self._plan is not None)

    def run(self, closed_jaxpr, ctx):
        plan = ctx.plan if ctx.plan is not None else self._plan
        mesh = plan.mesh
        ctx.notes["sharding"] = {
            "mesh": dict(mesh.shape),
            "batch_axis": plan.batch_axis,
            "rules": len(plan.rules),
            "kind": ctx.kind,
        }
        if ctx.kind == "block" and ctx.in_shardings is None:
            from jax.sharding import NamedSharding, PartitionSpec

            # block programs take batch-major activations: constrain
            # every input/output to the plan's data spec so the
            # compiled executable refuses silently-replicated operands
            shd = NamedSharding(mesh, plan.data_spec())
            ctx.in_shardings = shd
            ctx.out_shardings = shd
        _telemetry.record_sharding_stamp(ctx.label or "?", ctx.kind)
        return closed_jaxpr
