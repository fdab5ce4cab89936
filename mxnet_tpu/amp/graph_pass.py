"""AMP as a graph pass (reference: src/nnvm/low_precision_pass.cc + the
amp/lists cast-list machinery — ReducePrecision graph conversion that
selectively wraps ops in casts, rather than just casting parameters).

TPU re-design: the traced jaxpr is rewritten by an interpreter that
enforces the cast lists at every equation:
  * LP16 ops (the FLOP carriers: dot_general, conv) run in bfloat16 —
    float32 operands are cast down at the op boundary;
  * FP32 ops (numerically sensitive: exp/log/softmax chain, norms'
    rsqrt, reductions) run in float32 — low-precision operands are cast
    up, so a user-written eager op accumulates in fp32 *by construction*
    (the round-1 gap: _FP32_OPS was a comment-level contract);
  * everything else runs in the widest float dtype among its operands;
  * graph outputs are cast back to their original dtypes.

`convert_hybrid_block(net, graph_pass=True)` installs the rewritten
program as the block's compiled variant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

__all__ = ["amp_rewrite", "AmpStats", "LP16_PRIMS", "FP32_PRIMS",
           "build_amp_variant", "convert_block_graph"]

# the FLOP carriers — MXU ops that bf16 accelerates
LP16_PRIMS = frozenset({"dot_general", "conv_general_dilated"})

# numerically-sensitive ops pinned to fp32 (reference: amp/lists FP32 ops)
FP32_PRIMS = frozenset({
    "exp", "log", "log1p", "expm1", "rsqrt", "sqrt", "erf", "erf_inv",
    "lgamma", "digamma", "reduce_sum", "reduce_max", "reduce_min",
    "reduce_prod", "cumsum", "cumlogsumexp", "logistic", "tanh", "pow",
    "integer_pow", "div", "atan2",
})

_FLOAT_DTYPES = (jnp.float16, jnp.bfloat16, jnp.float32, jnp.float64)


class AmpStats:
    """Counts of cast decisions — observability for tests/debugging."""

    def __init__(self):
        self.lp16_ops = 0
        self.fp32_pinned_ops = 0

    def __repr__(self):
        return (f"AmpStats(lp16_ops={self.lp16_ops}, "
                f"fp32_pinned_ops={self.fp32_pinned_ops})")


def _is_float(x):
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)


def _cast_floats(vals, dtype):
    return [v.astype(dtype) if _is_float(v) and v.dtype != dtype else v
            for v in vals]


def _widest_float(vals):
    widest = None
    for v in vals:
        if _is_float(v):
            if widest is None or jnp.finfo(v.dtype).bits > \
                    jnp.finfo(widest).bits:
                widest = v.dtype
    return widest


def amp_rewrite(closed_jaxpr, target_dtype=jnp.bfloat16, stats=None):
    """Return callable(*flat_args) executing the jaxpr under the AMP cast
    lists. Outputs are cast back to the original output dtypes."""
    from ..subgraph import _eval_eqn

    jaxpr = closed_jaxpr.jaxpr
    consts = closed_jaxpr.consts
    out_dtypes = [getattr(v.aval, "dtype", None) for v in jaxpr.outvars]
    stats = stats if stats is not None else AmpStats()

    # decide once at rewrite time (trace-time work, not per step)
    plan = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in LP16_PRIMS:
            plan.append("lp16")
            stats.lp16_ops += 1
        elif name in FP32_PRIMS:
            plan.append("fp32")
            stats.fp32_pinned_ops += 1
        elif name in ("jit", "closed_call", "custom_jvp_call",
                      "custom_vjp_call", "remat2", "checkpoint",
                      "convert_element_type"):
            plan.append("exact")  # opaque bodies / explicit user casts
        else:
            plan.append("widest")

    def run(*args):
        env = {}

        def read(v):
            if isinstance(v, jcore.Literal):
                return jnp.asarray(v.val)
            return env[v]

        for v, c in zip(jaxpr.constvars, consts):
            env[v] = c
        for v, a in zip(jaxpr.invars, args):
            env[v] = a

        for eqn, decision in zip(jaxpr.eqns, plan):
            invals = [read(v) for v in eqn.invars]
            if decision == "lp16":
                invals = _cast_floats(invals, target_dtype)
            elif decision == "fp32":
                invals = _cast_floats(invals, jnp.float32)
            elif decision == "exact":
                # opaque call bodies expect their recorded operand dtypes
                invals = [
                    val.astype(v.aval.dtype)
                    if _is_float(val) and hasattr(v.aval, "dtype")
                    and jnp.issubdtype(v.aval.dtype, jnp.floating)
                    and val.dtype != v.aval.dtype else val
                    for val, v in zip(invals, eqn.invars)]
            else:
                w = _widest_float(invals)
                if w is not None:
                    invals = _cast_floats(invals, w)
            out = _eval_eqn(eqn, invals)
            if isinstance(out, (tuple, list)):
                for v, val in zip(eqn.outvars, out):
                    env[v] = val
            else:
                env[eqn.outvars[0]] = out

        outs = []
        for v, dt in zip(jaxpr.outvars, out_dtypes):
            val = read(v)
            if dt is not None and _is_float(val) and val.dtype != dt:
                val = val.astype(dt)
            outs.append(val)
        return outs

    run._amp_stats = stats
    return run


def build_amp_variant(cached_fn, target_dtype, pd, key, datas):
    """Trace + AMP-rewrite one compiled variant. Returns (jitted, stats).
    Legacy one-off builder, now a thin veneer over the pass pipeline
    (passes.AmpPass via apply_pipeline) so jit construction for
    captured bodies lives in ONE place; the eval_shape builds the
    pipeline entry eagerly (abstract — no compute) so stats are filled
    on return, as before."""
    from .. import passes as _passes

    stats = AmpStats()
    ctx = _passes.PassContext(label="amp_variant", kind="block")
    jitted = _passes.apply_pipeline(
        cached_fn, [_passes.AmpPass(target_dtype, stats=stats)], ctx)
    jax.eval_shape(jitted, pd, key, *datas)
    return jitted, stats


def convert_block_graph(block, example_inputs, target_dtype=jnp.bfloat16):
    """Enable the AMP graph pass on a HybridBlock: registers
    passes.AmpPass on the block's pass pipeline, so the traced jaxpr is
    rewritten under the cast lists for EVERY compiled variant — block
    jit, export, symbol lowering — now and on every rebuild.  Returns
    the AmpStats of the eagerly-built variant.  (The graph-pass mode of
    amp.convert_hybrid_block.)"""
    from .. import passes as _passes

    block.hybridize(True)
    block.pass_pipeline().register(_passes.AmpPass(target_dtype))
    block._jit_variants.clear()
    block(*example_inputs)  # force one build so stats are available
    return block._amp_stats
