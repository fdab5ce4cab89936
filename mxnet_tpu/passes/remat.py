"""Rematerialization pass: segmented ``jax.checkpoint`` over captured
training graphs, with a cost-model-driven ``auto`` policy.

The reference stack exposes per-layer mirroring (memonger); the TPU
papers' framing is a policy chosen from a cost model rather than from
measurement.  This pass splits the captured forward body into ~√N
contiguous equation segments and wraps each in ``jax.checkpoint``, so
the backward pass recomputes one segment at a time instead of keeping
every activation live — the classic O(√N) activation-memory schedule.
A single whole-body checkpoint would be pointless (the backward would
recompute everything at once and peak residency would not move);
segmentation is what bends the curve.

Policies (MXTPU_REMAT_POLICY, or ``RematPass(policy)``):

  none   leave the graph alone (default)
  dots   segments save matmul/conv outputs (jax.checkpoint_policies
         .dots_saveable) — cheap recompute, most of the win
  full   segments save only their boundary values — max memory saving,
         max recompute
  auto   estimate the fwd+bwd peak residency (passes/memory.py liveness
         walk, cross-checked against the diagnostics compile registry)
         for each policy and pick the cheapest one that fits the budget
         (MXTPU_REMAT_BUDGET_MB, else the device's memory_stats
         bytes_limit; with neither, resolves to ``none``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from .. import env as _env
from ..telemetry import instruments as _telemetry
from . import manager as _manager
from .manager import GraphPass

__all__ = [
    "POLICIES",
    "RematPass",
    "choose_policy",
    "default_segments",
    "remat_budget_bytes",
    "segmented_remat",
    "checkpoint_block",
]

POLICIES = ("none", "dots", "full")


def default_segments(n_eqns):
    """~√N contiguous segments: the textbook memory/recompute sweet
    spot."""
    return max(2, int(round(math.sqrt(max(n_eqns, 1)))))


def remat_budget_bytes():
    """The HBM budget `auto` fits into, or None (→ no remat)."""
    mb = int(_env.get("MXTPU_REMAT_BUDGET_MB"))
    if mb > 0:
        return mb << 20
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
    except Exception:
        pass
    return None


def checkpoint_block(block, *args, save=()):
    """``block(*args)`` as one ``jax.checkpoint`` segment while a program
    is being traced: the backward pass recomputes the block from its
    inputs instead of keeping what it computed, except the values named
    in ``save`` (`jax.ad_checkpoint.checkpoint_name`), which are kept.  A
    model that is a stack of equal layers calls each layer through this
    to hold one layer's activations at a time (the per-layer schedule;
    `segmented_remat` cuts a captured program by equation count instead).  State the block
    writes through the trace's sink (running statistics, an expert
    layer's counters) leaves the segment as outputs and is recorded
    outside it.  Untraced, it is a plain call."""
    from ..gluon.block import _push_sink, _StateSink, current_state_sink
    from ..ndarray.ndarray import NDArray

    nd_pos = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
    datas = [args[i]._data for i in nd_pos]
    if not any(isinstance(d, jax.core.Tracer) for d in datas):
        return block(*args)
    outer, written = current_state_sink(), []

    def segment(*xs):
        call = list(args)
        for i, x in zip(nd_pos, xs):
            call[i] = NDArray(x)
        inner = _StateSink()
        with _push_sink(inner):
            out = block(*call)
        written[:] = inner.params
        return (jax.tree_util.tree_map(
            lambda a: a._data if isinstance(a, NDArray) else a, out,
            is_leaf=lambda a: isinstance(a, NDArray)), tuple(inner.values))

    policy = (jax.checkpoint_policies.save_only_these_names(*save)
              if save else None)
    out, values = jax.checkpoint(segment, policy=policy)(*datas)
    if outer is not None:
        for p, v in zip(written, values):
            outer.record(p, v)
    return jax.tree_util.tree_map(NDArray, out)


def _seam_platform(closed, ctx):
    """Platform the rewritten program will actually run on: the devices
    already committed on the captured consts (traced weights), else the
    seam block's materialized parameters, else the process default.
    ``jax.default_backend()`` alone is wrong in a mixed-backend process —
    a CPU-placed program built under a TPU default would keep the CPU-
    hostile barrier, and an accelerator program under a CPU default
    would lose it."""
    platforms = set()

    def collect(arr):
        devs = getattr(arr, "devices", None)
        if callable(devs):
            try:
                platforms.update(d.platform for d in devs())
            except Exception:
                pass

    for c in closed.consts:
        collect(c)
    if not platforms and ctx is not None and ctx.block is not None:
        try:
            for _n, p in getattr(ctx.block, "_cached_param_list", ()):
                collect(p.data()._data)
        except Exception:
            pass
    if len(platforms) == 1:
        return platforms.pop()
    return jax.default_backend()


def segmented_remat(closed, policy, n_segments, ctx=None):
    """Rewrite ``closed`` so its equations run as ``n_segments``
    contiguous ``jax.checkpoint`` segments; returns a new ClosedJaxpr
    computing bitwise-identical outputs."""
    from ..subgraph import _eval_eqn

    jaxpr, consts = closed.jaxpr, list(closed.consts)
    eqns = list(jaxpr.eqns)
    if len(eqns) < 2:
        return closed
    n_segments = max(1, min(int(n_segments), len(eqns)))
    bounds = [len(eqns) * k // n_segments for k in range(n_segments + 1)]
    jax_policy = (None if policy == "full"
                  else jax.checkpoint_policies.dots_saveable)
    # XLA:CPU's thunk runtime mis-assigns layouts around the
    # optimization_barrier jax.checkpoint inserts (DotThunk's dim0-major
    # check rejects the transposed dots in the recompute); CPU has no
    # HBM to protect, so drop the CSE barrier there and keep it on real
    # accelerators where it preserves the rematerialization.
    prevent_cse = _seam_platform(closed, ctx) != "cpu"

    out_needed = {id(v) for v in jaxpr.outvars
                  if not isinstance(v, jcore.Literal)}
    segments = []
    for s in range(n_segments):
        chunk = eqns[bounds[s]:bounds[s + 1]]
        if not chunk:
            continue
        local = {id(v) for eqn in chunk for v in eqn.outvars}
        ins, seen = [], set()
        for eqn in chunk:
            for v in eqn.invars:
                if isinstance(v, jcore.Literal):
                    continue
                if id(v) in local or id(v) in seen:
                    continue
                seen.add(id(v))
                ins.append(v)
        later_use = {id(v) for eqn in eqns[bounds[s + 1]:]
                     for v in eqn.invars
                     if not isinstance(v, jcore.Literal)}
        outs, odone = [], set()
        for eqn in chunk:
            for v in eqn.outvars:
                if id(v) in odone:
                    continue
                if id(v) in later_use or id(v) in out_needed:
                    odone.add(id(v))
                    outs.append(v)
        segments.append((chunk, ins, outs))

    def rematted(*args):
        env = {}
        for v, val in zip(jaxpr.constvars, consts):
            env[id(v)] = val
        for v, val in zip(jaxpr.invars, args):
            env[id(v)] = val

        def read(v):
            if isinstance(v, jcore.Literal):
                return jnp.asarray(v.val)
            return env[id(v)]

        for chunk, ins, outs in segments:
            if not outs:  # dead tail — nothing downstream reads it
                continue

            def seg_fn(*vals, _chunk=chunk, _ins=ins, _outs=outs):
                local_env = {id(v): val for v, val in zip(_ins, vals)}

                def rd(v):
                    if isinstance(v, jcore.Literal):
                        return jnp.asarray(v.val)
                    return local_env[id(v)]

                for eqn in _chunk:
                    out = _eval_eqn(eqn, [rd(v) for v in eqn.invars])
                    if isinstance(out, (list, tuple)):
                        for v, val in zip(eqn.outvars, out):
                            local_env[id(v)] = val
                    else:
                        local_env[id(eqn.outvars[0])] = out
                return tuple(local_env[id(v)] for v in _outs)

            vals = tuple(read(v) for v in ins)
            res = jax.checkpoint(seg_fn, policy=jax_policy,
                                 prevent_cse=prevent_cse)(*vals)
            for v, val in zip(outs, res):
                env[id(v)] = val
        return tuple(read(v) for v in jaxpr.outvars)

    return _manager.retrace_flat(rematted, closed)


def choose_policy(closed, ctx):
    """`auto`: pick the cheapest policy whose estimated fwd+bwd peak
    residency fits the budget.  Estimates come from the liveness walk
    (passes/memory.py); the compile registry's measured peak for this
    seam, when present, floors the `none` estimate so a backend-reported
    number is never ignored."""
    from . import memory as _memory

    budget = remat_budget_bytes()
    if budget is None:
        return "none"

    estimates = {}
    n_seg = default_segments(len(closed.jaxpr.eqns))
    for cand in POLICIES:
        try:
            c = closed if cand == "none" else segmented_remat(
                closed, cand, n_seg, ctx)
            estimates[cand] = _memory.estimate_training_peak_bytes(c)
        except Exception:
            estimates[cand] = None
    try:
        from ..diagnostics.introspect import compile_registry
        entry = compile_registry().get((ctx.label, ctx.variant))
        measured = entry and entry.get("peak_hbm_bytes")
        if measured and estimates.get("none") is not None:
            estimates["none"] = max(estimates["none"], int(measured))
    except Exception:
        pass

    ctx.notes["remat_estimates"] = dict(estimates)
    ctx.notes["remat_budget_bytes"] = budget
    for cand in POLICIES:  # none → dots → full: least recompute first
        est = estimates.get(cand)
        if est is not None and est <= budget:
            return cand
    return "full" if estimates.get("full") is not None else "none"


class RematPass(GraphPass):
    """Wraps training graphs in segmented ``jax.checkpoint``.  Applies
    only to training builds (a predict graph has no backward to save
    memory in)."""

    name = "remat"
    priority = 90  # after precision rewrites: remat the graph AMP made
    kinds = ("block", "whole_step_fwd")

    def __init__(self, policy="auto", segments=None):
        self.policy = str(policy or "auto").lower()
        self.segments = segments

    def applies(self, ctx):
        if ctx.kind not in self.kinds:
            return False
        return ctx.training or ctx.kind == "whole_step_fwd"

    def run(self, closed, ctx):
        policy = self.policy
        if policy in ("auto", "1", "true", "on"):
            policy = choose_policy(closed, ctx)
        if policy not in POLICIES:
            raise ValueError(
                f"MXTPU_REMAT_POLICY={policy!r}: expected one of "
                f"{POLICIES + ('auto',)}")
        _telemetry.record_remat_policy(ctx.label, policy)
        ctx.notes["remat_policy"] = policy
        if policy == "none" or len(closed.jaxpr.eqns) < 2:
            return closed
        n_seg = self.segments or default_segments(len(closed.jaxpr.eqns))
        return segmented_remat(closed, policy, n_seg, ctx)
