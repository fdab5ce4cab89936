"""Fused optimizer-ladder Pallas kernel: the PR-4 bucket body — rescale
→ global-norm scale → per-element clip → `cls._rule` → master-copy cast
— as ONE kernel per parameter, one HBM read/write per operand.

The XLA bucket body is already a single fused dispatch, but with
multi-precision the low→f32 grad cast is a widening root (the r5
audit's optimizer-chain region): XLA materializes the f32 grad between
the cast and the update math, an extra read+write of every gradient.
The kernel runs the WHOLE ladder on each VMEM-resident block, so the
f32 grad never exists in HBM.

The optimizer's actual `cls._rule` traces INTO the kernel — the ladder
is generic over any elementwise rule (SGD/NAG/Signum/Adam/AdamW); rules
that couple elements across the tensor (LAMB-style layer norms) are
rejected by the allowlist and fall back.  Hyperparameters (lr, wd, t,
rescale, the rule's own scalars) ride in as one traced SMEM vector, so
LR schedules never retrace — exactly the weak-scalar contract of the
XLA path.  All kernel math is f32 (mp masters, or f32 weights), same op
order as `Optimizer._fused_param_step`; parity is allclose at ~1 ulp —
the kernel body compiles as one fused program (FMA contraction), which
the op-by-op XLA schedule need not match bit-for-bit.

`param_step` is the drop-in twin of `Optimizer._fused_param_step`:
unsupported rule/shape/dtype falls back to it verbatim, recording the
outcome via kernels.dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch as _dispatch

__all__ = ["param_step"]

# rules proven elementwise: safe to evaluate per VMEM block
_RULE_ALLOW = frozenset(("SGD", "NAG", "Signum", "Adam", "AdamW"))

_LANE = 128


def _fallback(cls, clip, gn, mp, w, st, g, lr, wd, t, scale, hyper):
    from ..optimizer.optimizer import Optimizer
    return Optimizer._fused_param_step(cls, clip, gn, mp, w, st, g, lr,
                                       wd, t, scale, hyper)


def _supported(cls, mp, w, st, g):
    """None when the ladder kernel can run this parameter, else the
    fallback outcome name."""
    if cls.__name__ not in _RULE_ALLOW:
        return "unsupported_rule"
    size = int(w.size)
    if size < 1024 or size % (8 * _LANE):
        return "unsupported_shape"
    if mp:
        master, inner = st
        if master.dtype != jnp.float32 or master.shape != w.shape:
            return "unsupported_dtype"
        leaves = jax.tree_util.tree_leaves(inner)
    else:
        if w.dtype != jnp.float32 or g.dtype != jnp.float32:
            return "unsupported_dtype"
        leaves = jax.tree_util.tree_leaves(st)
    for leaf in leaves:
        if (getattr(leaf, "shape", None) != w.shape
                or leaf.dtype != jnp.float32):
            return "unsupported_shape"
    return None


def _decide(cls, mp, w, st, g):
    """(use_kernel, outcome, bytes_saved, xla_bytes, kernel_bytes); the
    byte scores are None when the ladder exits before the model."""
    mode = _dispatch.mode()
    if mode == "off":
        return False, "off", 0, None, None
    reason = _supported(cls, mp, w, st, g)
    if reason is not None:
        return False, reason, 0, None, None
    if not _dispatch.platform_ok():
        return False, "platform", 0, None, None
    leaves = jax.tree_util.tree_leaves(st[1] if mp else st)
    from ..passes import memory as _memory
    xla_b, k_b = _memory.optimizer_region_bytes(
        w.size, w.dtype, len(leaves), mp)
    if mode == "force":
        return True, "kernel", max(0, xla_b - k_b), xla_b, k_b
    ok, outcome, saved = _dispatch.auto_accepts(xla_b, k_b)
    return ok, outcome, saved, xla_b, k_b


def _ladder_kernel(scal_ref, w_ref, g_ref, *refs, rule, clip, gn, mp,
                   n_state, hyper_keys, treedef, out_w_dtype):
    state_refs = refs[:n_state]
    outs = refs[n_state:]
    # hyperparameters enter the rule as (1, 1) vectors, not SMEM scalars:
    # a rule may do more than multiply with them (Adam's `beta ** t`), and
    # Mosaic legalizes such math on vectors only
    def vec(i):
        return jnp.full((1, 1), scal_ref[i], jnp.float32)

    lr, wd, t, rescale, gscale = (vec(i) for i in range(5))
    h = {k: vec(5 + j) for j, k in enumerate(hyper_keys)}
    h["t"] = t
    h["rescale_grad"] = rescale
    g = g_ref[...]
    if mp:
        g = g.astype(jnp.float32)
    g = g * rescale
    if gn:
        g = g * gscale
    if clip is not None:
        g = jnp.clip(g, -clip, clip)
    st = jax.tree_util.tree_unflatten(
        treedef, [r[...] for r in state_refs])
    nw, ns = rule(w_ref[...], g, st, lr, wd, h)
    ns_leaves = jax.tree_util.tree_leaves(ns)
    if mp:
        outs[0][...] = nw                       # new f32 master
        for r, leaf in zip(outs[1:1 + n_state], ns_leaves):
            r[...] = leaf
        outs[1 + n_state][...] = nw.astype(out_w_dtype)
    else:
        outs[0][...] = nw
        for r, leaf in zip(outs[1:], ns_leaves):
            r[...] = leaf


def _block_rows(m):
    for cand in (1024, 512, 256, 128, 64, 32, 16, 8):
        if m % cand == 0:
            return cand
    return 8


def _ladder_pallas(cls, clip, gn, mp, w, st, g, lr, wd, t, scale, hyper):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if mp:
        master, inner = st
        state_leaves, treedef = jax.tree_util.tree_flatten(inner)
        wv = master
    else:
        state_leaves, treedef = jax.tree_util.tree_flatten(st)
        wv = w
    n_state = len(state_leaves)
    m = w.size // _LANE
    bm = _block_rows(m)

    hyper_keys = tuple(sorted(k for k in hyper
                              if k not in ("rescale_grad", "t")))
    svals = [lr, wd, t, hyper["rescale_grad"],
             scale if gn else 0.0]
    svals += [hyper[k] for k in hyper_keys]
    scal = jnp.stack([jnp.asarray(v, jnp.float32) for v in svals])

    big = pl.BlockSpec((bm, _LANE), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    n_out = (2 + n_state) if mp else (1 + n_state)
    out_shape = []
    if mp:
        out_shape.append(jax.ShapeDtypeStruct((m, _LANE), jnp.float32))
    else:
        out_shape.append(jax.ShapeDtypeStruct((m, _LANE), w.dtype))
    out_shape += [jax.ShapeDtypeStruct((m, _LANE), jnp.float32)
                  for _ in range(n_state)]
    if mp:
        out_shape.append(jax.ShapeDtypeStruct((m, _LANE), w.dtype))

    kernel = functools.partial(
        _ladder_kernel,
        rule=cls._rule, clip=clip, gn=gn, mp=mp, n_state=n_state,
        hyper_keys=hyper_keys, treedef=treedef, out_w_dtype=w.dtype)
    outs = _dispatch.pallas_call(
        kernel,
        name="optimizer_update_ladder",
        grid=(m // bm,),
        in_specs=[smem, big, big] + [big] * n_state,
        out_specs=[big] * n_out,
        out_shape=out_shape,
        interpret=_dispatch.interpret_requested(),
    )(scal, wv.reshape(m, _LANE), g.reshape(m, _LANE),
      *[leaf.reshape(m, _LANE) for leaf in state_leaves])

    if mp:
        new_master = outs[0].reshape(w.shape)
        new_inner = jax.tree_util.tree_unflatten(
            treedef, [o.reshape(w.shape) for o in outs[1:1 + n_state]])
        new_w = outs[1 + n_state].reshape(w.shape)
        return new_w, (new_master, new_inner)
    new_w = outs[0].reshape(w.shape)
    new_state = jax.tree_util.tree_unflatten(
        treedef, [o.reshape(w.shape) for o in outs[1:]])
    return new_w, new_state


def param_step(cls, clip, gn, mp, w, st, g, lr, wd, t, scale, hyper):
    """Pallas-backed twin of Optimizer._fused_param_step — one
    parameter's rescale → clip → rule → cast ladder.  Falls back to the
    XLA body (bitwise-identical numerics) when the kernel can't run."""
    use_kernel, outcome, saved, xla_b, k_b = _decide(cls, mp, w, st, g)
    _dispatch.record("opt_" + cls.__name__.lower(), outcome, saved,
                     xla_bytes=xla_b, kernel_bytes=k_b)
    if not use_kernel:
        return _fallback(cls, clip, gn, mp, w, st, g, lr, wd, t, scale,
                         hyper)
    return _ladder_pallas(cls, clip, gn, mp, w, st, g, lr, wd, t, scale,
                          hyper)
