"""LayoutPass — whole-graph NHWC propagation with transpose elision.

TPUs strongly prefer channels-last tilings (C rides the 128-wide lane
dimension, so convs feed the MXU and BN/elementwise chains vectorize
without relayouts), but the reference default is NCHW and per-layer
``layout=`` flags leave mixed graphs paying transpose pairs at every
conv/norm seam.  This pass makes layout a COMPILER decision, the way
TVM's graph-level layout-transformation pass and the learned-TPU-cost-
model work frame it: walk the captured jaxpr once, rewrite every
``conv_general_dilated`` to NHWC/HWIO dimension numbers, propagate
channels-last through elementwise / BN / reduce / reduce_window ops, and
materialize a transpose ONLY at an unavoidable boundary.

The interpreter is lazy: every jaxpr var maps to a dict of
``{permutation: value}`` and values materialize on demand, so

  * a pre-existing ``transpose`` equation is ABSORBED into the
    permutation key (no op emitted) — transpose·transpose pairs cancel
    for free, and survivors sink to the graph edges (the final outvar
    reads at identity);
  * ``reshape`` / ``broadcast_in_dim`` register permutation-polymorphic
    makers, so a bias broadcast materializes directly in the layout its
    consumer wants instead of broadcasting channels-first and paying a
    transpose.

Weights are re-laid-out PERSISTENTLY and eagerly by
:func:`prepare_block` (called from ``HybridBlock._call_cached`` and
``TrainStep.__call__`` before the first trace): a one-time device-side
OIHW→HWIO transpose recorded on the Parameter as ``_layout_perm``.  The
captured program then sees HWIO weight invars from the start — one
compile, zero per-step weight transposes, and the PR-4/6 donated
whole-step path updates the physical (HWIO) buffers in place.
Checkpoints round-trip the LOGICAL layout (``Parameter.logical_data``),
so NCHW-era snapshots load bitwise and new snapshots stay portable.

Modes (``MXTPU_LAYOUT``, kernels-style kill-switch discipline):

  off   (default) nothing consults this module — captured programs are
        bitwise-identical to main with zero extra traces;
  auto  rewrite only when the passes/memory.py external-bytes model
        predicts a win: skip graphs with no channels-first convs (zero
        retrace), decline regions whose conv activations are under
        MXTPU_LAYOUT_MIN_BYTES, and decline when the bytes of inserted
        boundary transposes rival the predicted conv-side saving;
  nhwc  rewrite whenever a channels-first conv is present.

docs/layout.md is the user-facing tour.
"""
from __future__ import annotations

import sys

import numpy as _np

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import core as jcore

from .. import env as _env
from ..telemetry import instruments as _telemetry
from . import manager as _manager
from .manager import GraphPass

__all__ = ["LayoutPass", "mode", "min_bytes", "prepare_block",
           "weight_perm"]

# same normalization table as kernels.dispatch._MODES / numerics.mode():
# the ONE place MXTPU_LAYOUT is interpreted — resolve_passes injection,
# prepare_block, and the pass itself all read mode()
_MODES = {
    "": "off", "0": "off", "off": "off", "false": "off", "no": "off",
    "none": "off",
    "1": "auto", "auto": "auto", "on": "auto", "true": "auto",
    "yes": "auto",
    "nhwc": "nhwc", "force": "nhwc", "always": "nhwc",
}


def mode():
    """Resolved MXTPU_LAYOUT mode: 'off' | 'auto' | 'nhwc'."""
    raw = str(_env.get("MXTPU_LAYOUT")).strip().lower()
    try:
        return _MODES[raw]
    except KeyError:
        raise ValueError(
            f"MXTPU_LAYOUT={raw!r} is not a recognized mode; expected "
            f"off | auto | nhwc") from None


def min_bytes():
    """auto declines graphs whose conv activations total less than this."""
    return int(_env.get("MXTPU_LAYOUT_MIN_BYTES"))


# ---------------------------------------------------------------------------
# persistent weight re-layout
# ---------------------------------------------------------------------------


def weight_perm(nd):
    """The OIHW→HWIO-family permutation for an nd-spatial conv weight
    ((O, I, *k) → (*k, I, O)); 2-D: (2, 3, 1, 0)."""
    return tuple(range(2, 2 + nd)) + (1, 0)


def prepare_block(block, trainer=None):
    """One-time persistent re-layout of every channels-first conv weight
    under ``block`` to HWIO, recorded as ``Parameter._layout_perm``.

    Idempotent and eager: call sites (``HybridBlock._call_cached``,
    ``TrainStep.__call__``) run it BEFORE the first trace, so the
    captured program's weight invars are already channels-last — no
    extra compile, and the donated whole-step writeback updates the
    physical buffers consistently.  A ``trainer`` (when known) gets its
    momentum-class optimizer-state leaves transposed alongside, keeping
    state/weight layouts matched for already-created states.
    """
    if getattr(block, "_layout_prepared", False):
        return
    if mode() == "off":
        return
    complete = True
    for layer in _iter_convs(block):
        if layer._transpose or layer._channels_last:
            continue
        p = layer.weight
        if getattr(p, "_layout_perm", None) is not None:
            continue
        if p._data_map is None:
            # deferred init still pending — retry on the next call
            complete = False
            continue
        _relayout_param(p, layer._ndim)
        if trainer is not None:
            _relayout_states(trainer, p, p._layout_perm)
    if complete:
        object.__setattr__(block, "_layout_prepared", True)


def _iter_convs(block):
    from ..gluon.nn.conv_layers import _Conv

    seen = set()
    stack = [block]
    while stack:
        b = stack.pop()
        if id(b) in seen:
            continue
        seen.add(id(b))
        if isinstance(b, _Conv):
            yield b
        stack.extend(getattr(b, "_children", {}).values())


def _relayout_param(p, nd):
    """Device-side OIHW→HWIO transpose of every data (and grad) copy.
    ``p.shape`` stays LOGICAL; physical layout is ``p._layout_perm``."""
    perm = weight_perm(nd)
    for arr in p._data_map.values():
        arr._data = jnp.transpose(arr._data, perm)
        arr._version += 1
    # grads transpose WITHOUT a version bump: the Trainer's stale-grad
    # tracking compares versions, and a relayout is not a fresh gradient
    for g in (p._grad_map or {}).values():
        g._data = jnp.transpose(g._data, perm)
    p._layout_perm = perm


def _relayout_states(trainer, p, perm):
    """Best-effort: transpose momentum-class optimizer-state leaves
    (shaped like the logical weight) to match the new physical layout."""
    try:
        from ..ndarray.ndarray import NDArray

        states = getattr(trainer, "_states", None)
        params = getattr(trainer, "_params", None)
        if not states or params is None:
            return
        logical = tuple(p._shape or ())
        if len(logical) != len(perm):
            return

        def fix(leaf):
            if isinstance(leaf, NDArray) \
                    and tuple(leaf.shape) == logical:
                leaf._data = jnp.transpose(leaf._data, perm)
            return leaf

        for i, q in enumerate(params):
            if q is p and i < len(states) and states[i] is not None:
                jax.tree_util.tree_map(
                    fix, states[i],
                    is_leaf=lambda x: isinstance(x, NDArray))
    except Exception:
        pass


# ---------------------------------------------------------------------------
# the permutation-keyed lazy interpreter
# ---------------------------------------------------------------------------


def _ident(rank):
    return tuple(range(rank))


def _val_bytes(v):
    try:
        return int(v.size) * _np.dtype(v.dtype).itemsize
    except Exception:
        return 0


class _Stats:
    """One rewrite's accounting — lands in ctx.notes['layout'] and the
    layout_* telemetry counters."""

    __slots__ = ("convs_seen", "convs_rewritten", "convs_already_cl",
                 "bn_propagated", "act_propagated", "eqns_propagated",
                 "transposes_inserted", "inserted_bytes",
                 "transposes_absorbed", "benefit_bytes")

    def __init__(self):
        self.convs_seen = 0
        self.convs_rewritten = 0
        self.convs_already_cl = 0
        self.bn_propagated = 0
        self.act_propagated = 0
        self.eqns_propagated = 0
        self.transposes_inserted = 0
        self.inserted_bytes = 0
        self.transposes_absorbed = 0
        self.benefit_bytes = 0

    @property
    def naive_transposes(self):
        """What a naive PER-OP channels-last rewrite would pay: a
        transpose pair + weight relayout around every conv (3) and a
        pair around every propagated BN / activation (2)."""
        return (3 * self.convs_rewritten
                + 2 * (self.bn_propagated + self.act_propagated))

    @property
    def transposes_elided(self):
        return self.transposes_absorbed + max(
            0, self.naive_transposes - self.transposes_inserted)

    def as_dict(self):
        return {
            "convs_rewritten": self.convs_rewritten,
            "convs_already_cl": self.convs_already_cl,
            "bn_propagated": self.bn_propagated,
            "act_propagated": self.act_propagated,
            "eqns_propagated": self.eqns_propagated,
            "transposes_inserted": self.transposes_inserted,
            "transposes_elided": self.transposes_elided,
            "inserted_bytes": self.inserted_bytes,
            "benefit_bytes": self.benefit_bytes,
        }


# single-output shape-preserving primitives channels-last flows through
# untouched (lax-level operands of one eqn always share a shape; scalars
# pass unchanged)
_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "atan2",
    "and", "or", "xor", "not", "neg", "sign", "abs", "exp", "exp2",
    "expm1", "log", "log1p", "sqrt", "rsqrt", "cbrt", "tanh", "logistic",
    "erf", "erfc", "erf_inv", "sin", "cos", "tan", "sinh", "cosh",
    "asin", "acos", "atan", "floor", "ceil", "round", "is_finite",
    "integer_pow", "square", "convert_element_type", "select_n", "clamp",
    "nextafter", "eq", "ne", "lt", "le", "gt", "ge", "stop_gradient",
    "copy",
})
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
    "reduce_and", "reduce_or",
})
_RW_PRIMS = frozenset({
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
})


def _conv_perms(dn):
    """(lhs, rhs, out) permutations carrying each conv operand from the
    eqn's dimension_numbers to channels-last (NHWC / HWIO / NHWC),
    spatial order preserved — identity triple means the conv already IS
    channels-last.  Generic over rank and over deconv-style IO specs."""
    lhs_perm = (dn.lhs_spec[0],) + tuple(dn.lhs_spec[2:]) + (dn.lhs_spec[1],)
    rhs_perm = tuple(dn.rhs_spec[2:]) + (dn.rhs_spec[1], dn.rhs_spec[0])
    out_perm = (dn.out_spec[0],) + tuple(dn.out_spec[2:]) + (dn.out_spec[1],)
    return lhs_perm, rhs_perm, out_perm


def _closure_objects(fn, depth=0):
    if depth > 6 or not callable(fn):
        return
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        yield v
        if callable(v):
            yield from _closure_objects(v, depth + 1)


def _bn_target(eqn):
    """Recognize the framework's BN-training custom_vjp equation and
    recover its nondiff (eps, axis).  Returns (callable, eps, axis) —
    the exact function to RE-EMIT (never inline: the custom VJP is the
    closed-form backward) — or None.  Identity checks only; anything
    unrecognized stays a barrier."""
    if eqn.primitive.name != "custom_vjp_call":
        return None
    if eqn.params.get("num_consts") or len(eqn.invars) != 4 \
            or len(eqn.outvars) != 3:
        return None
    wf = eqn.params.get("bwd")
    f = getattr(wf, "f", None)
    if f is None:
        return None
    from ..ops import nn as _nn

    target = None
    if f is _nn._bn_train_bwd:
        target = _nn._bn_train
    else:
        # only a loaded kernels.norm can have emitted the equation
        _knorm = sys.modules.get("mxnet_tpu.kernels.norm")
        if _knorm is not None and f is _knorm._bn_train_bwd:
            target = _knorm.bn_train
    if target is None:
        return None
    # nondiff args ride the WrappedFun's _prepend_static_args transform
    # as Unhashable wrappers: ((eps, axis) order matches nondiff_argnums)
    for t in wf.transforms:
        if getattr(t[0], "__name__", "") != "_prepend_static_args":
            continue
        vals = tuple(getattr(a, "val", a) for a in t[1][0])
        if len(vals) == 2:
            return target, float(vals[0]), int(vals[1])
    return None


def _is_relu(eqn):
    """Exact-identity recognition of jax.nn.relu's custom_jvp equation
    (re-emitting relu keeps its gradient-at-zero semantics; inlining the
    call_jaxpr would not)."""
    if eqn.primitive.name != "custom_jvp_call":
        return False
    if eqn.params.get("num_consts") or len(eqn.invars) != 1 \
            or len(eqn.outvars) != 1:
        return False
    # the eqn's jvp_jaxpr_fun closes over the custom_jvp's own wrapped
    # jvp rule; its core function is jax.nn.relu.jvp exactly when the
    # equation came from jax.nn.relu
    fun = eqn.params["jvp_jaxpr_fun"]
    return any(getattr(o, "f", None) is jax.nn.relu.jvp
               for o in _closure_objects(fun.f))


class _Interpreter:
    """Evaluates a jaxpr re-emitting ops channels-last where profitable.

    ``vals[var]`` maps permutation → traced value, where a value stored
    under perm p satisfies ``v == transpose(x_logical, p)``.  ``makers``
    hold permutation-polymorphic constructors (reshape/broadcast) that
    build a requested layout directly.  Reads materialize lazily; a
    transpose is emitted only when no stored perm or maker can satisfy
    the request — that emission is the ONLY place transposes enter the
    rewritten program."""

    def __init__(self, stats):
        self.vals = {}
        self.makers = {}
        self.stats = stats

    # -- env ---------------------------------------------------------------
    def write(self, var, val, perm=None):
        rank = len(getattr(var, "aval", val).shape) \
            if hasattr(var, "aval") else _np.ndim(val)
        perm = _ident(rank) if perm is None else tuple(perm)
        self.vals.setdefault(var, {})[perm] = val

    def stored_perm(self, atom):
        """A non-identity permutation already held for `atom` (the
        channels-last propagation signal), else None."""
        if isinstance(atom, jcore.Literal):
            return None
        d = self.vals.get(atom)
        if not d:
            return None
        ident = _ident(len(atom.aval.shape))
        for p in d:
            if p != ident:
                return p
        return None

    def read(self, atom, perm=None):
        if isinstance(atom, jcore.Literal):
            v = atom.val
            if perm is None or _np.ndim(v) == 0 \
                    or tuple(perm) == _ident(_np.ndim(v)):
                return v
            return _np.transpose(v, perm)
        rank = len(atom.aval.shape)
        perm = _ident(rank) if perm is None else tuple(perm)
        d = self.vals.setdefault(atom, {})
        if perm in d:
            return d[perm]
        mk = self.makers.get(atom)
        if mk is not None:
            v = mk(perm)
            if v is not None:
                d[perm] = v
                return v
        ident = _ident(rank)
        if ident in d:
            src_p, src_v = ident, d[ident]
        elif d:
            src_p, src_v = next(iter(d.items()))
        elif mk is not None:
            v = mk(ident)
            if v is None:
                raise RuntimeError(f"layout: cannot materialize {atom}")
            d[ident] = v
            src_p, src_v = ident, v
        else:
            raise RuntimeError(f"layout: unbound var {atom}")
        q = tuple(src_p.index(perm[i]) for i in range(rank))
        if q == ident:
            d[perm] = src_v
            return src_v
        out = lax.transpose(src_v, q)
        self.stats.transposes_inserted += 1
        self.stats.inserted_bytes += _val_bytes(out)
        d[perm] = out
        return out

    # -- fallback ----------------------------------------------------------
    def barrier(self, eqn):
        """Re-bind the equation VERBATIM on identity-layout operands —
        the safe default for everything the pass does not recognize."""
        vals = [self.read(a) for a in eqn.invars]
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        outs = eqn.primitive.bind(*subfuns, *vals, **bind_params)
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for var, v in zip(eqn.outvars, outs):
            self.write(var, v)

    # -- rewrite rules -----------------------------------------------------
    def conv(self, eqn):
        from .memory import _aval_bytes

        self.stats.convs_seen += 1
        dn = eqn.params["dimension_numbers"]
        rank = len(dn.lhs_spec)
        ident = _ident(rank)
        lhs_perm, rhs_perm, out_perm = _conv_perms(dn)
        if lhs_perm == ident and out_perm == ident:
            # data already flows channels-last (NHWC-native layer);
            # re-conjugating just the kernel spec buys nothing
            self.stats.convs_already_cl += 1
            return self.barrier(eqn)
        x = self.read(eqn.invars[0], lhs_perm)
        w = self.read(eqn.invars[1], rhs_perm)
        new_spatial = tuple(range(1, rank - 1))
        params = dict(eqn.params)
        params["dimension_numbers"] = lax.ConvDimensionNumbers(
            lhs_spec=(0, rank - 1) + new_spatial,
            rhs_spec=(rank - 1, rank - 2) + tuple(range(rank - 2)),
            out_spec=(0, rank - 1) + new_spatial)
        out = eqn.primitive.bind(x, w, **params)
        self.write(eqn.outvars[0], out, out_perm)
        self.stats.convs_rewritten += 1
        self.stats.benefit_bytes += 2 * (
            _aval_bytes(eqn.invars[0].aval)
            + _aval_bytes(eqn.outvars[0].aval))

    def bn(self, eqn, target, eps, axis):
        xvar = eqn.invars[0]
        rank = len(xvar.aval.shape)
        axis = axis % rank
        if axis == rank - 1:
            return self.barrier(eqn)  # already channels-last
        p = self.stored_perm(xvar)
        if p is None or p[-1] != axis:
            # send the channel axis last, other dims keeping order
            p = tuple(i for i in range(rank) if i != axis) + (axis,)
        x = self.read(xvar, p)
        gamma = self.read(eqn.invars[1])
        beta = self.read(eqn.invars[2])
        shift = self.read(eqn.invars[3])
        out, mean, var = target(x, gamma, beta, shift,
                                float(eps), int(p.index(axis)))
        self.write(eqn.outvars[0], out, p)
        self.write(eqn.outvars[1], mean)
        self.write(eqn.outvars[2], var)
        self.stats.bn_propagated += 1

    def relu(self, eqn):
        p = self.stored_perm(eqn.invars[0])
        if p is None:
            return self.barrier(eqn)
        out = jax.nn.relu(self.read(eqn.invars[0], p))
        self.write(eqn.outvars[0], out, p)
        self.stats.act_propagated += 1

    def transpose(self, eqn):
        xvar = eqn.invars[0]
        if isinstance(xvar, jcore.Literal):
            return self.barrier(eqn)
        q = tuple(eqn.params["permutation"])
        d = self.vals.get(xvar)
        mk = self.makers.get(xvar)
        if not d and mk is None:
            return self.barrier(eqn)
        out_var = eqn.outvars[0]
        rank = len(q)
        if d:
            # absorb: out stored under s holds transpose(x, s∘q) with
            # (s∘q)[i] = q[s[i]]; pick s so s∘q is a perm we already hold
            ident = _ident(rank)
            p0, v0 = (ident, d[ident]) if ident in d \
                else next(iter(d.items()))
            s = tuple(q.index(p0[i]) for i in range(rank))
            self.write(out_var, v0, s)
        else:
            def out_maker(s, _mk=mk, _q=q):
                return _mk(tuple(_q[i] for i in s))
            self.makers[out_var] = out_maker
        self.stats.transposes_absorbed += 1

    def reshape(self, eqn):
        xvar = eqn.invars[0]
        if eqn.params.get("dimensions") is not None \
                or isinstance(xvar, jcore.Literal):
            return self.barrier(eqn)
        new_sizes = tuple(eqn.params["new_sizes"])
        out_rank = len(new_sizes)
        out_nonsing = sum(1 for dim in new_sizes if dim != 1)
        x_shape = tuple(xvar.aval.shape)
        env = self

        def order_ok(p):
            # transpose(x, p) keeps x's row-major element order iff the
            # non-singleton dims keep their relative order under p
            pos = [p.index(i) for i in range(len(x_shape))
                   if x_shape[i] != 1]
            return pos == sorted(pos)

        def maker(s):
            if s != _ident(out_rank) and out_nonsing > 1:
                return None  # read() materializes identity + transpose
            target = tuple(new_sizes[s[i]] for i in range(out_rank))
            src = next((v for p, v in env.vals.get(xvar, {}).items()
                        if order_ok(p)), None)
            if src is None:
                src = env.read(xvar)
            return jnp.reshape(src, target)

        self.makers[eqn.outvars[0]] = maker

    def broadcast(self, eqn):
        xvar = eqn.invars[0]
        shape = tuple(eqn.params["shape"])
        bd = tuple(eqn.params["broadcast_dimensions"])
        out_rank = len(shape)
        env = self

        def maker(s):
            target = tuple(shape[s[i]] for i in range(out_rank))
            inv_s = {dim: i for i, dim in enumerate(s)}
            if isinstance(xvar, jcore.Literal):
                cands = [(_ident(_np.ndim(xvar.val)), xvar.val)]
            else:
                ident = _ident(len(xvar.aval.shape))
                cands = sorted(env.vals.get(xvar, {}).items(),
                               key=lambda kv: kv[0] != ident)
            for p, v in cands:
                nbd = tuple(inv_s[bd[p[k]]] for k in range(len(p)))
                if all(nbd[j] < nbd[j + 1] for j in range(len(nbd) - 1)):
                    return lax.broadcast_in_dim(v, target, nbd)
            if s == _ident(out_rank):
                return lax.broadcast_in_dim(env.read(xvar), shape, bd)
            return None

        self.makers[eqn.outvars[0]] = maker

    def reduce(self, eqn):
        xvar = eqn.invars[0]
        p = self.stored_perm(xvar)
        if p is None:
            return self.barrier(eqn)
        axes = tuple(eqn.params["axes"])
        new_axes = tuple(sorted(p.index(a) for a in axes))
        kept = [p[k] for k in range(len(p)) if k not in set(new_axes)]
        if kept != sorted(kept):
            # surviving dims would come out permuted — materialize instead
            return self.barrier(eqn)
        v = self.read(xvar, p)
        bp = dict(eqn.params)
        bp["axes"] = new_axes
        subfuns, bind_params = eqn.primitive.get_bind_params(bp)
        out = eqn.primitive.bind(*subfuns, v, **bind_params)
        self.write(eqn.outvars[0], out)
        self.stats.eqns_propagated += 1

    def reduce_window(self, eqn):
        xvar = eqn.invars[0]
        p = self.stored_perm(xvar)
        if p is None:
            return self.barrier(eqn)
        v = self.read(xvar, p)
        bp = dict(eqn.params)
        for k in ("window_dimensions", "window_strides", "base_dilation",
                  "window_dilation", "padding"):
            old = tuple(bp[k])
            bp[k] = tuple(old[p[i]] for i in range(len(p)))
        subfuns, bind_params = eqn.primitive.get_bind_params(bp)
        out = eqn.primitive.bind(*subfuns, v, **bind_params)
        self.write(eqn.outvars[0], out, p)
        self.stats.eqns_propagated += 1

    def opt_barrier(self, eqn):
        perms, vals = [], []
        for a in eqn.invars:
            p = self.stored_perm(a)
            perms.append(p)
            vals.append(self.read(a, p))
        outs = eqn.primitive.bind(*vals)
        for var, p, v in zip(eqn.outvars, perms, outs):
            self.write(var, v, p)

    def elementwise(self, eqn):
        p = None
        rank = 0
        for a in eqn.invars:
            sh = _np.shape(a.val) if isinstance(a, jcore.Literal) \
                else a.aval.shape
            if len(sh) == 0:
                continue
            if rank and len(sh) != rank:
                return self.barrier(eqn)  # unexpected mixed ranks
            rank = len(sh)
            if p is None:
                p = self.stored_perm(a)
        if p is None or len(p) != rank:
            return self.barrier(eqn)
        vals = []
        for a in eqn.invars:
            sh = _np.shape(a.val) if isinstance(a, jcore.Literal) \
                else a.aval.shape
            vals.append(self.read(a, p if len(sh) else None))
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        out = eqn.primitive.bind(*subfuns, *vals, **bind_params)
        self.write(eqn.outvars[0], out, p)
        self.stats.eqns_propagated += 1

    # -- driver ------------------------------------------------------------
    def run(self, closed, args):
        jaxpr = closed.jaxpr
        for var, val in zip(jaxpr.constvars, closed.consts):
            self.write(var, val)
        for var, val in zip(jaxpr.invars, args):
            self.write(var, val)
        for eqn in jaxpr.eqns:
            # bind under the equation's own name stack, so the scopes of
            # the blocks (BottleneckV1_3/BatchNorm_bn2) survive the
            # rewrite into the HLO's op_names
            scope = str(eqn.source_info.name_stack)
            if scope:
                with jax.named_scope(scope):
                    self.rewrite(eqn)
            else:
                self.rewrite(eqn)
        # outvars read at identity: surviving transposes sink to the edges
        return [self.read(v) for v in jaxpr.outvars]

    def rewrite(self, eqn):
        """One equation through the rule for its primitive."""
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            self.conv(eqn)
        elif name == "transpose":
            self.transpose(eqn)
        elif name == "reshape":
            self.reshape(eqn)
        elif name == "broadcast_in_dim":
            self.broadcast(eqn)
        elif name in _REDUCE_PRIMS:
            self.reduce(eqn)
        elif name in _RW_PRIMS:
            self.reduce_window(eqn)
        elif name == "optimization_barrier":
            self.opt_barrier(eqn)
        elif name == "custom_vjp_call":
            bn = _bn_target(eqn)
            if bn is not None:
                self.bn(eqn, *bn)
            else:
                self.barrier(eqn)
        elif name == "custom_jvp_call" and _is_relu(eqn):
            self.relu(eqn)
        elif name in _ELEMENTWISE and len(eqn.outvars) == 1:
            self.elementwise(eqn)
        else:
            self.barrier(eqn)


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def _scan_convs(jaxpr):
    """(channels_first_convs, total_convs, activation_bytes) of the
    top-level conv equations — the zero-cost pre-gate."""
    from .memory import _aval_bytes

    cf = total = act_bytes = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "conv_general_dilated":
            continue
        total += 1
        dn = eqn.params["dimension_numbers"]
        ident = _ident(len(dn.lhs_spec))
        lhs_perm, _, out_perm = _conv_perms(dn)
        if lhs_perm == ident and out_perm == ident:
            continue  # data already channels-last; kernel spec is moot
        cf += 1
        act_bytes += (_aval_bytes(eqn.invars[0].aval)
                      + _aval_bytes(eqn.outvars[0].aval))
    return cf, total, act_bytes


class LayoutPass(GraphPass):
    """Whole-graph channels-last rewrite (module docstring has the full
    story).  Priority 20: after AmpPass(10) fixed dtypes (the byte-model
    scoring must see them) and before KernelPass(40) audits the program
    XLA will actually compile.  An internal error fails the build: a
    rewrite that silently did nothing is indistinguishable from one that
    had nothing to do."""

    name = "layout"
    priority = 20
    kinds = ("block", "export", "whole_step", "whole_step_fwd")

    def __init__(self, mode=None):
        # a forced mode serves the MXTPU_PASSES=layout named-pass path;
        # None defers to MXTPU_LAYOUT at run time
        self._forced = mode

    def run(self, closed, ctx):
        m = self._forced if self._forced is not None else mode()
        note = {"mode": m, "kind": ctx.kind}
        ctx.notes["layout"] = note
        if m == "off":
            note["decision"] = "off"
            return closed
        cf, total, act_bytes = _scan_convs(closed.jaxpr)
        note["convs_seen"] = total
        note["convs_channels_first"] = cf
        if cf == 0:
            # nothing to do: no retrace, no interpreter — the common
            # steady-state (weights pre-laid-out, convs already NHWC)
            note["decision"] = "no_cf_convs"
            return closed
        if ctx.kind == "whole_step":
            # the loss forward was already rewritten at its own
            # whole_step_fwd seam; convs surviving HERE are AD-generated
            # gradient convs whose layouts derive from the rewritten
            # forward — re-conjugating them would fight XLA's own
            # transpose folding, so the outer seam only audits
            note["decision"] = "audit_only"
            return closed
        if m == "auto" and act_bytes < min_bytes():
            note["decision"] = "too_small"
            note["conv_activation_bytes"] = act_bytes
            return closed
        stats = _Stats()

        def rewritten(*flat):
            return tuple(_Interpreter(stats).run(closed, flat))

        new_closed = _manager.retrace_flat(rewritten, closed)
        note.update(stats.as_dict())
        if m == "auto" and stats.benefit_bytes <= 2 * stats.inserted_bytes:
            # boundary transposes rival the predicted conv-side win
            note["decision"] = "declined_no_savings"
            return closed
        note["decision"] = "rewritten"
        _telemetry.record_layout_rewrite(
            stats.convs_rewritten, stats.transposes_inserted,
            stats.transposes_elided)
        return new_closed
