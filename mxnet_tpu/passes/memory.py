"""Peak-residency estimation: a liveness walk over a captured jaxpr.

The reference stack's NNVM memory planner assigns storage by walking
the graph in topological order and freeing buffers at their last use;
the peak of that walk is the plan's residency requirement.  This module
runs the same walk over a jaxpr (recursing into jit/remat2/custom-call
sub-jaxprs) and reports the peak live bytes — a backend-independent
estimate the remat `auto` policy and the diagnostics compile registry
use.  XLA's own `memory_analysis().temp_size_in_bytes` is not usable
for this on CPU: it reports the SUM of temp allocations, not a
liveness-packed peak, so rematerialization never changes it there.

The estimate is an upper-bound-ish approximation (no buffer aliasing,
no fusion eliding intermediates), but it moves the right way: wrapping
segments in ``jax.checkpoint`` drops forward activations from the
backward program's live set, and the walk sees exactly that.
"""
from __future__ import annotations

import itertools

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

__all__ = [
    "estimate_peak_bytes",
    "estimate_training_peak_bytes",
    "estimate_region_bytes",
]

# Call-like primitives whose sub-jaxpr binds the eqn's operands 1:1 —
# safe to inline into the walk.  Loop/branch primitives (scan, while,
# cond) slice or select their operands, so they stay opaque: their
# outputs are counted, their bodies are not expanded.
_INLINE_PRIMS = ("jit", "remat2", "closed_call", "core_call",
                 "custom_jvp_call", "custom_vjp_call")


def _aval_bytes(aval):
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except TypeError:  # symbolic dim
            n *= 1
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG key arrays) — itemsize if exposed
        itemsize = getattr(dtype, "itemsize", 4)
    return n * itemsize


def _sub_jaxpr(eqn):
    """(inner Jaxpr, inner consts) when the eqn is an inlineable call,
    else None."""
    if eqn.primitive.name not in _INLINE_PRIMS:
        return None
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is None:
            continue
        if hasattr(sub, "jaxpr"):  # ClosedJaxpr
            inner, consts = sub.jaxpr, list(sub.consts)
        else:
            inner, consts = sub, []
        if len(inner.invars) == len(eqn.invars):
            return inner, consts
    return None


def estimate_peak_bytes(closed):
    """Peak live bytes of one program: walk eqns in order, allocate
    outputs, free each value after its last use.  Program inputs,
    consts and outputs stay resident for the whole walk (they are real
    buffers XLA holds)."""
    jaxpr = closed.jaxpr
    counter = itertools.count()
    token_bytes = {}
    steps = []  # (in_tokens, out_tokens) per flattened eqn

    def new_token(aval):
        t = next(counter)
        token_bytes[t] = _aval_bytes(aval)
        return t

    def walk(j, in_tokens, const_tokens):
        env = {}
        for v, t in zip(j.constvars, const_tokens):
            env[id(v)] = t
        for v, t in zip(j.invars, in_tokens):
            env[id(v)] = t

        def read(v):
            if isinstance(v, jcore.Literal):
                return None
            return env.get(id(v))

        for eqn in j.eqns:
            ins = [read(v) for v in eqn.invars]
            sub = _sub_jaxpr(eqn)
            if sub is not None:
                inner, consts = sub
                const_ts = [new_token(jax.api_util.shaped_abstractify(c))
                            for c in consts]
                inner_outs = walk(inner, ins, const_ts)
                for v, t in zip(eqn.outvars, inner_outs):
                    if t is None:  # inner returned a literal
                        t = new_token(v.aval)
                        steps.append(((), (t,)))
                    env[id(v)] = t
            else:
                outs = []
                for v in eqn.outvars:
                    t = new_token(v.aval)
                    env[id(v)] = t
                    outs.append(t)
                steps.append((tuple(t for t in ins if t is not None),
                              tuple(outs)))
        return [read(v) for v in j.outvars]

    in_ts = [new_token(v.aval) for v in jaxpr.invars]
    const_ts = [new_token(v.aval) for v in jaxpr.constvars]
    out_ts = walk(jaxpr, in_ts, const_ts)

    last_use = {}
    for i, (ins, _) in enumerate(steps):
        for t in ins:
            last_use[t] = i
    pinned = set(in_ts) | set(const_ts)
    pinned.update(t for t in out_ts if t is not None)

    current = set(in_ts) | set(const_ts)
    cur = sum(token_bytes[t] for t in current)
    peak = cur
    for i, (ins, outs) in enumerate(steps):
        for t in outs:
            if t not in current:
                current.add(t)
                cur += token_bytes[t]
        peak = max(peak, cur)
        for t in set(ins) | set(outs):
            # free at last use; dead values (never read) free immediately
            if (t in current and t not in pinned
                    and last_use.get(t, -1) <= i):
                current.remove(t)
                cur -= token_bytes[t]
    return int(peak)


def estimate_training_peak_bytes(closed):
    """Peak live bytes of the fwd+bwd program derived from a forward
    jaxpr: grad of the summed float outputs w.r.t. every float input —
    the program whose residency rematerialization actually changes.
    Falls back to the forward-only estimate when the program has no
    float outputs or inputs to differentiate."""
    jaxpr = closed.jaxpr

    def _is_float(aval):
        dtype = getattr(aval, "dtype", None)
        return dtype is not None and jnp.issubdtype(dtype, jnp.floating)

    argnums = tuple(i for i, v in enumerate(jaxpr.invars)
                    if _is_float(v.aval))
    has_float_out = any(_is_float(v.aval) for v in jaxpr.outvars)
    if not argnums or not has_float_out:
        return estimate_peak_bytes(closed)

    def scalar_loss(*flat):
        outs = jax.core.eval_jaxpr(jaxpr, closed.consts, *flat)
        total = jnp.zeros((), jnp.float32)
        for o in outs:
            if hasattr(o, "dtype") and jnp.issubdtype(o.dtype,
                                                      jnp.floating):
                total = total + jnp.sum(o.astype(jnp.float32))
        return total

    sds = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
           for v in jaxpr.invars]
    grad_closed = jax.make_jaxpr(
        jax.grad(scalar_loss, argnums=argnums))(*sds)
    return estimate_peak_bytes(grad_closed)


# ---------------------------------------------------------------------------
# per-region external-bytes model
# ---------------------------------------------------------------------------
#
# A union-find of fusable ops at the jaxpr level — before lowering — on
# top of the liveness walk's flattening (_sub_jaxpr / _aval_bytes);
# external bytes = values crossing a region's boundary.  Its one reader
# is the measurement plane (observability/measure.py), which records the
# prediction beside each program's measured time.
#
# The model treats REDUCTIONS and large WIDENING CONVERTS as fusion
# roots — their producers fuse in, their consumers start a new kernel, so
# the value at the boundary round-trips through HBM (a pre-chip
# assumption: on the compiled ResNet-50 step XLA fuses BatchNorm's sums
# into the convolutions, PERF.md section 5):
#
#   * anchor prims (conv/dot/gather/...) are their own region;
#   * reduce prims and >=`widen_threshold`-byte widening converts are
#     fusion ROOTS: they merge upstream, and everything downstream of
#     their output belongs to a later region (tracked by a per-value
#     "root generation" — a step only merges with producers of its own
#     generation);
#   * everything else elementwise-ish merges freely within a generation;
#   * a region's external bytes = bytes of values crossing its boundary
#     (inputs produced outside + outputs consumed outside), the HBM
#     traffic a perfectly-fused XLA schedule still pays.

_ANCHOR_PRIMS = frozenset((
    "conv_general_dilated", "dot_general", "reduce_window_sum",
    "reduce_window_max", "reduce_window_min", "scatter", "scatter-add",
    "scatter_add", "gather", "sort", "dynamic_slice", "dynamic_update_slice",
    "iota", "rng_bit_generator", "random_bits", "fft", "custom_call",
    "pallas_call", "while", "scan", "cond",
))

_REDUCE_PRIMS = frozenset((
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_precision",
    "cumsum", "cumlogsumexp", "cummax", "cummin", "cumprod",
))


def _flatten_steps(closed):
    """Flatten a ClosedJaxpr (inlining _INLINE_PRIMS sub-jaxprs, the same
    walk estimate_peak_bytes does) into a step list for the region model:
    (prim_name, in_tokens, out_tokens).  Returns (steps, token_bytes,
    input_tokens, output_tokens, token_dtype_size)."""
    jaxpr = closed.jaxpr
    counter = itertools.count()
    token_bytes = {}
    token_itemsize = {}
    steps = []

    def new_token(aval):
        t = next(counter)
        token_bytes[t] = _aval_bytes(aval)
        try:
            token_itemsize[t] = np.dtype(
                getattr(aval, "dtype", np.float32)).itemsize
        except TypeError:
            token_itemsize[t] = 4
        return t

    def walk(j, in_tokens, const_tokens):
        env = {}
        for v, t in zip(j.constvars, const_tokens):
            env[id(v)] = t
        for v, t in zip(j.invars, in_tokens):
            env[id(v)] = t

        def read(v):
            if isinstance(v, jcore.Literal):
                return None
            return env.get(id(v))

        for eqn in j.eqns:
            ins = [read(v) for v in eqn.invars]
            sub = _sub_jaxpr(eqn)
            if sub is not None:
                inner, consts = sub
                const_ts = [new_token(jax.api_util.shaped_abstractify(c))
                            for c in consts]
                inner_outs = walk(inner, ins, const_ts)
                for v, t in zip(eqn.outvars, inner_outs):
                    if t is None:
                        t = new_token(v.aval)
                        steps.append(("literal", (), (t,)))
                    env[id(v)] = t
            else:
                outs = []
                for v in eqn.outvars:
                    t = new_token(v.aval)
                    env[id(v)] = t
                    outs.append(t)
                steps.append((eqn.primitive.name,
                              tuple(t for t in ins if t is not None),
                              tuple(outs)))
        return [read(v) for v in j.outvars]

    in_ts = [new_token(v.aval) for v in jaxpr.invars]
    const_ts = [new_token(v.aval) for v in jaxpr.constvars]
    out_ts = walk(jaxpr, in_ts, const_ts)
    boundary_in = set(in_ts) | set(const_ts)
    boundary_out = set(t for t in out_ts if t is not None)
    return steps, token_bytes, token_itemsize, boundary_in, boundary_out


def estimate_region_bytes(closed, widen_threshold=1 << 20):
    """Segment one captured jaxpr into XLA-fusion regions and charge each
    region its external HBM bytes.  Returns regions sorted by external
    bytes, descending:

        [{"eqns": int, "external_bytes": int, "input_bytes": int,
          "output_bytes": int, "prims": {name: count}}, ...]

    `widen_threshold`: widening converts producing at least this many
    bytes are treated as fusion roots (the audit's empirical
    f32-materialization boundary); smaller ones fuse like elementwise.
    """
    steps, token_bytes, token_itemsize, boundary_in, boundary_out = \
        _flatten_steps(closed)

    producer = {}
    consumers = {}
    for i, (_, ins, outs) in enumerate(steps):
        for t in outs:
            producer[t] = i
        for t in ins:
            consumers.setdefault(t, []).append(i)

    def kind_of(i):
        prim, ins, outs = steps[i]
        if prim in _ANCHOR_PRIMS:
            return "anchor"
        if prim in _REDUCE_PRIMS or prim.startswith("reduce_"):
            return "root"
        if prim == "convert_element_type" and ins and outs:
            if (token_itemsize[outs[0]] > token_itemsize[ins[0]]
                    and token_bytes[outs[0]] >= widen_threshold):
                return "root"
        return "fuse"

    kinds = [kind_of(i) for i in range(len(steps))]

    # root generation per value: consumers of a root output live one
    # generation later, so they can never merge back across the boundary
    gen = {t: 0 for t in boundary_in}
    step_gen = [0] * len(steps)
    for i, (_, ins, outs) in enumerate(steps):
        g = max((gen.get(t, 0) for t in ins), default=0)
        step_gen[i] = g
        out_g = g + 1 if kinds[i] == "root" else g
        for t in outs:
            gen[t] = out_g

    parent = list(range(len(steps)))

    def find(i):
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    def union(a, b):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb

    for i, (_, ins, _) in enumerate(steps):
        if kinds[i] == "anchor":
            continue
        for t in ins:
            j = producer.get(t)
            if j is None or kinds[j] == "anchor":
                continue
            # merge with same-generation producers only: a root merges
            # upstream (its inputs share its generation), while steps
            # downstream of a root output carry a later generation and
            # stay in their own region
            if step_gen[j] == step_gen[i] and kinds[j] != "root":
                union(i, j)
            elif kinds[j] == "root" and kinds[i] == "root" \
                    and step_gen[j] == step_gen[i]:
                union(i, j)

    regions = {}
    for i in range(len(steps)):
        if kinds[i] == "anchor":
            continue
        regions.setdefault(find(i), []).append(i)

    out = []
    for members in regions.values():
        mset = set(members)
        in_bytes = out_bytes = 0
        seen_in, seen_out = set(), set()
        prims = {}
        for i in members:
            prim, ins, outs = steps[i]
            prims[prim] = prims.get(prim, 0) + 1
            for t in ins:
                if t in seen_in:
                    continue
                j = producer.get(t)
                if j is None or j not in mset:
                    seen_in.add(t)
                    in_bytes += token_bytes[t]
            for t in outs:
                if t in seen_out:
                    continue
                used_outside = t in boundary_out or any(
                    c not in mset for c in consumers.get(t, ()))
                if used_outside:
                    seen_out.add(t)
                    out_bytes += token_bytes[t]
        out.append({
            "eqns": len(members),
            "external_bytes": in_bytes + out_bytes,
            "input_bytes": in_bytes,
            "output_bytes": out_bytes,
            "prims": dict(sorted(prims.items(), key=lambda kv: -kv[1])),
        })
    out.sort(key=lambda r: -r["external_bytes"])
    return out
