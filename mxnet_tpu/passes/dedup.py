"""Cross-CachedOp dedup: structurally identical captured programs share
ONE compiled executable.

Multi-head models and serving `ModelRegistry` replicas trace the same
graph once per block today; XLA compiles each copy.  With
``MXTPU_GRAPH_DEDUP=1`` every block-seam build canonicalizes its
(pass-rewritten) jaxpr — de Bruijn variable numbering, shapes/dtypes,
the equation graph, recursively through nested jaxprs — and looks the
key up in a process-wide executable cache.  Constants enter the shared
executable as runtime ARGUMENTS, so two blocks whose programs differ
only in weight/const values still share.  A hit skips the trace bump
(the `jit_trace_total` zero-retrace proof) and counts in
``graph_dedup_hits_total``.

Programs that cannot be canonicalized safely (effects, huge embedded
constants, identity-hashed callables in eqn params) simply do not
share — correctness first; the build falls back to a private
executable.
"""
from __future__ import annotations

import threading

import numpy as np

import jax
from jax.extend import core as jcore

from ..telemetry import instruments as _telemetry
from . import manager as _manager

__all__ = [
    "DedupExecutable",
    "executable_cache_info",
    "reset_executable_cache",
    "structural_key",
]

_CACHE_LOCK = threading.Lock()
_EXEC_CACHE = {}
_STATS = {"hits": 0, "misses": 0, "unhashable": 0}

# Embedded constants larger than this make the key unhashable (and the
# program un-shared) rather than hashing megabytes of weights per build.
_MAX_CONST_BYTES = 1 << 20


class _Unhashable(Exception):
    pass


def _aval_key(aval):
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "?")),
            bool(getattr(aval, "weak_type", False)))


def _canon(obj):
    """Canonicalize one eqn param (or nested const) into a hashable,
    value-comparable token."""
    if obj is None or isinstance(obj, (bool, int, float, complex, str,
                                       bytes)):
        return obj
    if isinstance(obj, jcore.Jaxpr):
        return ("jaxpr", _jaxpr_key(obj))
    if hasattr(obj, "jaxpr") and hasattr(obj, "consts"):  # ClosedJaxpr
        # nested consts are BAKED into the shared program, so their
        # values (not just avals) must participate in the key
        return ("closed", tuple(_canon(c) for c in obj.consts),
                _jaxpr_key(obj.jaxpr))
    if isinstance(obj, np.dtype):
        return ("dtype", str(obj))
    if hasattr(obj, "__array__") and hasattr(obj, "dtype") \
            and hasattr(obj, "shape"):
        arr = np.asarray(obj)
        if arr.nbytes > _MAX_CONST_BYTES:
            raise _Unhashable
        return ("nd", arr.shape, str(arr.dtype), arr.tobytes())
    if isinstance(obj, (tuple, list)):
        return ("seq", tuple(_canon(x) for x in obj))
    if isinstance(obj, dict):
        return ("map", tuple((str(k), _canon(v)) for k, v in
                             sorted(obj.items(), key=lambda kv: str(kv[0]))))
    if isinstance(obj, (set, frozenset)):
        return ("set", tuple(sorted(repr(x) for x in obj)))
    try:
        hash(obj)
    except TypeError:
        raise _Unhashable from None
    # identity-hashed objects (callables, thunks) are still CORRECT key
    # components — equal only to themselves — they just never match
    # across blocks, so such programs don't dedup
    return ("obj", type(obj).__module__, type(obj).__qualname__, obj)


# custom-derivative calls carry their rule callables/thunks as params.
# The raw objects hash by identity (unique per trace), so keying on them
# would poison every program containing a custom op — but they CANNOT
# simply be dropped either: the rules decide what jax.vjp through the
# shared executable computes, and two blocks with identical primal
# structure but different custom gradients (make_loss's constant-grad
# bwd vs stop_gradient) must not share one executable.  Each rule param
# is therefore reduced to a STABLE, semantics-bearing token: jaxpr
# thunks are forced (all-zeros symbolic-zero pattern — deterministic,
# trace-time-only cost) and keyed by the traced rule jaxpr; wrapped
# rule callables are keyed by the identity of their underlying user
# function, which IS shared across traces of the same library op.  A
# rule that can't be tokenized makes the program unhashable, so it
# falls back to a private executable — correctness first.
_RULE_JAXPR_THUNKS = frozenset(("jvp_jaxpr_fun", "fwd_jaxpr_thunk"))
_RULE_FUN_PARAMS = frozenset(("fwd", "bwd", "jvp"))
_RULE_DERIVED_PARAMS = frozenset(("out_trees",))  # fixed by the fwd jaxpr
_CUSTOM_CALL_PRIMS = frozenset(("custom_jvp_call", "custom_vjp_call"))


def _rule_fun_token(obj):
    """Stable token for a wrapped rule callable: the underlying user
    function (``WrappedFun.f``), equal-by-identity across traces of the
    same op."""
    f = getattr(obj, "f", None) or (obj if callable(obj) else None)
    if f is None:
        raise _Unhashable
    return ("rulefn", f)


# forcing is top-level only: a rule jaxpr often contains the op itself
# (jax.nn.relu's jvp recomputes relu), so forcing nested thunks would
# recurse forever.  Inside a forced rule, nested custom calls are keyed
# by their primal jaxpr + stable fun tokens, which first-order
# differentiation through the shared executable never looks past.
_RULE_DEPTH = threading.local()


def _rule_jaxpr_token(eqn, thunk):
    """Force a rule-jaxpr thunk with the no-symbolic-zeros pattern and
    key the traced rule itself."""
    if getattr(_RULE_DEPTH, "d", 0):
        return ("rulejaxpr", "nested")
    n = len(eqn.invars) - int(eqn.params.get("num_consts") or 0)
    _RULE_DEPTH.d = 1
    try:
        forced = thunk.call_wrapped(*([False] * n))
        return ("rulejaxpr", _canon(forced))
    except _Unhashable:
        raise
    except Exception:
        raise _Unhashable from None
    finally:
        _RULE_DEPTH.d = 0


def _pallas_key(params):
    """Structural token for one ``pallas_call``: the traced kernel body
    plus the launch geometry that selects a Mosaic program.  Anything we
    can't reduce to structure raises ``_Unhashable`` — the program then
    takes a private executable, never a wrong shared one.

    Kernel bodies mutate their refs, so they carry jax state effects —
    internal to the pallas_call, invisible to the surrounding program.
    They are canonicalized WITH their effect structure (two bodies match
    only if their read/write effects match positionally) instead of
    tripping the top-level no-effects rule."""
    params = dict(params)
    prev = _EFFECT_TOLERANT[0]
    _EFFECT_TOLERANT[0] = True
    try:
        kernel = ("kernel", _canon(params.pop("jaxpr")))
    finally:
        _EFFECT_TOLERANT[0] = prev
    gm = params.pop("grid_mapping", None)
    geo = ()
    if gm is not None:
        blocks = []
        for bm in getattr(gm, "block_mappings", ()):
            blocks.append((
                tuple(getattr(bm, "block_shape", ())),
                _canon(getattr(bm, "index_map_jaxpr", None)),
            ))
        geo = (tuple(getattr(gm, "grid", ())), tuple(blocks))
    rest = {}
    for k, v in params.items():
        try:
            rest[k] = _canon(v)
        except _Unhashable:
            # compiler params / cost estimates that resist tokenizing
            # are keyed by repr when stable; an address-bearing repr is
            # identity, not structure — poison the key instead
            r = repr(v)
            if "0x" in r:
                raise
            rest[k] = ("repr", r)
    return ("pallas", kernel, geo, _canon(rest))


def _eqn_params_key(eqn):
    params = dict(eqn.params)
    if eqn.primitive.name in _CUSTOM_CALL_PRIMS:
        rules = []
        for k in sorted(params):
            if k in _RULE_DERIVED_PARAMS:
                params.pop(k)
            elif k in _RULE_JAXPR_THUNKS:
                rules.append((k, _rule_jaxpr_token(eqn, params.pop(k))))
            elif k in _RULE_FUN_PARAMS:
                rules.append((k, _rule_fun_token(params.pop(k))))
        return ("custom", _canon(params), tuple(rules))
    if eqn.primitive.name == "pallas_call":
        # a Pallas kernel IS a structural feature: two programs share an
        # executable only when kernel body + grid + block maps agree
        try:
            return _pallas_key(params)
        except _Unhashable:
            raise
        except Exception:
            raise _Unhashable from None
    return _canon(params)


# canonicalizing a Pallas kernel body (see _pallas_key): its internal
# ref state effects become part of the key instead of poisoning it
_EFFECT_TOLERANT = [False]


def _effects_key(effects):
    toks = []
    for e in effects:
        r = repr(e)
        if "0x" in r:        # address-bearing repr: identity, not structure
            raise _Unhashable
        toks.append(r)
    return tuple(sorted(toks))


def _jaxpr_key(jaxpr):
    effects = getattr(jaxpr, "effects", None)
    eff_tok = ()
    if effects:
        if not _EFFECT_TOLERANT[0]:
            raise _Unhashable  # effectful programs never share executables
        eff_tok = _effects_key(effects)
    ids = {}

    def vid(v):
        token = ids.get(id(v))
        if token is None:
            token = ids[id(v)] = len(ids)
        return token

    def atom(v):
        if isinstance(v, jcore.Literal):
            return ("lit", _canon(v.val))
        return ("var", vid(v), _aval_key(v.aval))

    parts = [
        ("effects", eff_tok),
        ("const", tuple((vid(v), _aval_key(v.aval))
                        for v in jaxpr.constvars)),
        ("in", tuple((vid(v), _aval_key(v.aval)) for v in jaxpr.invars)),
    ]
    for eqn in jaxpr.eqns:
        parts.append((eqn.primitive.name,
                      tuple(atom(v) for v in eqn.invars),
                      tuple((vid(v), _aval_key(v.aval))
                            for v in eqn.outvars),
                      _eqn_params_key(eqn)))
    parts.append(("out", tuple(atom(v) for v in jaxpr.outvars)))
    return tuple(parts)


def structural_key(closed):
    """Canonical key of a ClosedJaxpr modulo var names and TOP-LEVEL
    const values (consts become runtime args of the shared executable,
    so only their avals matter).  None ⇒ not safely shareable."""
    try:
        return ("prog",
                tuple(_aval_key(jax.api_util.shaped_abstractify(c))
                      for c in closed.consts),
                _jaxpr_key(closed.jaxpr))
    except _Unhashable:
        return None


class _SharedExec:
    """One compiled executable serving every structurally identical
    program: jit of ``run(consts, *flat)`` over the FIRST matching
    jaxpr (all matches are structurally equal, so evaluating that one
    with each caller's consts/args is exact)."""

    __slots__ = ("jitted",)

    def __init__(self, closed):
        jaxpr = closed.jaxpr

        def run_shared(consts, *flat):
            return jax.core.eval_jaxpr(jaxpr, consts, *flat)

        self.jitted = jax.jit(run_shared)


class _Entry:
    __slots__ = ("shared", "consts", "out_tree", "hit")

    def __init__(self, shared, consts, out_tree, hit):
        self.shared = shared
        self.consts = consts
        self.out_tree = out_tree
        self.hit = hit


class DedupExecutable:
    """The block-seam executable under MXTPU_GRAPH_DEDUP=1: callable
    like a jitted function (with ``.lower()`` for compile
    introspection), backed by the process-wide shared-executable
    cache."""

    def __init__(self, fn, passes, ctx):
        self._fn = fn
        self._passes = passes
        self._ctx = ctx
        self._entries = {}
        self._lock = threading.Lock()

    def _entry(self, args):
        flat, sig = _manager.signature(args)
        entry = self._entries.get(sig)
        if entry is None:
            with self._lock:
                entry = self._entries.get(sig)
                if entry is None:
                    entry = self._build(args)
                    self._entries[sig] = entry
        return entry, flat

    def _build(self, args):
        ctx = self._ctx
        closed, out_tree = _manager.trace_closed(self._fn, args)
        closed = _manager.run_passes(closed, self._passes, ctx)
        key = structural_key(closed)
        hit = False
        if key is None:
            with _CACHE_LOCK:
                _STATS["unhashable"] += 1
            shared = _SharedExec(closed)  # private, unshared
        else:
            with _CACHE_LOCK:
                shared = _EXEC_CACHE.get(key)
                hit = shared is not None
                if not hit:
                    shared = _EXEC_CACHE[key] = _SharedExec(closed)
                _STATS["hits" if hit else "misses"] += 1
        if hit:
            _telemetry.record_dedup_hit(ctx.label)
        else:
            # one real build = one trace bump, exactly like a direct jit
            ctx.fire_on_build()
        return _Entry(shared, tuple(closed.consts), out_tree, hit)

    def __call__(self, *args):
        entry, flat = self._entry(args)
        outs = entry.shared.jitted(list(entry.consts), *flat)
        return jax.tree_util.tree_unflatten(entry.out_tree, list(outs))

    def lower(self, *args):
        entry, flat = self._entry(args)
        return entry.shared.jitted.lower(list(entry.consts), *flat)


def executable_cache_info():
    """{entries, hits, misses, unhashable} of the process-wide shared
    executable cache (tools/diagnose.py --passes)."""
    with _CACHE_LOCK:
        return {"entries": len(_EXEC_CACHE), **_STATS}


def reset_executable_cache():
    with _CACHE_LOCK:
        _EXEC_CACHE.clear()
        for k in _STATS:
            _STATS[k] = 0
