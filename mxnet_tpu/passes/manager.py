"""Pass manager: the NNVM-style seam between trace and compile.

The reference stack runs graph passes (AMP's low_precision_pass, memory
planning, fusion) on the NNVM graph a CachedOp captured, *before*
handing it to the executor.  Here the captured graph is a jaxpr and the
executor is XLA, so the seam is the point where the framework would
call ``jax.jit`` on a captured block body.  Every such call site —
`HybridBlock._build_jit`, the subgraph variant, `export()`, symbol
lowering, and the whole-step train program — routes through
:func:`apply` instead, which traces the body once per input signature,
runs the registered passes jaxpr → jaxpr, and compiles the REWRITTEN
program.  docs/passes.md is the user-facing tour.

With no passes resolved, :func:`apply` returns a plain
``jax.jit(fn)`` — bitwise-identical to the pre-pipeline framework, and
what ``MXTPU_PASSES=0`` forces unconditionally.
"""
from __future__ import annotations

import threading
import time

import jax
from jax.api_util import shaped_abstractify

from .. import env as _env
from ..telemetry import instruments as _telemetry
from . import _state

__all__ = [
    "GraphPass",
    "PassContext",
    "PassManager",
    "apply",
    "apply_pipeline",
    "block_context",
    "pipelined_callable",
    "pipeline_enabled",
    "register_named_pass",
    "resolve_passes",
    "retrace_flat",
    "run_passes",
    "trace_closed",
    "wrap_forward",
]

# Seam kinds a pass can opt into (PassContext.kind):
#   block          a CachedOp variant (HybridBlock._build_jit / subgraph)
#   export         the inference function jax_export serializes
#   symbol         SymbolBlock's lowered symbolic graph
#   whole_step     the outer one-dispatch train program (fwd+bwd+update)
#   whole_step_fwd the forward body embedded inside the whole-step
#                  program (where AMP acts; the outer program also
#                  holds optimizer state, which passes must not touch)
KINDS = ("block", "export", "symbol", "whole_step", "whole_step_fwd")


class PassContext:
    """Everything a pass may consult about the seam it is rewriting."""

    __slots__ = ("block", "label", "variant", "kind", "training",
                 "donate_argnums", "on_build", "notes", "plan",
                 "in_shardings", "out_shardings")

    def __init__(self, block=None, label="", variant="", kind="block",
                 training=False, donate_argnums=(), on_build=None,
                 plan=None, in_shardings=None, out_shardings=None):
        self.block = block
        self.label = label or (type(block).__name__ if block is not None
                               else "?")
        self.variant = variant
        self.kind = kind
        self.training = bool(training)
        self.donate_argnums = tuple(donate_argnums or ())
        # Fired once per built pipeline entry (new input signature), in
        # place of the side effects the suppressed trace would have had
        # (the block's jit_trace_total bump).
        self.on_build = on_build
        self.notes = {}
        # The ShardingPlan for this seam, or None.  Plan-carrying
        # contexts get a ShardingPass injected in resolve_passes; a
        # None plan (mesh=None) never does, so that path compiles the
        # same program main compiles.  Deliberately per-context, not
        # process-global: two trainers with different meshes coexist.
        self.plan = plan
        # Optional jit placement constraints forwarded verbatim to
        # jax.jit by apply()/apply_pipeline().  None means "let jax
        # infer from operands" — the default everywhere today; the
        # whole-step path places operands with device_put instead
        # (host arrays in its arg list make pytree-prefix shardings
        # fragile), so these are for block/export seams and tests.
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings

    def fire_on_build(self):
        if self.on_build is not None:
            self.on_build()

    def __repr__(self):
        return (f"PassContext({self.label}/{self.variant or '?'} "
                f"kind={self.kind} training={self.training})")


class GraphPass:
    """Base class: a jaxpr → jaxpr rewrite.

    Subclasses set ``name`` (unique within a pipeline), ``priority``
    (lower runs earlier; ties break by name, so ordering is
    deterministic regardless of registration order) and ``kinds`` (the
    seams the pass participates in), and implement :meth:`run`.
    """

    name = "?"
    priority = 50
    kinds = ("block",)

    def applies(self, ctx):
        return ctx.kind in self.kinds

    def run(self, closed_jaxpr, ctx):
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}(name={self.name!r}, priority={self.priority})"


class PassManager:
    """Ordered, name-deduped pass registry — one per HybridBlock
    (``block.pass_pipeline()``), plus free-standing instances in tests.
    Registering a pass with an existing name replaces it."""

    def __init__(self, passes=()):
        self._lock = threading.Lock()
        self._passes = []
        for p in passes:
            self.register(p)

    def register(self, graph_pass):
        with self._lock:
            self._passes = [p for p in self._passes
                            if p.name != graph_pass.name]
            self._passes.append(graph_pass)
        return graph_pass

    def remove(self, name):
        with self._lock:
            before = len(self._passes)
            self._passes = [p for p in self._passes if p.name != name]
            return len(self._passes) != before

    def get(self, name):
        with self._lock:
            for p in self._passes:
                if p.name == name:
                    return p
        return None

    def passes(self):
        """Registered passes in execution order: (priority, name)."""
        with self._lock:
            return sorted(self._passes, key=lambda p: (p.priority, p.name))

    def __len__(self):
        with self._lock:
            return len(self._passes)

    def __iter__(self):
        return iter(self.passes())

    def __repr__(self):
        return f"PassManager({self.passes()!r})"


# MXTPU_PASSES can name passes by string ("amp,numerics"); factories
# register here (passes/__init__.py) so env config needs no imports.
_NAMED = {}


def register_named_pass(name, factory):
    _NAMED[name] = factory
    return factory


def pipeline_enabled():
    """False only under the kill switch (MXTPU_PASSES=0/off/false/no):
    every seam then compiles its captured program verbatim, including
    blocks with explicitly registered pipelines."""
    return str(_env.get("MXTPU_PASSES")).strip().lower() not in (
        "0", "off", "false", "no")


def resolve_passes(ctx):
    """The pipeline for one seam build: the block's registered passes
    and any passes force-added by name via MXTPU_PASSES — filtered by
    :meth:`GraphPass.applies` and sorted (priority, name)."""
    if not pipeline_enabled():
        return []
    passes = []
    pm = getattr(ctx.block, "_pass_manager", None) \
        if ctx.block is not None else None
    if pm is not None:
        passes.extend(pm.passes())
    spec = str(_env.get("MXTPU_PASSES")).strip()
    if spec.lower() not in ("", "auto", "1", "on", "true", "yes"):
        for name in spec.split(","):
            name = name.strip()
            if not name or any(p.name == name for p in passes):
                continue
            factory = _NAMED.get(name)
            if factory is None:
                raise ValueError(
                    f"MXTPU_PASSES names unknown pass {name!r}; "
                    f"registered: {sorted(_NAMED)}")
            passes.append(factory())
    # mode() is the ONE normalization of MXTPU_NUMERICS — TrainStep's
    # step-boundary poll reads the same function, so a value that
    # installs no pass here also triggers no polling there
    from ..observability import numerics as _numerics
    if _numerics.mode() != "off" \
            and not any(p.name == "numerics" for p in passes):
        passes.append(_numerics.NumericsPass())
    # sharding joins only when the context CARRIES a plan (mesh=None →
    # ctx.plan None → never injected, the kill-switch acceptance
    # contract) and MXTPU_SHARDING isn't off — the same mode() Trainer
    # used to resolve that plan in the first place
    if ctx.plan is not None:
        from ..sharding import mode as _sharding_mode
        if _sharding_mode() != "off" \
                and not any(p.name == "sharding" for p in passes):
            from ..sharding.shard_pass import ShardingPass
            passes.append(ShardingPass(ctx.plan))
    passes = [p for p in passes if p.applies(ctx)]
    passes.sort(key=lambda p: (p.priority, p.name))
    return passes


def trace_closed(fn, args):
    """``make_jaxpr`` with block trace-side-effects suppressed; returns
    (ClosedJaxpr, out_tree)."""
    with _state.suppress_trace_bumps():
        closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    _, out_tree = jax.tree_util.tree_flatten(out_shape)
    return closed, out_tree


def run_passes(closed, passes, ctx):
    for p in passes:
        t0 = time.perf_counter()
        closed = p.run(closed, ctx)
        _telemetry.record_pass(p.name, (time.perf_counter() - t0) * 1e3)
    return closed


def retrace_flat(fn_flat, closed):
    """Re-trace a flat-args callable at ``closed``'s input signature.
    The pass contract is jaxpr → jaxpr; interpreter-style rewrites
    (amp_rewrite) produce a callable and round-trip back to a
    ClosedJaxpr through this."""
    sds = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
           for v in closed.jaxpr.invars]
    return jax.make_jaxpr(lambda *xs: tuple(fn_flat(*xs)))(*sds)


def signature(args):
    """(flat leaves, hashable signature) of a pytree of arguments."""
    flat, in_tree = jax.tree_util.tree_flatten(args)
    return flat, (in_tree, tuple(shaped_abstractify(x) for x in flat))


def pipelined_callable(fn, passes, ctx):
    """``fn`` with the pipeline applied at trace time: one cached
    (rewritten ClosedJaxpr, out_tree) per input signature, evaluated
    inline via ``eval_jaxpr``.  Traceable — jit / vjp / export of the
    result see the REWRITTEN program, and re-traces at a known
    signature hit the cache instead of re-running the passes."""
    cache = {}
    lock = threading.Lock()

    def pipelined(*args):
        flat, sig = signature(args)
        entry = cache.get(sig)
        if entry is None:
            with lock:
                entry = cache.get(sig)
                if entry is None:
                    closed, out_tree = trace_closed(fn, args)
                    closed = run_passes(closed, passes, ctx)
                    entry = (closed, out_tree)
                    cache[sig] = entry
                    ctx.fire_on_build()
        closed, out_tree = entry
        outs = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
        return jax.tree_util.tree_unflatten(out_tree, list(outs))

    pipelined._pass_ctx = ctx
    pipelined._pass_list = passes
    return pipelined


def apply(fn, ctx):
    """THE seam: compile ``fn`` through the pass pipeline.

    No passes → plain ``jax.jit(fn)`` (bitwise main); otherwise →
    ``jax.jit`` of the pipelined traceable — a REAL jit object, so
    donation, ``.lower()`` (compile introspection) and ``jax_export``
    all work unchanged.
    """
    return apply_pipeline(fn, resolve_passes(ctx), ctx)


def _jit_shardings(ctx):
    """in/out_shardings kwargs for jax.jit — only the ones the context
    actually sets, so the default stays a vanilla jit call (bitwise
    main, and robust to jax versions where the kwarg default differs
    from passing None)."""
    kw = {}
    if ctx.in_shardings is not None:
        kw["in_shardings"] = ctx.in_shardings
    if ctx.out_shardings is not None:
        kw["out_shardings"] = ctx.out_shardings
    return kw


def apply_pipeline(fn, passes, ctx):
    """:func:`apply` with an explicit pass list, bypassing resolution —
    for one-off variant builders (amp.build_amp_variant) and tests.
    Ignores the MXTPU_PASSES kill switch: the caller asked for exactly
    these passes."""
    if not passes:
        return jax.jit(fn, donate_argnums=ctx.donate_argnums,
                       **_jit_shardings(ctx))
    return jax.jit(pipelined_callable(fn, passes, ctx),
                   donate_argnums=ctx.donate_argnums,
                   **_jit_shardings(ctx))


def wrap_forward(fn, ctx):
    """Pipeline for a forward body embedded in a larger program (the
    whole-step train program's loss forward): returns ``fn`` untouched
    when no passes apply, else the pipelined traceable — no jit; the
    enclosing program's trace swallows the rewritten jaxpr inline."""
    passes = resolve_passes(ctx)
    if not passes:
        return fn
    return pipelined_callable(fn, passes, ctx)


def block_context(block, training, kind="block", bump=True):
    """PassContext for a HybridBlock seam.  ``bump`` wires on_build to
    the block's jit_trace_total bump — pipeline builds count exactly
    like direct traces did."""
    on_build = None
    if bump and kind == "block":
        def on_build():
            block._bump_trace(training)
    return PassContext(
        block=block, label=type(block).__name__,
        variant="train" if training else "predict",
        kind=kind, training=training, on_build=on_build)
