"""Gluon Block / HybridBlock (reference: python/mxnet/gluon/block.py:202,1006).

Block: imperative container of Parameters and child Blocks; forward() runs
eagerly through the taped NDArray ops.

HybridBlock: hybridize() turns the block into the **jit boundary** — the
TPU-native CachedOp (reference: src/imperative/cached_op.cc). The first call
traces forward() into a jaxpr and compiles with jax.jit:

  * params enter the traced function as inputs (like CachedOp's data_indices),
  * a PRNG key input feeds dropout etc. via the trace key-provider
    (the FResourceRequest/kRandom analog),
  * stateful aux updates (BatchNorm running stats) are collected by a trace
    sink and returned as extra outputs, applied after each call — keeping the
    compiled function pure while preserving the reference's mutable-aux-input
    semantics,
  * autograd over the compiled op is ONE tape node via jax.vjp on the jitted
    function — the CachedOp::Backward analog, with XLA rematerialization
    available via mx.gluon.checkpoint (jax.checkpoint) instead of
    MXNET_BACKWARD_DO_MIRROR,
  * shape/dtype changes retrace automatically (SetForwardGraph parity);
    train/predict mode are separate compiled variants.
"""
from __future__ import annotations

import json
import re
import threading as _threading
import time

import jax
import jax.numpy as jnp
import numpy as _np

from .. import _random
from .. import autograd as ag
from ..diagnostics import introspect as _introspect
from ..diagnostics import spans as _spans
from ..passes import _state as _pass_state
from ..telemetry import instruments as _telemetry
from ..base import DeferredInitializationError
from ..device import Device, current_device
from ..ndarray.ndarray import NDArray
from .parameter import Constant, Parameter, cast_params

__all__ = ["Block", "HybridBlock", "SymbolBlock", "checkpoint_block",
           "current_state_sink"]


# ---------------------------------------------------------------------------
# trace-time state sink (BatchNorm running stats & friends)
# ---------------------------------------------------------------------------

class _StateSink:
    def __init__(self):
        self.params = []
        self.values = []

    def record(self, param, value_data):
        self.params.append(param)
        self.values.append(value_data)


_sink_stack = []


def current_state_sink():
    return _sink_stack[-1] if _sink_stack else None


class _push_sink:
    def __init__(self, sink):
        self._sink = sink

    def __enter__(self):
        _sink_stack.append(self._sink)
        return self._sink

    def __exit__(self, *exc):
        _sink_stack.pop()
        return False


def checkpoint_block(block, *args, save=()):
    """``block(*args)`` as one ``jax.checkpoint`` segment while a program
    is being traced: the backward pass recomputes the block from its
    inputs instead of keeping what it computed, except the values named
    in ``save`` (`jax.ad_checkpoint.checkpoint_name`), which are kept.  A
    model that is a stack of equal layers calls each layer through this
    to hold one layer's activations at a time.  State the block writes
    through the trace's sink (running statistics, an expert layer's
    counters) leaves the segment as outputs and is recorded outside it.
    Untraced, it is a plain call."""
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


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------

_hook_suppress = _threading.local()


def _hooks_suppressed():
    return getattr(_hook_suppress, "depth", 0) > 0


class _suppress_hooks:
    """Forward hooks stay silent during shape-inference dry passes (the
    deferred-init eager pass is plumbing, not a reportable forward)."""

    def __enter__(self):
        _hook_suppress.depth = getattr(_hook_suppress, "depth", 0) + 1

    def __exit__(self, *exc):
        _hook_suppress.depth -= 1


class HookHandle:
    """Detachable hook registration (reference: gluon/utils.py
    HookHandle — supports detach() and `with handle:`)."""

    def __init__(self, hooks_list, hook):
        self._hooks_list = hooks_list
        self._hook = hook

    def detach(self):
        if self._hook is not None and self._hook in self._hooks_list:
            self._hooks_list.remove(self._hook)
        self._hook = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.detach()
        return False


def _any_traced(args):
    """True when a block is being called inside a jit/vjp trace."""
    for a in args:
        if isinstance(getattr(a, "_data", None), jax.core.Tracer):
            return True
    return False


class Block:
    """Base container (reference: gluon/block.py:202)."""

    def __init__(self):
        object.__setattr__(self, "_children", {})
        object.__setattr__(self, "_reg_params", {})

    # -- attribute registration (reference: Block.__setattr__) -----------
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            stale = self._children.get(name) is not value
            self._children[name] = value
            object.__setattr__(value, "_scope_name",
                               f"{type(value).__name__}_{name}")
            if stale:
                # structure changed: any compiled variant is stale
                # (reference: test_gluon.py test_hybrid_stale_cache)
                self._clear_cached()
        elif isinstance(value, Parameter):
            self._reg_params[name] = value
        else:
            existing = self._children.pop(name, None)
            if existing is None:
                self._reg_params.pop(name, None)
            elif existing is not value:
                self._clear_cached()
        object.__setattr__(self, name, value)

    def register_child(self, block, name=None):
        name = name or str(len(self._children))
        self._children[name] = block
        object.__setattr__(self, name, block)
        object.__setattr__(block, "_scope_name",
                           f"{type(block).__name__}_{name}")
        self._clear_cached()  # adding a child invalidates compiled variants
        return block

    def register_parameter(self, name, param):
        self._reg_params[name] = param
        object.__setattr__(self, name, param)
        return param

    # -- parameter collection ---------------------------------------------
    def _collect_params_with_prefix(self, prefix=""):
        out = {}
        for name, p in self._reg_params.items():
            out[prefix + name] = p
        for cname, child in self._children.items():
            out.update(child._collect_params_with_prefix(
                prefix + cname + "."))
        return out

    def collect_params(self, select=None):
        """Dict of structured-name -> Parameter (reference: collect_params).

        `select` is a regex over names ('.*weight', 'dense0_bias|...')."""
        params = self._collect_params_with_prefix()
        if select is None:
            return params
        pat = re.compile(select)
        return {k: v for k, v in params.items() if pat.match(k)}

    @property
    def params(self):
        return self._reg_params

    def initialize(self, init=None, device=None, verbose=False,
                   force_reinit=False, ctx=None):  # noqa: ARG002
        """Initialize all parameters (reference: Block.initialize)."""
        device = device if device is not None else ctx
        # one span for the whole tree (collect_params reaches every leaf)
        with _spans.span("block.initialize", cat="compile"):
            for name, p in self.collect_params().items():
                p._structured_name = name  # full path for Load/Mixed routing
                p.initialize(init=None, device=device,
                             default_init=init or _default_init(),
                             force_reinit=force_reinit)
        self._clear_cached()
        return self

    def _clear_cached(self):
        for child in self._children.values():
            child._clear_cached()

    def share_parameters(self, shared):
        """Replace this block's Parameters with the ones in `shared`
        (reference: Block.share_parameters, gluon/block.py — keys are
        structured names as produced by collect_params()). Unmatched
        names keep their own parameters; matched ones become the SAME
        Parameter object, so data and gradients are shared."""
        if shared is None:
            return self
        if not isinstance(shared, dict):
            raise ValueError(
                "share_parameters expects the dict collect_params() "
                f"returns, got {type(shared)}")

        def walk(block, prefix):
            for name in list(block._reg_params):
                full = prefix + name
                if full in shared:
                    block._reg_params[name] = shared[full]
                    object.__setattr__(block, name, shared[full])
            for cname, child in block._children.items():
                walk(child, prefix + cname + ".")

        walk(self, "")
        self._clear_cached()
        return self

    # -- forward ----------------------------------------------------------
    # -- hooks (reference: Block.register_forward_hook / _pre_hook,
    #    gluon/block.py + utils.HookHandle) --------------------------------
    def register_forward_hook(self, hook):
        """`hook(block, inputs, outputs)` after every forward; returns a
        detachable handle."""
        if not hasattr(self, "_fwd_hooks") or \
                not isinstance(self._fwd_hooks, list):
            object.__setattr__(self, "_fwd_hooks", list(
                getattr(self, "_fwd_hooks", ())))
        self._fwd_hooks.append(hook)
        return HookHandle(self._fwd_hooks, hook)

    def register_forward_pre_hook(self, hook):
        """`hook(block, inputs)` before every forward; returns a
        detachable handle."""
        if not hasattr(self, "_fwd_pre_hooks"):
            object.__setattr__(self, "_fwd_pre_hooks", [])
        self._fwd_pre_hooks.append(hook)
        return HookHandle(self._fwd_pre_hooks, hook)

    def __call__(self, *args, **kwargs):
        self._fire_fwd_pre_hooks(args)
        if _any_traced(args):
            return self._forward_scoped(args, kwargs)
        out = self.forward(*args, **kwargs)
        self._fire_fwd_hooks(args, out)
        return out

    def _forward_scoped(self, args, kwargs):
        """``forward`` while a program is being traced, under this block's
        name: ``<class>_<name its parent registered it under>``, e.g.
        ``BottleneckV1_3/BatchNorm_bn2`` in every HLO instruction's
        ``op_name``.  Stable across processes; metadata only, the traced
        program is the same.  Hooks never fire on tracers."""
        scope = getattr(self, "_scope_name", None) or type(self).__name__
        with jax.named_scope(scope):
            return self.forward(*args, **kwargs)

    def _fire_fwd_pre_hooks(self, args):
        pre = getattr(self, "_fwd_pre_hooks", ())
        if not pre or _hooks_suppressed():
            return
        # same tracer guard as _fire_fwd_hooks: hooks observe executed
        # values only — firing during a jit trace would crash value-
        # reading hooks and fire once per compile instead of per call
        if _any_traced(args):
            return
        for hook in pre:
            hook(self, args)

    def _fire_fwd_hooks(self, args, out):
        hooks = getattr(self, "_fwd_hooks", ())
        if not hooks or _hooks_suppressed():
            return
        # never hand tracer-backed values to monitor callbacks: under jit
        # tracing a value-reading hook would crash (and fire only once at
        # trace time) — the reference's op hooks likewise observe only
        # executed values, not graph construction
        vals = list(args) + (list(out) if isinstance(out, (list, tuple))
                             else [out])
        for v in vals:
            data = getattr(v, "_data", None)
            if data is not None and isinstance(data, jax.core.Tracer):
                return
        for hook in hooks:
            hook(self, args, out)

    def forward(self, *args):
        raise NotImplementedError

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def cast(self, dtype):
        cast_params(self.collect_params().values(), dtype)
        self._clear_cached()
        return self

    def reset_ctx(self, ctx=None, device=None):
        for p in self.collect_params().values():
            p.reset_ctx(ctx=ctx, device=device)
        self._clear_cached()

    reset_device = reset_ctx

    def zero_grad(self):
        for p in self.collect_params().values():
            if p.grad_req != "null" and p._data_map is not None:
                p.zero_grad()

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def setattr(self, name, value):
        """Set an attribute on all parameters (reference: Block.setattr)."""
        for p in self.collect_params().values():
            setattr(p, name, value)

    # -- checkpoint --------------------------------------------------------
    def save_parameters(self, filename, deduplicate=False):  # noqa: ARG002
        """Save params as .npz keyed by structured names (reference:
        Block.save_parameters, gluon/block.py:340; format here is the
        cnpy/.npz path of src/serialization/cnpy.cc).

        ASYNC CONTRACT (deliberate divergence from the reference, which
        blocks on return): the write overlaps training on a native-engine
        IO thread. In-framework readers (load_parameters, nd.load) and
        mx.waitall() barrier correctly; an EXTERNAL consumer (shell cp, a
        second process, an upload hook) must call mx.waitall() first.
        `mx.nd.save` is synchronous-on-return like the reference if you
        need stat-after-save semantics. See docs/migration.md."""
        arrays = {}
        for name, p in self._collect_params_with_prefix().items():
            if p._data_map is None:
                continue
            arrays[name] = _np.asarray(p.data().asnumpy())
        # the serialize+write runs on a native-engine IO thread so training
        # continues while the checkpoint lands; loads (and waitall) barrier
        # on the path's engine var (_checkpoint_io; reference: engine-pushed
        # NDArray::Save)
        from .._checkpoint_io import async_save_npz

        async_save_npz(filename, arrays)

    def load_parameters(self, filename, device=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current", ctx=None):  # noqa: ARG002
        """Load params saved by save_parameters (reference: block.py:379)."""
        import os

        from .._checkpoint_io import wait_for_path

        wait_for_path(str(filename))  # barrier on any in-flight async save
        device = device if device is not None else ctx
        path = str(filename)
        if not os.path.exists(path) and os.path.exists(path + ".npz"):
            path = path + ".npz"
            wait_for_path(path)  # the save may have keyed the .npz name
        from .._dtype_codec import DTYPE_KEY, decode_entry, read_sidecar

        # restore bf16/f8 dtypes from the codec sidecar (npz alone loses
        # them to raw void records — a bf16-trained net must checkpoint).
        # Entries decode lazily: NpzFile decompresses per access, so a
        # partial load of a large checkpoint reads only what it needs.
        npz = _np.load(path, allow_pickle=False)
        sidecar = read_sidecar(npz)
        loaded = set(npz.files) - {DTYPE_KEY}
        params = self._collect_params_with_prefix()
        for name, p in params.items():
            if name not in loaded:
                if not allow_missing:
                    raise KeyError(
                        f"Parameter {name} missing in file {filename}; "
                        "set allow_missing=True to skip")
                continue
            arr = decode_entry(name, npz[name], sidecar)
            # dtype contract (reference: parameter.py:286-315 _load_init):
            # mismatch errors unless cast_dtype=True, which casts saved ->
            # current (dtype_source='current') or adopts the saved dtype
            # (dtype_source='saved')
            if cast_dtype and dtype_source not in ("current", "saved"):
                raise ValueError(
                    f"dtype_source must be 'current' or 'saved', got "
                    f"{dtype_source!r}")
            if p.dtype is not None and _np.dtype(p.dtype) != arr.dtype:
                if not cast_dtype:
                    raise AssertionError(
                        f"Failed loading Parameter '{name}' from saved "
                        f"params: dtype incompatible expected {p.dtype} vs "
                        f"saved {arr.dtype}. Set cast_dtype=True to cast "
                        "the dtype of saved params.")
                if dtype_source == "current":
                    arr = arr.astype(p.dtype, copy=False)
                else:  # 'saved': retype data AND grad buffers together
                    p.cast(arr.dtype)
            if p._data_map is None and p._deferred is None:
                # never initialized: the saved value is its initialization
                p._defer_to_data(device or current_device())
            p.set_data(NDArray(jnp.asarray(arr, p.dtype)))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise KeyError(
                    f"file {filename} contains extra parameters {sorted(extra)}; "
                    "set ignore_extra=True to skip")
        self._clear_cached()

    # misc parity helpers
    def register_op_hook(self, callback, monitor_all=False):
        """Install a monitor callback on every descendant block's forward
        (reference: block.py:877 register_op_hook -> CachedOp::
        RegisterOpHook). callback(block_name, tensor_name, tensor) fires
        for each output (and each input when monitor_all=True).

        Granularity note: ops fuse inside the jit boundary on TPU, so the
        observable unit is the block forward — the analog of the
        reference hiding per-op detail under bulked exec
        (docs perf.md:293-296); hybridized blocks report at the jit
        boundary. Use MXNET_EXEC_BULK_EXEC-style de-optimization by
        calling .hybridize(active=False) for per-block detail."""
        def make_hook(prefix):
            def hook(block, inputs, output):
                name = prefix or type(block).__name__
                if monitor_all:
                    for i, a in enumerate(inputs):
                        callback(name, f"{name}_input{i}", a)
                outs = (output if isinstance(output, (list, tuple))
                        else [output])
                for i, o in enumerate(outs):
                    callback(name, f"{name}_output{i}", o)
            return hook

        def walk(block, prefix):
            block.register_forward_hook(make_hook(prefix))
            for cname, child in block._children.items():
                walk(child, f"{prefix}.{cname}" if prefix else cname)

        walk(self, "")
        return self

    def summary(self, *inputs):
        """Print a per-layer summary (reference: Block.summary)."""
        rows = []

        def walk(block, prefix):
            n_params = sum(
                int(_np.prod(p.shape)) for p in block._reg_params.values()
                if p.shape is not None)
            rows.append((prefix or type(block).__name__,
                         type(block).__name__, n_params))
            for name, child in block._children.items():
                walk(child, f"{prefix}.{name}" if prefix else name)

        walk(self, "")
        total = sum(r[2] for r in rows)
        print(f"{'Layer':<40}{'Type':<24}{'Params':>12}")
        print("-" * 76)
        for name, typ, n in rows:
            print(f"{name:<40}{typ:<24}{n:>12}")
        print("-" * 76)
        print(f"Total params: {total}")
        return total


def _default_init():
    from .. import initializer

    return initializer.Uniform()


def _traced_forward(block, params, training, param_data, key, input_datas):
    """Shared trace body for the CachedOp jit and as_pure_function: run
    block.forward with traced param stand-ins, a folded-key RNG provider,
    and a state sink collecting aux writes. Returns (out_datas, sink)."""
    sink = _StateSink()
    counter = [0]

    def key_provider():
        counter[0] += 1
        return jax.random.fold_in(key, counter[0])

    wrapped = [NDArray(d) for d in input_datas]
    with ag.suspend_taping(), ag._scope(training=training), \
            _push_sink(sink), _random.key_provider(key_provider):
        for name, p in params:
            p._traced_data = NDArray(param_data[name])
        try:
            out = block.forward(*wrapped)
        finally:
            for _, p in params:
                p._traced_data = None
    out_datas = jax.tree_util.tree_map(
        lambda a: a._data if isinstance(a, NDArray) else a, out,
        is_leaf=lambda a: isinstance(a, NDArray))
    return out_datas, sink


# ---------------------------------------------------------------------------
# HybridBlock
# ---------------------------------------------------------------------------

# The ops allowed to trip the dynamic-graph fallback: every op whose
# OUTPUT shape is value-dependent snapshots its inputs eagerly on the
# host (contrib/ops.py), which raises a concretization error under jit
# tracing by design. A concretization error from anywhere else is a user
# tracing bug and must propagate (ADVICE.md block.py:581).
_DYNAMIC_OUTPUT_OPS = frozenset({
    "boolean_mask", "box_nms", "bipartite_matching", "multibox_target",
    "multibox_detection", "dynamic_reshape", "getnnz", "proposal",
})


def _dynamic_output_origin(exc):
    """Name of the known dynamic-output op the concretization error was
    raised under, walking its traceback; None when the error came from
    user control flow (or any frame outside the framework's op table)."""
    import os

    tb = exc.__traceback__
    while tb is not None:
        code = tb.tb_frame.f_code
        if code.co_name in _DYNAMIC_OUTPUT_OPS and \
                os.sep + "mxnet_tpu" + os.sep in code.co_filename:
            return code.co_name
        tb = tb.tb_next
    return None


class HybridBlock(Block):
    """Block that can compile its forward as one XLA program."""

    def __init__(self):
        super().__init__()
        object.__setattr__(self, "_active", False)
        object.__setattr__(self, "_jit_variants", {})
        object.__setattr__(self, "_cached_param_list", None)
        object.__setattr__(self, "_state_params", {})
        object.__setattr__(self, "_flags", {})
        # per-variant retrace counter: cached_fn bumps it once per jit
        # trace (= one XLA compile, including shape-signature misses
        # AFTER the variant was first built — which _jit_variants alone
        # can't see). serving.InferenceEngine.warmup() reads it to prove
        # every bucket is pre-compiled.
        object.__setattr__(self, "_trace_counts", {})
        # thread-safe CachedOp analog (reference:
        # src/imperative/cached_op_threadsafe.cc): one lock guards variant
        # build + aux-state swap so concurrent inference threads share the
        # compiled executable safely. Executing the jitted fn itself is
        # thread-safe (XLA executables are immutable).
        import threading as _threading

        object.__setattr__(self, "_cache_lock", _threading.RLock())

    def hybridize(self, active=True, backend=None, backend_opts=None,
                  **kwargs):  # noqa: ARG002
        """Enable compiled execution (reference: HybridBlock.hybridize;
        static_alloc/static_shape flags are accepted — XLA always runs
        static-shape, buffer reuse is PJRT's job)."""
        object.__setattr__(self, "_active", active)
        self._flags.update(kwargs)
        self._jit_variants.clear()
        # children stay eager; this block is the jit boundary — but mark
        # nested HybridBlocks inactive to avoid double tracing.
        for child in self._children.values():
            child.hybridize(False)

    def optimize_for(self, x, *args, backend=None, backend_opts=None,
                     **kwargs):  # noqa: ARG002
        """Compile with an optional subgraph backend (reference:
        HybridBlock.optimize_for, block.py:1281 → build_subgraph.cc).

        With backend=None this is hybridize+run. With a registered
        backend name (mxnet_tpu.subgraph.register_backend), the traced
        jaxpr is partitioned: maximal regions matched by the backend are
        replaced by its substituted implementations, and the partitioned
        program becomes this block's compiled variant."""
        self.hybridize(True)
        if backend is None:
            return self(x, *args)
        # record the backend; the variant is (re)built from it on demand —
        # so cast()/load_parameters()/_clear_cached() cannot silently drop
        # the partitioned program (reference: HybridBlock remembers its
        # backend and re-partitions in _build_cache)
        object.__setattr__(self, "_variant_builder", ("subgraph", backend))
        object.__setattr__(self, "_subgraph_backend", backend)
        self._jit_variants.clear()
        return self(x, *args)

    def _clear_cached(self):
        jv = getattr(self, "_jit_variants", None)
        if jv is not None:  # may fire from __setattr__ mid-__init__
            jv.clear()
        super()._clear_cached()

    def __call__(self, *args, **kwargs):
        self._fire_fwd_pre_hooks(args)
        concrete_tensors = (
            not kwargs and bool(args)
            and all(isinstance(a, NDArray) for a in args)
            and not any(isinstance(a._data, jax.core.Tracer) for a in args))
        if concrete_tensors:
            # remember input signature for export() (reference: CachedOp
            # remembers bound shapes via SetForwardGraph)
            object.__setattr__(
                self, "_last_input_specs",
                [(tuple(a.shape), a.dtype) for a in args])
            if self._active and not getattr(self, "_dynamic_graph", False):
                try:
                    return self._call_cached(*args)
                except (jax.errors.TracerArrayConversionError,
                        jax.errors.ConcretizationTypeError) as e:
                    # Concretization during trace has two causes with
                    # opposite remedies. (1) A known dynamic-OUTPUT op
                    # (boolean_mask, box_nms selection — value-dependent
                    # shapes XLA cannot trace): the reference CachedOp
                    # flips to dynamic-shape execution (imperative
                    # per-op) for such graphs, and we do the same — run
                    # this block eagerly from now on, hybridize() a
                    # no-op for it. (2) A genuine tracing bug in user
                    # control flow (`if x > 0:` on a traced value):
                    # falling back would permanently mask the bug AND
                    # silently lose compiled performance (ADVICE.md
                    # block.py:581), so anything NOT raised from inside
                    # a known dynamic-output op re-raises.
                    op = _dynamic_output_origin(e)
                    if op is None:
                        raise
                    import warnings

                    _telemetry.record_fallback(type(self).__name__)
                    warnings.warn(
                        f"{type(self).__name__}.forward contains the "
                        f"dynamic-output op '{op}'; running imperatively "
                        "(reference CachedOp dynamic-shape mode). "
                        f"Original error: {type(e).__name__}: {e}",
                        stacklevel=2)
                    object.__setattr__(self, "_dynamic_graph", True)
        elif _any_traced(args):
            return self._forward_scoped(args, kwargs)
        out = self.forward(*args, **kwargs)
        self._fire_fwd_hooks(args, out)
        return out

    # -- deferred shape inference -----------------------------------------
    def infer_shape(self, *args):
        """Run a shape-only eager pass so deferred params materialize
        (reference: HybridBlock.infer_shape, block.py:1462)."""
        with ag.pause(), _suppress_hooks():
            self.forward(*args)

    # -- the CachedOp ------------------------------------------------------
    def _ensure_initialized(self, args):
        try:
            for p in self.collect_params().values():
                if p.grad_req or True:
                    p._check_initialized()
            return
        except DeferredInitializationError:
            # one eager pass completes deferred init (layers infer
            # shapes); monitor hooks stay silent — it is plumbing
            with ag.pause(), _suppress_hooks():
                self.forward(*args)

    def _make_cached_fn(self, training):
        """The traceable whole-block function (shared by the plain jit
        variant and the subgraph-partitioned variant)."""
        params = sorted(self.collect_params().items())
        object.__setattr__(self, "_cached_param_list", params)
        block = self

        def cached_fn(param_data, key, *input_datas):
            # host side effect: this body runs once per jit trace (new
            # shape/dtype signature -> one XLA compile), never on cache
            # hits — the retrace signal jit_trace_count() exposes.
            # Suppressed while the pass pipeline (or compile
            # introspection) re-traces for its own purposes: the
            # pipeline fires ctx.on_build once per built entry instead.
            if not _pass_state.suppressed():
                block._bump_trace(training)
            out_datas, sink = _traced_forward(
                block, params, training, param_data, key, input_datas)
            # trace-time side effect: remember which params get aux updates
            # (per train/predict variant — predict traces have no BN updates)
            block._state_params[training] = list(sink.params)
            return out_datas, tuple(sink.values)

        return cached_fn

    def _bump_trace(self, training):
        with self._cache_lock:
            self._trace_counts[training] = \
                self._trace_counts.get(training, 0) + 1
        _telemetry.record_trace(
            type(self).__name__, "train" if training else "predict")

    def jit_trace_count(self, training=False):
        """How many times the train/predict variant has been traced —
        each trace is one XLA compile (first build plus every
        shape/dtype-signature cache miss since). Monotonic across
        hybridize()/_clear_cached(); the serving warmup's zero-miss
        proof snapshots it before and after driving every bucket."""
        return self._trace_counts.get(bool(training), 0)

    def call_cached_graph(self, *args):
        """Thread-safe entry into the compiled predict-mode graph — the
        serving hot path (serving/engine.py, docs/serving.md).

        Forces predict mode and no taping regardless of the calling
        thread's autograd state, and never falls back to eager: a block
        that already dropped to dynamic-graph execution (or was never
        hybridized) cannot honor the bucketed-compile-cache contract, so
        this raises instead of silently serving uncompiled. Safe to call
        from many threads at once — variant build is serialized by the
        cache lock, and executing the jitted function is reentrant (XLA
        executables are immutable)."""
        if not self._active:
            raise RuntimeError(
                f"{type(self).__name__}.call_cached_graph requires "
                "hybridize() — the serving engine only runs compiled "
                "graphs")
        if getattr(self, "_dynamic_graph", False):
            raise RuntimeError(
                f"{type(self).__name__} fell back to dynamic-graph "
                "(imperative) execution; it cannot be served through "
                "the bucketed jit cache")
        with ag.pause():
            return self._call_cached(*args)

    def aot_introspect(self, variant, *args, label=None):
        """AOT-lower the predict-mode graph at ``args``' exact signature
        and record XLA's cost/memory analysis in the diagnostics compile
        registry under ``(label or class name, variant)``.

        serving.InferenceEngine.warmup() calls this once per batch
        bucket, so the registry proves which shapes are pre-compiled
        (and what each costs) — the per-bucket analog of the cache-miss
        capture in _call_cached. Reads the executable jit already holds
        for these arguments (diagnostics/introspect.py); gated by
        MXTPU_DIAG_COMPILE like every introspection. Returns
        the registry entry dict or None."""
        with ag.pause():
            if self._jit_variants.get(False) is None:
                self._call_cached(*args)  # builds the predict variant
            jitted = self._jit_variants.get(False)
            if jitted is None:
                return None
            pd = {n: p.data()._data for n, p in self._cached_param_list}
            key = _random.next_key()
            datas = [a._data for a in args]
            return _introspect.capture_compile(
                label or type(self).__name__, variant, jitted,
                (pd, key, *datas))

    def pass_pipeline(self):
        """This block's graph-pass pipeline (docs/passes.md): a
        passes.PassManager whose registered passes rewrite every
        compiled variant — block jit, export, symbol lowering.  Call
        ``hybridize(True)`` (or clear the jit cache) after changing the
        pipeline so already-built variants rebuild through it."""
        from .. import passes as _passes

        pm = getattr(self, "_pass_manager", None)
        if pm is None:
            pm = _passes.PassManager()
            object.__setattr__(self, "_pass_manager", pm)
        return pm

    def _build_jit(self, training):
        from .. import passes as _passes

        return _passes.apply(self._make_cached_fn(training),
                             _passes.block_context(self, training))

    def _build_variant(self, training, args):
        """Build the compiled variant honoring any recorded graph rewrite
        (subgraph backend / AMP graph pass)."""
        builder = getattr(self, "_variant_builder", None)
        if builder is None:
            return self._build_jit(training)
        kind, payload = builder
        cached_fn = self._make_cached_fn(training)
        pd = {n: p.data()._data for n, p in self._cached_param_list}
        key = _random.next_key()
        datas = [a._data for a in args]
        if kind == "subgraph":
            from .. import passes as _passes
            from .. import subgraph as _subgraph

            part, n_sub = _subgraph.partition_call(
                cached_fn, payload, pd, key, *datas)
            object.__setattr__(self, "_subgraph_count", n_sub)
            # bump=False: partition_call already traced cached_fn once
            # (bump fired there); the partitioned wrapper itself never
            # self-bumped under a plain jit either
            return _passes.apply(
                part, _passes.block_context(self, training, bump=False))
        if kind == "amp_graph":
            from ..amp.graph_pass import build_amp_variant

            fn, stats = build_amp_variant(cached_fn, payload, pd, key,
                                          datas)
            object.__setattr__(self, "_amp_stats", stats)
            return fn
        raise ValueError(f"unknown variant builder {kind!r}")

    def _call_cached(self, *args):
        training = bool(ag.is_training())
        compile_t0 = None  # set on cache miss: this call traces + compiles
        jitted = self._jit_variants.get(training)
        if jitted is None:
            # one thread completes deferred init + builds; others reuse
            # (reference: cached_op_threadsafe.cc serializes graph setup)
            with self._cache_lock:
                jitted = self._jit_variants.get(training)
                if jitted is None:
                    self._ensure_initialized(args)
                    compile_t0 = time.perf_counter()
                    with _spans.span(type(self).__name__, cat="compile"):
                        jitted = self._build_variant(training, args)
                    self._jit_variants[training] = jitted
        else:
            self._ensure_initialized(args)
        params = self._cached_param_list
        names = [n for n, _ in params]
        param_nds = [p.data() for _, p in params]
        pd = {n: nd._data for n, nd in zip(names, param_nds)}
        key = _random.next_key()
        if params:
            # mesh-placed params (sharding.ShardingPlan.apply) commit the
            # computation to the mesh's device set; the key is committed to
            # the default device, and jit refuses mixed assignments —
            # replicate it onto the same mesh.
            _shd = getattr(pd[names[0]], "sharding", None)
            _mesh = getattr(_shd, "mesh", None)
            if _mesh is not None and len(_shd.device_set) > 1:
                key = jax.device_put(
                    key,
                    jax.sharding.NamedSharding(
                        _mesh, jax.sharding.PartitionSpec()))
        arr_datas = [a._data for a in args]

        taping = ag.taping_active() and (
            any(p.grad_req != "null" for _, p in params)
            or any(a._requires_grad_entry for a in args)
        )

        with _spans.span(type(self).__name__, cat="fwd"):
            if taping:
                def fn(pd_, *xs):
                    out, state = jitted(pd_, key, *xs)
                    return out, state

                out_datas, vjp_fn, state_vals = jax.vjp(
                    fn, pd, *arr_datas, has_aux=True)
            else:
                out_datas, state_vals = jitted(pd, key, *arr_datas)

        if compile_t0 is not None:
            # the whole cache-miss call is the compile cost users feel:
            # trace + XLA compile + first dispatch (async — the device run
            # itself isn't awaited here)
            variant = "train" if training else "predict"
            compile_seconds = time.perf_counter() - compile_t0
            _telemetry.record_compile(
                type(self).__name__, variant, compile_seconds)
            # AOT-introspect what XLA built for this signature: flops,
            # bytes accessed, arg/out/temp sizes → the compile registry
            # (diagnostics.report / tools/diagnose.py), read off the
            # executable jit just built; MXTPU_DIAG_COMPILE=0 skips.
            _introspect.capture_compile(
                type(self).__name__, variant, jitted,
                (pd, key, *arr_datas), compile_seconds=compile_seconds)

        # apply aux state updates (BN running stats) — serialized so
        # concurrent threads cannot interleave half-written stats
        state_params = self._state_params.get(training) or ()
        if state_params:
            with self._cache_lock:
                for p, v in zip(state_params, state_vals):
                    target = p.data() if isinstance(p, Parameter) else p
                    target._data = v
                    target._version += 1

        flat_out, treedef = jax.tree_util.tree_flatten(out_datas)
        wrapped_flat = [NDArray(o) for o in flat_out]

        if taping:
            nd_inputs = param_nds + list(args)

            def node_vjp(out_ct):
                cts = out_ct if isinstance(out_ct, tuple) else (out_ct,)
                ct_tree = jax.tree_util.tree_unflatten(treedef, list(cts))
                all_cts = vjp_fn(ct_tree)
                pd_ct = all_cts[0]
                x_cts = all_cts[1:]
                flat_pd = [pd_ct[n] for n in names]
                return tuple(flat_pd) + tuple(x_cts)

            node = ag.TapeNode(
                node_vjp,
                nd_inputs,
                [a._tape_entry for a in nd_inputs],
                [(tuple(o.shape), o.dtype) for o in flat_out],
                multi_out=len(flat_out) > 1,
                name=f"CachedOp({type(self).__name__})",
            )
            for idx, w in enumerate(wrapped_flat):
                w._tape_entry = (node, idx)

        out = jax.tree_util.tree_unflatten(treedef, wrapped_flat)
        for hook in getattr(self, "_fwd_hooks", ()):
            hook(self, args, out)
        return out

    # -- pure functional view ---------------------------------------------
    def as_pure_function(self, training=False):
        """Return (fn, params) where fn(params, key, *inputs) ->
        (out, new_params) is a PURE jax function of the whole block.

        This is the TPU-native export of the CachedOp: the function is
        jit/pjit/shard_map-able, differentiable, and shardable; aux-state
        updates (BN running stats) come back in new_params instead of
        mutating. Used by __graft_entry__ and the sharded
        training paths.
        """
        params = sorted(self.collect_params().items())
        block = self

        def fn(param_data, key, *input_datas):
            out_datas, sink = _traced_forward(
                block, params, training, param_data, key, input_datas)
            name_of = {id(p): n for n, p in params}
            new_params = dict(param_data)
            for p, v in zip(sink.params, sink.values):
                new_params[name_of[id(p)]] = v
            return out_datas, new_params

        param_data = {n: p.data()._data for n, p in params}
        return fn, param_data

    def trainable_param_names(self):
        """Names of params with grad_req != 'null' (BN stats excluded)."""
        return [n for n, p in sorted(self.collect_params().items())
                if p.grad_req != "null"]

    # -- export ------------------------------------------------------------
    def export(self, path, epoch=0, remove_amp_cast=True):  # noqa: ARG002
        """Export for deployment (reference: HybridBlock.export →
        model-symbol.json + model-0000.params, block.py:1480).

        TPU-native artifact: params .npz + the inference program serialized
        as portable StableHLO via jax.export — the AOT-compiled-graph role
        model-symbol.json played. Round-trips through SymbolBlock.imports.
        Requires one prior call (to know input shapes)."""
        specs = getattr(self, "_last_input_specs", None)
        if specs is None:
            raise RuntimeError(
                "export needs input shapes: call the block once first")
        params_file = f"{path}-{epoch:04d}.params.npz"
        self.save_parameters(params_file)
        fn, param_data = self.as_pure_function(training=False)
        key = jax.random.PRNGKey(0)

        def infer_fn(pd, *xs):
            out, _ = fn(pd, key, *xs)
            return out

        from jax import export as jax_export

        from .. import passes as _passes

        # through the pipeline: a converted/remat'd block exports the
        # SAME program it runs (apply returns a real jax.jit, which
        # jax_export requires)
        jitted = _passes.apply(infer_fn, _passes.PassContext(
            block=self, label=type(self).__name__, variant="export",
            kind="export"))
        exp = jax_export.export(jitted)(
            {n: jax.ShapeDtypeStruct(a.shape, a.dtype)
             for n, a in param_data.items()},
            *[jax.ShapeDtypeStruct(s, d) for s, d in specs])
        hlo_file = f"{path}-{epoch:04d}.stablehlo.bin"
        with open(hlo_file, "wb") as f:
            f.write(exp.serialize())
        meta = {
            "format": "mxnet_tpu-stablehlo",
            "class": type(self).__name__,
            "params": params_file,
            "stablehlo": hlo_file,
            "inputs": [[list(s), str(_np.dtype(d))] for s, d in specs],
        }
        with open(f"{path}-symbol.json", "w") as f:
            json.dump(meta, f, indent=2)
        return f"{path}-symbol.json", params_file


class SymbolBlock(HybridBlock):
    """Run a graph artifact as a Block (reference: gluon/block.py:1654).

    Two artifact kinds:
      * an mx.symbol DAG (``SymbolBlock(outputs, inputs, params=...)`` or a
        saved symbol json) — evaluated through the symbol op table;
      * a StableHLO bundle from HybridBlock.export — rehydrated with
        jax.export.deserialize (inference only, like a deployed
        model-symbol.json was).
    """

    def __init__(self, outputs=None, inputs=None, params=None):
        super().__init__()
        object.__setattr__(self, "_exported", None)
        object.__setattr__(self, "_symbol", None)
        object.__setattr__(self, "_input_names", [])
        object.__setattr__(self, "_arg_params", {})
        if outputs is None:
            return  # imports() fills in
        from ..symbol.symbol import Symbol as Sym

        if isinstance(outputs, (list, tuple)):
            from ..symbol.symbol import Group

            outputs = Group(list(outputs))
        if not isinstance(outputs, Sym):
            raise TypeError("outputs must be a Symbol")
        if inputs is None:
            raise ValueError("SymbolBlock needs the input symbols")
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        object.__setattr__(self, "_symbol", outputs)
        object.__setattr__(
            self, "_input_names", [s.name for s in inputs])
        arg_names = [n for n in outputs.list_arguments()
                     if n not in self._input_names]
        params = params or {}
        for n in arg_names:
            p = Parameter(name=n, shape=None)
            if n in params:
                v = params[n]
                arr = v.data() if isinstance(v, Parameter) else v
                if isinstance(arr, NDArray):
                    arr = arr._data
                p.shape = tuple(arr.shape)
                p.initialize(device=current_device())
                p.set_data(NDArray(jnp.asarray(arr)))
            self._arg_params[n] = p
            self.register_parameter(n.replace(".", "_"), p)

    @staticmethod
    def imports(symbol_file, input_names=("data",), param_file=None,
                ctx=None, device=None, allow_missing=False):  # noqa: ARG004
        """Load an exported artifact (reference: SymbolBlock.imports)."""
        import os

        with open(symbol_file) as f:
            head = f.read()
        blk = SymbolBlock()
        if isinstance(input_names, str):
            input_names = [input_names]
        try:
            meta = json.loads(head)
        except json.JSONDecodeError:
            meta = None
        if meta and meta.get("format") == "mxnet_tpu-stablehlo":
            from jax import export as jax_export

            base = os.path.dirname(os.path.abspath(symbol_file))
            from .._checkpoint_io import wait_for_path

            def _resolve(p):
                # barrier BEFORE the existence probe — an in-flight async
                # save would otherwise redirect to the wrong path. Try the
                # path as given, its basename next to the symbol file, and
                # each one's .npz twin (a reference-era caller passes
                # "net-0000.params"; export writes "net-0000.params.npz").
                cands = [p, os.path.join(base, os.path.basename(p))]
                cands += [c + ".npz" for c in cands]
                for c in cands:
                    wait_for_path(c)
                    if os.path.exists(c):
                        return c
                return cands[0]

            with open(_resolve(meta["stablehlo"]), "rb") as f:
                exported = jax_export.deserialize(f.read())
            from .._dtype_codec import decode_npz

            loaded = decode_npz(_np.load(
                _resolve(param_file or meta["params"]),
                allow_pickle=False))
            object.__setattr__(blk, "_exported", exported)
            object.__setattr__(
                blk, "_arg_params",
                {n: jnp.asarray(a) for n, a in loaded.items()})
            object.__setattr__(blk, "_input_names", list(input_names))
            return blk
        if meta and meta.get("format") == "mxnet_tpu-symbol":
            from ..symbol.symbol import fromjson

            sym = fromjson(head)
            from ..symbol.symbol import var as sym_var

            inputs = [sym_var(n) for n in input_names]
            blk2 = SymbolBlock(sym, inputs)
            if param_file:
                blk2.load_parameters(param_file,
                                     allow_missing=allow_missing)
            return blk2
        raise ValueError(f"unrecognized artifact {symbol_file}")

    def forward(self, *args):
        if self._exported is not None:
            datas = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                     for a in args]
            out = self._exported.call(self._arg_params, *datas)
            out = jax.tree_util.tree_map(NDArray, out)
            if isinstance(out, (list, tuple)) and len(out) == 1:
                return out[0]
            return out
        if self._symbol is None:
            raise RuntimeError("empty SymbolBlock")
        # lower + jit once (Executor does the same); retraces only on
        # shape/dtype change via jit's cache
        jitted = getattr(self, "_sym_jit", None)
        if jitted is None:
            from .. import passes as _passes

            jitted = _passes.apply(
                self._symbol._lower(),
                _passes.PassContext(block=self,
                                    label=type(self).__name__,
                                    variant="symbol", kind="symbol"))
            object.__setattr__(self, "_sym_jit", jitted)
        feed = {}
        for n, a in zip(self._input_names, args):
            feed[n] = a._data if isinstance(a, NDArray) else jnp.asarray(a)
        for n, p in self._arg_params.items():
            feed[n] = p.data()._data
        outs = jitted(feed)
        outs = [NDArray(o) for o in outs]
        return outs[0] if len(outs) == 1 else outs
