"""AMP — automatic mixed precision (reference: python/mxnet/amp/, 2321 LoC).

TPU re-design: bf16 is the native mixed-precision dtype; unlike fp16-on-GPU,
bf16's fp32-range exponent makes loss scaling unnecessary (the reference's
dynamic LossScaler exists for fp16 and is kept as an API shim). The
reference's cast-list machinery (amp/lists/symbol_fp16.py) maps to a simple
policy: matmul/conv compute in bf16, reductions/norms accumulate in fp32 —
which XLA does automatically once params/inputs are bf16 and normalization
ops upcast internally (see ops/nn.py batch_norm/rms_norm).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as _np

from ..base import normalize_dtype
from ..diagnostics import spans as _spans
from ..ndarray.ndarray import NDArray

from . import lists  # noqa: E402  (reference: amp/lists/ cast tables)

__all__ = ["init", "init_trainer", "scale_loss", "unscale",
           "convert_hybrid_block", "convert_model", "convert_symbol",
           "LossScaler", "lists", "warn_if_model_exists",
           "list_lp16_ops", "list_fp32_ops", "list_lp16_fp32_ops",
           "list_conditional_fp32_ops", "list_widest_type_cast",
           "list_loss_output_functions", "list_lp16_use_fp32_params"]

_initialized = False
_target_dtype = "bfloat16"

# back-compat aliases of the canonical tables in lists/symbol_bf16.py
_FP32_OPS = lists.symbol_bf16.FP32_FUNCS
_LP16_OPS = lists.symbol_bf16.BF16_FUNCS


def list_lp16_ops(target_dtype="bfloat16"):  # noqa: ARG001
    """Reference: amp/amp.py:769 — both fp16 and bf16 answer the TPU
    (bf16) table; see lists/symbol_fp16.py."""
    return list(_LP16_OPS)


def list_fp32_ops(target_dtype="bfloat16"):  # noqa: ARG001
    return list(_FP32_OPS)


def list_lp16_fp32_ops(target_dtype="bfloat16"):  # noqa: ARG001
    """Ops that run in either precision (reference: amp/amp.py:787)."""
    return list(lists.symbol_bf16.BF16_FP32_FUNCS)


def list_conditional_fp32_ops(target_dtype="bfloat16"):  # noqa: ARG001
    return list(lists.symbol_bf16.CONDITIONAL_FP32_FUNCS)


def list_widest_type_cast(target_dtype="bfloat16"):  # noqa: ARG001
    return list(lists.symbol_bf16.WIDEST_TYPE_CASTS)


def list_loss_output_functions(target_dtype="bfloat16"):  # noqa: ARG001
    return list(lists.symbol_bf16.LOSS_OUTPUT_FUNCTIONS)


def list_lp16_use_fp32_params(target_dtype="bfloat16"):  # noqa: ARG001
    """Reference: amp/amp.py:823 — None for fp16; the param-restrict map
    for bf16."""
    if target_dtype in ("float16", "fp16", _np.float16):
        return None
    return dict(lists.symbol_bf16.BF16_USE_FP32_PARAMS)


def warn_if_model_exists():
    """Warn about Blocks created before amp.init (reference:
    amp/amp.py:301 — walks the caller stack for Block locals)."""
    import inspect
    import logging

    from ..gluon.block import Block

    for f in inspect.stack():
        for k, v in f.frame.f_locals.items():
            if isinstance(v, Block):
                logging.warning("Block %s created in [%s:%d] before "
                                "AMP init.", k, f.filename, f.lineno)
                return


def convert_symbol(sym, target_dtype="bfloat16", target_dtype_ops=None,
                   fp32_ops=None, conditional_fp32_ops=None,
                   excluded_sym_names=None, data_names=None,
                   cast_optional_params=False):  # noqa: ARG001
    """Convert a Symbol to mixed precision (reference: amp/amp.py:430
    low_precision_pass over the nnvm graph). TPU-native: wraps the DAG in
    one `_amp_graph` node whose lowering traces the original graph to a
    jaxpr and rewrites it under the cast lists (amp.graph_pass.
    amp_rewrite) — outputs keep their original dtypes, matmuls/convs run
    bf16 on the MXU."""
    from ..symbol.symbol import Symbol

    if not isinstance(sym, Symbol):
        raise TypeError(f"convert_symbol expects a Symbol, got {type(sym)}")
    dt = "bfloat16" if target_dtype in ("float16", "fp16", "bfloat16",
                                        "bf16", _np.float16) \
        else str(target_dtype)
    leaves = {}
    for s in sym._topo():
        if s._op is None and s._name not in leaves:
            leaves[s._name] = s
    import json as _json
    return Symbol.create(
        "_amp_graph", *leaves.values(), name=f"amp_{sym.name}",
        nout=len(sym.list_outputs()),
        subgraph=sym.tojson(),
        in_names=_json.dumps(list(leaves)),
        target_dtype=dt)


def init(target_dtype="bfloat16", target_precision_ops=None,
         conditional_fp32_ops=None, fp32_ops=None):  # noqa: ARG001
    """Enable AMP (reference: amp.init). On TPU this sets the default policy
    used by convert_hybrid_block / Trainer AMP hooks."""
    global _initialized, _target_dtype
    _target_dtype = "bfloat16" if target_dtype in ("float16", "fp16",
                                                   "bfloat16", "bf16") \
        else target_dtype
    _initialized = True


def init_trainer(trainer):
    """Attach a loss scaler to the trainer (fp16 parity; no-op for bf16)."""
    trainer._amp_loss_scaler = LossScaler()
    return trainer


def scale_loss(loss, trainer):
    """Context manager scaling the loss (reference: amp.scale_loss).

    bf16 needs no scaling; returned object supports `with` and yields the
    (unscaled) loss for drop-in compatibility.
    """
    import contextlib

    scaler = getattr(trainer, "_amp_loss_scaler", None)

    @contextlib.contextmanager
    def ctx():
        if scaler is None or _target_dtype == "bfloat16":
            yield loss
        else:
            scaled = loss * scaler.loss_scale
            yield scaled

    return ctx()


def unscale(trainer):
    """Unscale gradients after backward (fp16 path; bf16 no-op)."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        return
    inv = 1.0 / scaler.loss_scale
    for p in trainer._params:
        if p.grad_req != "null":
            for g in p.list_grad():
                g._data = g._data * inv
                g._version += 1


def _keeps_fp32(p):
    # norms' scale/shift and running stats stay fp32 (cast-list analog),
    # and so does an expert layer's router: its top-k is decided on
    # float32 probabilities; and a looped model's exit gate, whose
    # sigmoid weighs every exit's loss; and a short convolution's taps,
    # a few numbers a channel that multiply in float32 inside the mix;
    # and a delta-rule layer's decay parameters (A_log, dt_bias): its
    # log-decays are float32 sums through the sequence
    name = p.name.lower()
    return any(k in name for k in ("gamma", "beta", "running", "moving",
                                   "router", "exit_gate", "conv_taps",
                                   "a_log", "dt_bias"))


def convert_hybrid_block(net, target_dtype="bfloat16", target_dtype_ops=None,
                         fp32_ops=None, conditional_fp32_ops=None,
                         excluded_sym_names=None, device=None,
                         cast_params_offline=True, graph_pass=False,
                         example_inputs=None):  # noqa: ARG001
    """Convert a HybridBlock to mixed precision (reference: amp.py:676
    convert_hybrid_block): params cast to bf16 except norm/scale params;
    the compiled program then runs matmuls/convs on the MXU in bf16.

    ``graph_pass=True`` is the reference's *graph-level* cast conversion
    (low_precision_pass.cc — every op forced through the cast lists
    regardless of how it was written): instead of casting params, the
    block's pass pipeline (docs/passes.md) gains passes.AmpPass, so
    every compiled variant — block jit, export, symbol lowering, the
    whole-step train program's forward — is rewritten under the cast
    lists.  Pass ``example_inputs`` (a tuple) to build the first
    variant eagerly and fill ``net._amp_stats`` before returning.
    """
    dtype = normalize_dtype("bfloat16" if target_dtype in (
        "float16", "fp16", "bfloat16", "bf16") else target_dtype)
    if graph_pass:
        from .graph_pass import convert_block_graph

        if example_inputs is not None:
            convert_block_graph(net, tuple(example_inputs), dtype)
        else:
            from .. import passes as _passes

            net.hybridize(True)
            net.pass_pipeline().register(_passes.AmpPass(dtype))
            net._jit_variants.clear()
        return net
    from ..gluon.parameter import cast_params

    with _spans.span("amp.convert", cat="compile"):
        cast_params([p for p in net.collect_params().values()
                     if (p._data_map is not None or p.shape is not None)
                     and not _keeps_fp32(p)], dtype)
    net._clear_cached()
    if not getattr(net, "amp_casts_inputs", True):
        # the block's floating inputs are not activations (noise levels,
        # weights of a loss): they keep the precision they come in
        return net
    # wrap forward so inputs are cast on entry
    orig_forward = net.forward

    def forward(*args):
        cast_args = [
            a.astype(dtype) if isinstance(a, NDArray)
            and _np.issubdtype(a.dtype, _np.floating) else a
            for a in args
        ]
        return orig_forward(*cast_args)

    net.forward = forward
    return net


convert_model = convert_hybrid_block


class LossScaler:
    """Dynamic loss scaler (reference: amp/loss_scaler.py). Needed for fp16
    only; bf16 training keeps scale 1."""

    def __init__(self, init_scale=2 ** 16, scale_factor=2.0,
                 scale_window=2000):
        self.loss_scale = 1.0 if _target_dtype == "bfloat16" else init_scale
        self._factor = scale_factor
        self._window = scale_window
        self._unskipped = 0
        self._check_cache = {}  # (shape, dtype) signature -> jitted check

    def has_overflow(self, params):
        """True when any gradient holds a non-finite value — ONE fused
        device reduction (the multi_all_finite kernel) and ONE host sync
        per step, instead of a per-array isfinite + sync loop."""
        grads = [p.grad()._data for p in params if p.grad_req != "null"]
        if not grads:
            return False
        import jax

        from ..ops.optimizer_ops import multi_all_finite

        sig = tuple((g.shape, str(g.dtype)) for g in grads)
        fn = self._check_cache.get(sig)
        if fn is None:
            fn = self._check_cache[sig] = jax.jit(
                lambda *gs: multi_all_finite(*gs))
        overflow = not bool(fn(*grads)[0])  # the step's one host sync
        if overflow:
            try:
                from ..observability import flight as _flight

                _flight.record("amp_overflow", arrays=len(grads),
                               loss_scale=float(self.loss_scale))
            except Exception:
                pass
        return overflow

    def update_scale(self, overflow):
        if overflow:
            self.loss_scale = max(self.loss_scale / self._factor, 1.0)
            self._unskipped = 0
        else:
            self._unskipped += 1
            if self._unskipped >= self._window:
                self.loss_scale *= self._factor
                self._unskipped = 0


from . import graph_pass  # noqa: E402
from .graph_pass import convert_block_graph  # noqa: E402


def _amp_graph_lower(ins, attrs):
    """Symbol-op lowering for convert_symbol's `_amp_graph` node: rebuild
    the wrapped DAG, trace it to a jaxpr at the incoming shapes, and run
    it under the AMP cast lists."""
    import json as _json

    import jax

    from ..symbol.symbol import fromjson
    from .graph_pass import amp_rewrite

    subfn = fromjson(attrs["subgraph"])._lower()
    names = _json.loads(attrs["in_names"])
    dt = jnp.bfloat16 if attrs["target_dtype"] in ("bfloat16", "bf16") \
        else jnp.dtype(attrs["target_dtype"])
    closed = jax.make_jaxpr(
        lambda *xs: tuple(subfn(dict(zip(names, xs)))))(*ins)
    outs = amp_rewrite(closed, dt)(*ins)
    return tuple(outs) if len(outs) > 1 else outs[0]


def _register_amp_sym_op():
    from ..symbol.symbol import register_sym_op

    register_sym_op("_amp_graph", _amp_graph_lower)


_register_amp_sym_op()
