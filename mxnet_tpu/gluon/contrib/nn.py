"""Contrib layers beyond the reference's surface.

`MoEDense` — Mixture-of-Experts FFN (GShard-style top-k routing over
`parallel/moe.py`). The reference has no MoE; this layer plus
`parallel.moe_ffn_sharded` gives expert parallelism as a first-class
capability (shard the expert dimension over an 'ep' mesh axis).

`DroplessMoE` — the expert layer that holds a share: top-k routing that
drops nothing over gated three-matrix experts, told which experts live
here (`ep_rank` of `ep_size`); `parallel.moe.dropless_moe` is its
functional part (docs/moe.md).  `GatedMLP` is one such expert as a plain
block: a dense feed-forward layer, and the shared expert `DroplessMoE`
adds beside the routed ones.

`GatedShortConv` — a token mixer that is not attention: one projection to
three streams, `npx.gated_short_conv` (a depthwise causal convolution
over the last few positions between two elementwise gates) and an output
projection (docs/hybrid.md).
"""
from __future__ import annotations

import jax

from ... import autograd as ag
from ... import numpy_extension as npx
from ...ndarray.ndarray import apply_op
from ...telemetry import instruments as _telemetry
from ..block import HybridBlock, current_state_sink
from ..nn import Dense
from ..parameter import Parameter

__all__ = ["MoEDense", "GatedMLP", "GatedShortConv", "DroplessMoE"]


class MoEDense(HybridBlock):
    """MoE feed-forward: route each token to top_k of num_experts FFNs.

    Input (..., in_units) -> (output (..., in_units), aux_loss). The
    auxiliary load-balancing loss should be added to the training loss
    (scaled by ~1e-2), per the Switch-Transformer recipe.
    """

    def __init__(self, in_units, hidden_units, num_experts, top_k=2,
                 capacity_factor=1.25, weight_initializer=None):
        super().__init__()
        self._E = int(num_experts)
        self._top_k = int(top_k)
        self._cf = float(capacity_factor)
        self.router = Parameter("router", shape=(in_units, num_experts),
                                init=weight_initializer)
        self.wi = Parameter("wi",
                            shape=(num_experts, in_units, hidden_units),
                            init=weight_initializer)
        self.wo = Parameter("wo",
                            shape=(num_experts, hidden_units, in_units),
                            init=weight_initializer)

    def forward(self, x):
        from ...parallel import moe as _moe

        router = self.router.data_for(x)
        wi = self.wi.data_for(x)
        wo = self.wo.data_for(x)

        def pure(xv, r, a, b):
            shape = xv.shape
            tokens = xv.reshape(-1, shape[-1])
            out, aux = _moe.moe_ffn(
                {"router": r, "wi": a, "wo": b}, tokens,
                capacity_factor=self._cf, top_k=self._top_k)
            return out.reshape(shape), aux

        return apply_op(pure, x, router, wi, wo, name="moe_dense")

    def __repr__(self):
        return (f"MoEDense(experts={self._E}, top_k={self._top_k}, "
                f"capacity_factor={self._cf})")


class GatedMLP(HybridBlock):
    """down(silu(gate x) * up x) over the last axis, no biases:
    (..., in_units) -> (..., in_units) through ``hidden_units``."""

    def __init__(self, in_units, hidden_units, dtype="float32"):
        super().__init__()

        def proj(out_units, in_units):
            return Dense(out_units, use_bias=False, flatten=False,
                         dtype=dtype, in_units=in_units)

        self.gate_proj = proj(hidden_units, in_units)
        self.up_proj = proj(hidden_units, in_units)
        self.down_proj = proj(in_units, hidden_units)

    def forward(self, x):
        mid = apply_op(lambda g, u: jax.nn.silu(g) * u, self.gate_proj(x),
                       self.up_proj(x), name="silu_mul")
        return self.down_proj(mid)


class GatedShortConv(HybridBlock):
    """out_proj(C * conv(B * x~)) with [B ; C ; x~] = in_proj(x), no
    biases: (B, S, units) -> (B, S, units).  ``conv`` is depthwise and
    causal over the last ``kernel`` positions (`npx.gated_short_conv`, one
    op under the scope ``short_conv.mix``); its taps ``conv_taps`` (units,
    kernel) — tap ``kernel - 1`` on the position itself — stay float32
    under `amp.convert_hybrid_block`, like a norm's scale.  The whole
    block runs under the scope ``short_conv``."""

    def __init__(self, units, kernel=3, dtype="float32"):
        super().__init__()

        def proj(out_units, in_units):
            return Dense(out_units, use_bias=False, flatten=False,
                         dtype=dtype, in_units=in_units)

        self.in_proj = proj(3 * units, units)
        self.conv_taps = Parameter("conv_taps", shape=(units, kernel))
        self.out_proj = proj(units, units)

    def forward(self, x):
        with jax.named_scope("short_conv"):
            mixed = npx.gated_short_conv(self.in_proj(x),
                                         self.conv_taps.data_for(x))
            return self.out_proj(mixed)


class DroplessMoE(HybridBlock):
    """A chip's share of a dropless mixture of gated experts.

    Input (..., in_units) -> output (..., in_units).  The router scores
    all ``num_experts`` experts of the layer (softmax in float32, the
    ``top_k`` largest, gates divided by their sum when
    ``normalize_top_k``); this block holds experts ``ep_rank *
    num_experts / ep_size`` onward, ``num_experts / ep_size`` of them,
    stacked: ``gate_proj``/``up_proj`` (held, in_units, hidden_units) and
    ``down_proj`` (held, hidden_units, in_units).  The output is the sum
    over the held experts among each token's ``top_k``: with ``ep_size``
    1 the whole layer, otherwise this chip's part of it, and the parts of
    all ``ep_size`` shares add up to the whole (tests/test_sdar_moe.py).
    No token is dropped; there is no capacity and no auxiliary loss.

    The router's variants (`parallel.moe.route_top_k`):
    ``scoring_func="sigmoid"`` scores each expert by a sigmoid of its own
    logit; ``selection_bias=True`` adds the parameter ``router_bias``
    (``num_experts``,) — float32, kept so by amp, no gradient — to the
    scores for the choice of the ``top_k`` alone, never to a gate;
    ``routed_scaling_factor`` multiplies every gate; ``normalize_eps`` is
    what is added to the chosen gates' sum before they are divided by it
    (None: `route_top_k`'s own rule).  ``shared_units``
    adds a shared expert ``shared``, a `GatedMLP` of that width that every
    token passes: it is computed whole on every share, under the scope
    ``moe.shared``, and summed outside the share's partial result, so it
    counts once when the shares are added (tests/test_deepseek_v3.py).

    ``running_load`` (not trained) holds what the last training step counted on
    the device: [assignments routed to the held experts, busiest held
    expert's rows over their mean] and, with a selection bias, the share
    of the assignments the bias changed.  `telemetry.flush_moe_load()`
    reads it into the gauges ``moe_rows_routed_here``,
    ``moe_expert_load_max_over_mean`` and ``moe_bias_moved_share``, and
    sets ``moe_buffer_rows``: the length of the row buffer that step ran
    on (docs/moe.md).
    """

    def __init__(self, in_units, hidden_units, num_experts, top_k, *,
                 ep_size=1, ep_rank=0, normalize_top_k=True,
                 scoring_func="softmax", selection_bias=False,
                 routed_scaling_factor=1.0, shared_units=None,
                 normalize_eps=None, dtype="float32",
                 weight_initializer=None):
        super().__init__()
        if num_experts % ep_size or not 0 <= ep_rank < ep_size:
            raise ValueError(
                f"{num_experts} experts over ep_size={ep_size}, "
                f"ep_rank={ep_rank}: not a share")
        if scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func {scoring_func!r}: 'softmax' or "
                             "'sigmoid'")
        held = num_experts // ep_size
        self._top_k = int(top_k)
        self._first = int(ep_rank) * held
        self._normalize = bool(normalize_top_k)
        self._scoring = scoring_func
        self._scale = float(routed_scaling_factor)
        self._normalize_eps = normalize_eps
        # the router, and its bias, stay float32 under
        # amp.convert_hybrid_block
        self.router = Parameter("router", shape=(num_experts, in_units),
                                init=weight_initializer)
        self.router_bias = Parameter(
            "router_bias", shape=(num_experts,), init="zeros",
            grad_req="null", differentiable=False) if selection_bias \
            else None
        self.gate_proj = Parameter(
            "gate_proj", shape=(held, in_units, hidden_units), dtype=dtype,
            init=weight_initializer)
        self.up_proj = Parameter(
            "up_proj", shape=(held, in_units, hidden_units), dtype=dtype,
            init=weight_initializer)
        self.down_proj = Parameter(
            "down_proj", shape=(held, hidden_units, in_units), dtype=dtype,
            init=weight_initializer)
        self.running_load = Parameter(
            "running_load", shape=(3 if selection_bias else 2,),
            init="zeros", grad_req="null", differentiable=False)
        # the buffer lengths of the shapes last traced: TrainStep stages
        # the counters with them
        self.running_load.moe_rungs = None
        self.shared = GatedMLP(in_units, shared_units, dtype) \
            if shared_units else None

    def forward(self, x):
        from ...parallel import moe as _moe

        rungs = self.running_load.moe_rungs = _moe.buffer_rungs(
            x.size // x.shape[-1] * self._top_k,
            self.router.shape[0] // self.gate_proj.shape[0])

        def pure(xv, r, g, u, d, *bias):
            out, load = _moe.dropless_moe(
                xv.reshape(-1, xv.shape[-1]), r, g, u, d,
                top_k=self._top_k, first_expert=self._first,
                normalize=self._normalize, scoring=self._scoring,
                bias=bias[0] if bias else None, scale=self._scale,
                normalize_eps=self._normalize_eps)
            return out.reshape(xv.shape), load

        bias = () if self.router_bias is None \
            else (self.router_bias.data_for(x),)
        out, load = apply_op(
            pure, x, self.router.data_for(x), self.gate_proj.data_for(x),
            self.up_proj.data_for(x), self.down_proj.data_for(x), *bias,
            name="dropless_moe")
        if ag.is_training():
            sink = current_state_sink()
            if sink is not None:
                sink.record(self.running_load, load._data)
            else:
                self.running_load.data_for(x)._assign_from(load.detach())
                _telemetry.stage_moe_load(
                    getattr(self, "_scope_name", None)
                    or type(self).__name__, load._data, rungs)
        if self.shared is not None:
            with jax.named_scope("moe.shared"):
                out = out + self.shared(x)
        return out

    def __repr__(self):
        held, d, f = self.gate_proj.shape
        return (f"DroplessMoE({d} -> {f} -> {d}, experts "
                f"{self._first}..{self._first + held - 1} of "
                f"{self.router.shape[0]}, top_k={self._top_k})")
