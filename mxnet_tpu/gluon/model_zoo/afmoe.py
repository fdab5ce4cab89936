"""A decoder language model of model type ``afmoe``: grouped-query
attention of two kinds in one stack — sliding-window layers that carry
rotary positions and, every few layers, a global one that carries none —
with a sigmoid gate on attention's output, four norms a layer, a few
dense feed-forward layers and then sparse ones beside a shared expert
(arcee-ai/Trinity-Mini is the published size this was written for).

    h0 = Embed(tokens) * sqrt(hidden_size)                 (``mup_enabled``)
    a = x + N2(Attn(N1(x)));  y = a + N4(FFN(N3(a)))       (each layer)

*Attn* (`decoder.GroupedQueryAttention`): q, k, v, g = W_q x, W_k x, W_v x,
W_g x; an RMSNorm over each head of q and of k; on a
``"sliding_attention"`` layer rotate-half rotary positions and the band
mask — query i keeps key j iff 0 <= i - j < ``sliding_window`` — on a
``"full_attention"`` layer NO positions and the causal mask; both
through the flash kernels (scopes ``attention.window`` /
``attention.global`` inside ``attention``); Attn = W_o (heads *
sigmoid(g)) (scope ``attention.gate``).  No biases.

*FFN* by the layer's index: the first ``num_dense_layers`` one `GatedMLP`,
every other `DroplessMoE` with a sigmoid router in float32 whose bias
(``expert_bias`` in the published code, ``router_bias`` here) selects and
never weighs, gates divided by their sum + 1e-20 (``route_norm``) times
``route_scale``, and a shared expert beside the routed ones — on a share
of ``ep_size`` chips the routed sum runs over the experts held here and
the shared expert is computed whole.

`AfmoeForCausalLM` takes the tokens (B, S) and returns each sequence's
mean next-token cross-entropy over the vocabulary rows held here,
float32 — the loss itself, so `gluon.TrainStep(net, None, trainer,
n_data=1)` runs it as one donated program.  bf16 through
`amp.convert_hybrid_block`: norms, the router and its bias, the softmax
statistics and the loss stay float32.
"""
from __future__ import annotations

import collections
import math

import jax.numpy as jnp

from ...ndarray.ndarray import NDArray, apply_op
from ...telemetry import instruments as _telemetry
from ..block import HybridBlock
from ..contrib.nn import DroplessMoE, GatedMLP
from ..nn import Dense, Embedding, HybridSequential
from .decoder import (GroupedQueryAttention, RMSNorm, next_token_loss,
                      run_layers)

__all__ = ["AfmoeDecoderLayer", "AfmoeModel", "AfmoeForCausalLM", "afmoe"]

# layer_types' names -> the flash call's scope inside ``attention`` and
# the name the gauge ``decoder_layers`` counts
KINDS = {"sliding_attention": "window", "full_attention": "global"}


class AfmoeDecoderLayer(HybridBlock):
    """a = x + norm(attention(norm(x))); y = a + norm(ffn(norm(a))).

    ``kind``: ``"sliding_attention"`` (the band of ``window`` keys, rotary
    positions) or ``"full_attention"`` (causal, no positions);
    ``attention``: `GroupedQueryAttention`'s sizes; ``feed_forward``:
    ("dense", `GatedMLP`'s width) or ("moe", `DroplessMoE`'s arguments)."""

    def __init__(self, units, kind, window, attention, feed_forward,
                 epsilon=1e-5, dtype="float32"):
        super().__init__()
        ff_kind, ff_args = feed_forward
        self.kind = (KINDS[kind], ff_kind)
        sliding = kind == "sliding_attention"
        self.input_layernorm = RMSNorm(units, epsilon)
        self.self_attn = GroupedQueryAttention(
            units, epsilon=epsilon, dtype=dtype, head_norm=True,
            window=window if sliding else None, rotary=sliding,
            output_gate=True, scope="attention." + KINDS[kind], **attention)
        self.post_attention_layernorm = RMSNorm(units, epsilon)
        self.pre_mlp_layernorm = RMSNorm(units, epsilon)
        self.mlp = GatedMLP(units, ff_args, dtype) if ff_kind == "dense" \
            else DroplessMoE(units, dtype=dtype, **ff_args)
        self.post_mlp_layernorm = RMSNorm(units, epsilon)

    def forward(self, x, positions):
        a = x + self.post_attention_layernorm(self.self_attn(
            self.input_layernorm(x), positions, causal=True))
        return a + self.post_mlp_layernorm(self.mlp(
            self.pre_mlp_layernorm(a)))


class AfmoeModel(HybridBlock):
    """Embedding (times sqrt(units) with ``mup``), one layer for each of
    ``layer_types`` — the first ``num_dense_layers`` with a dense
    feed-forward, the others sparse — and the final norm:
    ``forward(tokens (B, S), positions (S,))`` -> hidden states (B, S,
    units).  With ``remat`` each layer is one checkpoint segment of a
    training program that keeps its flash kernel's two results."""

    def __init__(self, vocab_size, units, layer_types, num_dense_layers,
                 dense_units, window, attention, moe, mup=True, remat=False,
                 epsilon=1e-5, dtype="float32"):
        super().__init__()
        self._remat = bool(remat)
        self._embed_scale = math.sqrt(units) if mup else None
        self.embed_tokens = Embedding(vocab_size, units, dtype=dtype)
        self.layers = HybridSequential()
        for i, kind in enumerate(layer_types):
            if kind not in KINDS:
                raise ValueError(f"layer_types[{i}] = {kind!r}: one of "
                                 f"{sorted(KINDS)}")
            self.layers.add(AfmoeDecoderLayer(
                units, kind, window, attention,
                ("dense", dense_units) if i < num_dense_layers
                else ("moe", moe), epsilon=epsilon, dtype=dtype))
        self.norm = RMSNorm(units, epsilon)

    def forward(self, tokens, positions):
        sites = _telemetry.short_conv_sites_traced()
        x = self.embed_tokens(tokens)
        if self._embed_scale is not None:
            scale = self._embed_scale
            x = apply_op(lambda e: e * jnp.asarray(scale, e.dtype), x,
                         name="mup_embed_scale")
        x = run_layers(self.layers, self._remat, x, positions)
        _telemetry.set_decoder_stack(
            collections.Counter(layer.kind for layer in self.layers), sites)
        return self.norm(x)


class AfmoeForCausalLM(HybridBlock):
    """The next-token objective around `AfmoeModel`.

    ``forward(tokens)``: tokens (B, S) int32 below ``vocab_size`` (the
    rows of embedding and head held here).  Position i < S - 1 is scored
    on token i + 1 over those rows, and

        loss of a sequence = (1 / (S - 1)) * sum over i < S - 1 of
                             CE(logits_i, tokens_{i+1})

    comes back per sequence, float32 — the loss itself, so a TrainStep
    takes this block with ``loss_fn=None`` and ``n_data=1``.  The head
    ``lm_head`` is its own matrix (``tie_word_embeddings`` false)."""

    def __init__(self, vocab_size, units, layer_types, dtype="float32",
                 **model):
        super().__init__()
        self.model = AfmoeModel(vocab_size, units, layer_types, dtype=dtype,
                                **model)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             dtype=dtype, in_units=units)

    def forward(self, tokens):
        seq = tokens.shape[1]
        positions = jnp.arange(seq, dtype=jnp.int32)
        hidden = self.model(tokens, NDArray(positions))
        return next_token_loss(hidden, self.lm_head.weight.data_for(tokens),
                               tokens, positions)


def afmoe(vocab_size, hidden_size, layer_types, num_attention_heads,
          num_key_value_heads, head_dim, intermediate_size,
          moe_intermediate_size, num_experts, num_experts_per_tok,
          sliding_window, num_dense_layers=2, num_shared_experts=1,
          route_norm=True, route_scale=1.0, score_func="sigmoid",
          rms_norm_eps=1e-5, rope_theta=10000.0, rope_scaling=None,
          mup_enabled=True, tie_word_embeddings=False, n_group=1,
          topk_group=1, num_expert_groups=1, num_limited_groups=1,
          num_hidden_layers=None, ep_size=1, ep_rank=0, remat=False, dtype="float32"):
    """`AfmoeForCausalLM` from the keys of a ``config.json`` of model type
    ``afmoe`` (``num_experts`` is the router's width, every expert of a
    layer, held here or not; the ``num_shared_experts`` are one gated MLP
    of ``num_shared_experts * moe_intermediate_size``).
    ``num_hidden_layers``, if given, has to be ``layer_types``' length;
    ``layer_types`` says which layers are global
    (``global_attn_every_n_layers`` is how the published list was made).
    Not written, so refused: grouped
    selection (``n_group`` / ``topk_group`` / ``num_expert_groups`` /
    ``num_limited_groups`` other than 1), ``rope_scaling``, a
    ``score_func`` other than the sigmoid, tied embeddings."""
    groups = (n_group, topk_group, num_expert_groups, num_limited_groups)
    if groups != (1, 1, 1, 1):
        raise NotImplementedError(
            f"n_group, topk_group, num_expert_groups, num_limited_groups = "
            f"{groups}: grouped expert selection is not written")
    if rope_scaling is not None:
        raise NotImplementedError(f"rope_scaling={rope_scaling!r}: scaled "
                                  "rotary positions are not written")
    if score_func != "sigmoid":
        raise NotImplementedError(f"score_func={score_func!r}: the router "
                                  "scores by a sigmoid")
    if tie_word_embeddings:
        raise NotImplementedError("tie_word_embeddings=True: the head is "
                                  "its own matrix")
    layer_types = list(layer_types)
    if num_hidden_layers not in (None, len(layer_types)):
        raise ValueError(f"num_hidden_layers={num_hidden_layers} for "
                         f"{len(layer_types)} layer_types")
    return AfmoeForCausalLM(
        vocab_size, hidden_size, layer_types, dtype=dtype,
        num_dense_layers=num_dense_layers, dense_units=intermediate_size,
        window=int(sliding_window), mup=bool(mup_enabled), remat=remat,
        epsilon=rms_norm_eps,
        attention=dict(
            num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
            head_dim=head_dim, rope_theta=rope_theta),
        moe=dict(
            hidden_units=moe_intermediate_size, num_experts=num_experts,
            top_k=num_experts_per_tok, ep_size=ep_size, ep_rank=ep_rank,
            normalize_top_k=route_norm, scoring_func="sigmoid",
            selection_bias=True, routed_scaling_factor=route_scale,
            normalize_eps=1e-20,
            shared_units=num_shared_experts * moe_intermediate_size or None))
