"""A decoder language model of model type ``lfm2_moe``: a stack of layers
of two kinds — gated short convolutions and grouped-query attention —
over a few dense feed-forward layers and then sparse ones
(LiquidAI/LFM2-24B-A2B is the published size this was written for).

Pre-norm residual layers, h = x + Op(RMSNorm(x)), y = h +
FF(RMSNorm(h)).  Each layer chooses its two halves by itself:

*Op* by ``layer_types[i]``: ``"conv"`` is `GatedShortConv` — one
projection to three streams, a depthwise causal convolution over the last
``conv_L_cache`` positions between two elementwise gates
(`npx.gated_short_conv`), one output projection; ``"full_attention"`` is
`decoder.GroupedQueryAttention` with an RMSNorm over each head of the
queries and keys, rotate-half rotary positions and the causal mask
through the flash kernel.

*FF* by the layer's index: the first ``num_dense_layers`` one `GatedMLP`,
every other `DroplessMoE` with a sigmoid router whose bias selects and
never weighs, gates divided by their sum + 1e-6 and no shared expert — on
a share of ``ep_size`` chips the sum over the experts held here.

`Lfm2MoeForCausalLM` takes the tokens (B, S) and returns each sequence's
mean next-token cross-entropy over the vocabulary rows held here,
float32 — the loss itself, so `gluon.TrainStep(net, None, trainer,
n_data=1)` runs it as one donated program.  With ``tie_word_embeddings``
the head's rows are the embedding's own: one parameter, two uses, and its
gradient is the sum of the two.  bf16 through `amp.convert_hybrid_block`:
norms, the convolution's taps, the router and its bias, the softmax
statistics and the loss stay float32.
"""
from __future__ import annotations

import collections

import jax.numpy as jnp

from ...ndarray.ndarray import NDArray
from ...telemetry import instruments as _telemetry
from ..block import HybridBlock
from ..contrib.nn import DroplessMoE, GatedMLP, GatedShortConv
from ..nn import Dense, Embedding, HybridSequential
from .decoder import (GroupedQueryAttention, RMSNorm, next_token_loss,
                      run_layers)

__all__ = ["Lfm2MoeDecoderLayer", "Lfm2MoeModel", "Lfm2MoeForCausalLM",
           "lfm2_moe"]

OPERATORS = {"conv": "conv", "full_attention": "attention"}


class Lfm2MoeDecoderLayer(HybridBlock):
    """h = x + operator(norm(x)); y = h + feed_forward(norm(h)).

    ``operator``: ("conv", `GatedShortConv`'s arguments) or
    ("full_attention", `GroupedQueryAttention`'s); ``feed_forward``:
    ("dense", `GatedMLP`'s width) or ("moe", `DroplessMoE`'s arguments).
    ``kind`` is the pair of names the gauge ``decoder_layers`` counts."""

    def __init__(self, units, operator, feed_forward, epsilon=1e-5,
                 dtype="float32"):
        super().__init__()
        op_kind, op_args = operator
        ff_kind, ff_args = feed_forward
        self.kind = (OPERATORS[op_kind], ff_kind)
        self.operator_norm = RMSNorm(units, epsilon)
        if op_kind == "conv":
            self.conv = GatedShortConv(units, dtype=dtype, **op_args)
        else:
            self.self_attn = GroupedQueryAttention(
                units, epsilon=epsilon, dtype=dtype, head_norm=True,
                **op_args)
        self.ffn_norm = RMSNorm(units, epsilon)
        self.feed_forward = GatedMLP(units, ff_args, dtype) \
            if ff_kind == "dense" else DroplessMoE(units, dtype=dtype,
                                                   **ff_args)

    def forward(self, x, positions):
        z = self.operator_norm(x)
        h = x + (self.conv(z) if self.kind[0] == "conv"
                 else self.self_attn(z, positions, causal=True))
        return h + self.feed_forward(self.ffn_norm(h))


class Lfm2MoeModel(HybridBlock):
    """Embedding, one layer for each of ``layer_types`` — the first
    ``num_dense_layers`` with a dense feed-forward, the others sparse —
    and the final norm: ``forward(tokens (B, S), positions (S,))`` ->
    hidden states (B, S, units).  With ``remat`` each layer is one
    checkpoint segment of a training program (an attention layer keeps its
    flash kernel's two results, a convolution layer nothing)."""

    def __init__(self, vocab_size, units, layer_types, num_dense_layers,
                 dense_units, conv, attention, moe, remat=False,
                 epsilon=1e-5, dtype="float32"):
        super().__init__()
        self._remat = bool(remat)
        self.embed_tokens = Embedding(vocab_size, units, dtype=dtype)
        self.layers = HybridSequential()
        for i, kind in enumerate(layer_types):
            if kind not in OPERATORS:
                raise ValueError(f"layer_types[{i}] = {kind!r}: one of "
                                 f"{sorted(OPERATORS)}")
            self.layers.add(Lfm2MoeDecoderLayer(
                units, (kind, conv if kind == "conv" else attention),
                ("dense", dense_units) if i < num_dense_layers
                else ("moe", moe), epsilon=epsilon, dtype=dtype))
        self.embedding_norm = RMSNorm(units, epsilon)

    def forward(self, tokens, positions):
        sites = _telemetry.short_conv_sites_traced()
        x = run_layers(self.layers, self._remat, self.embed_tokens(tokens),
                       positions)
        _telemetry.set_decoder_stack(
            collections.Counter(layer.kind for layer in self.layers), sites)
        return self.embedding_norm(x)


class Lfm2MoeForCausalLM(HybridBlock):
    """The next-token objective around `Lfm2MoeModel`.

    ``forward(tokens)``: tokens (B, S) int32 below ``vocab_size`` (the
    rows of the embedding held here).  Position i < S - 1 is scored on
    token i + 1 over those rows, and

        loss of a sequence = (1 / (S - 1)) * sum over i < S - 1 of
                             CE(logits_i, tokens_{i+1})

    comes back per sequence, float32 — the loss itself, so a TrainStep
    takes this block with ``loss_fn=None`` and ``n_data=1``.  The logits
    are the final norm's output times the embedding's own matrix
    (``tie_word_embeddings``) or, untied, a head ``lm_head`` of its own."""

    def __init__(self, vocab_size, units, layer_types, dtype="float32",
                 tie_word_embeddings=True, **model):
        super().__init__()
        self.model = Lfm2MoeModel(vocab_size, units, layer_types,
                                  dtype=dtype, **model)
        self.lm_head = None if tie_word_embeddings else Dense(
            vocab_size, use_bias=False, flatten=False, dtype=dtype,
            in_units=units)

    def forward(self, tokens):
        seq = tokens.shape[1]
        positions = jnp.arange(seq, dtype=jnp.int32)
        hidden = self.model(tokens, NDArray(positions))
        head = (self.model.embed_tokens if self.lm_head is None
                else self.lm_head).weight
        return next_token_loss(hidden, head.data_for(tokens), tokens,
                               positions)


def lfm2_moe(vocab_size, hidden_size, layer_types, num_attention_heads,
             num_key_value_heads, intermediate_size, moe_intermediate_size,
             num_experts, num_experts_per_tok, num_dense_layers=2,
             conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
             norm_topk_prob=True, routed_scaling_factor=1.0,
             use_expert_bias=True, rope_theta=1000000.0,
             tie_word_embeddings=True, num_hidden_layers=None, ep_size=1,
             ep_rank=0, remat=False, dtype="float32"):
    """`Lfm2MoeForCausalLM` from the keys of a ``config.json`` of model
    type ``lfm2_moe`` (``num_experts`` is the router's width, every expert
    of a layer, held here or not; a head is ``hidden_size /
    num_attention_heads`` wide; ``rope_theta`` is ``rope_parameters``'
    own).  ``num_hidden_layers``, if given, has to be ``layer_types``'
    length; a convolution with a bias is not written."""
    if conv_bias:
        raise NotImplementedError("conv_bias=True: the short convolution's "
                                  "projections with a bias are not written")
    layer_types = list(layer_types)
    if num_hidden_layers not in (None, len(layer_types)):
        raise ValueError(f"num_hidden_layers={num_hidden_layers} for "
                         f"{len(layer_types)} layer_types")
    return Lfm2MoeForCausalLM(
        vocab_size, hidden_size, layer_types, dtype=dtype,
        tie_word_embeddings=tie_word_embeddings,
        num_dense_layers=num_dense_layers, dense_units=intermediate_size,
        remat=remat, epsilon=norm_eps,
        conv=dict(kernel=conv_L_cache),
        attention=dict(
            num_heads=num_attention_heads, num_kv_heads=num_key_value_heads,
            head_dim=hidden_size // num_attention_heads,
            rope_theta=rope_theta),
        moe=dict(
            hidden_units=moe_intermediate_size, num_experts=num_experts,
            top_k=num_experts_per_tok, ep_size=ep_size, ep_rank=ep_rank,
            normalize_top_k=norm_topk_prob, scoring_func="sigmoid",
            selection_bias=bool(use_expert_bias),
            routed_scaling_factor=routed_scaling_factor,
            normalize_eps=1e-6, shared_units=None))
