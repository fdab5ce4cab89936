"""A decoder language model of model type ``deepseek_v3``: latent
attention, a leading dense layer, then sparse layers with a shared expert
(kakaocorp/kanana-2-30b-a3b-instruct-2601 is the published size this was
written for; it compresses no queries, ``q_lora_rank`` null).

Pre-norm residual layers, x <- x + Attn(RMSNorm(x)), x <- x +
FFN(RMSNorm(x)).

*Latent attention* (`MultiHeadLatentAttention`): q = W_q x, per head
[q_nope ; q_rope]; [c ; k_rope] = W_kv_a x with ONE k_rope for all heads;
c <- RMSNorm(c); [k_nope ; v]_h = W_kv_b c per head; rotary positions on
q_rope and k_rope (neighbouring pairs with ``rope_interleave``);
k_h = [k_nope_h ; k_rope]; o_h = softmax_causal(q_h k_h^T /
sqrt(nope + rope)) v_h through the flash kernel, whose keys are wider
than its values; Attn = W_o [o_1 .. o_H].  No biases.

*FFN*: the first ``first_k_dense`` layers one `GatedMLP`; every other
layer `DroplessMoE` with a sigmoid router whose bias selects and never
weighs, a scaling factor on the gates and a shared expert beside the
routed ones — on a share of ``ep_size`` chips the routed sum runs over
the experts held here and the shared expert is computed whole.

`DeepseekV3ForCausalLM` takes the tokens (B, S) and returns each
sequence's mean next-token cross-entropy over the vocabulary rows held
here, float32 — the loss itself, so `gluon.TrainStep(net, None, trainer,
n_data=1)` runs it as one donated program.  bf16 through
`amp.convert_hybrid_block`: norms, the router and its bias, the softmax
statistics and the loss stay float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ... import numpy_extension as npx
from ...ndarray.ndarray import NDArray, apply_op
from ..block import HybridBlock
from ..contrib.nn import DroplessMoE, GatedMLP
from ..nn import Dense, Embedding, HybridSequential
from .decoder import RMSNorm, attend, next_token_loss, run_layers

__all__ = ["MultiHeadLatentAttention", "DeepseekV3DecoderLayer",
           "DeepseekV3Model", "DeepseekV3ForCausalLM", "deepseek_v3"]


class MultiHeadLatentAttention(HybridBlock):
    """Causal self-attention whose keys and values come from one low-rank
    latent a token (``kv_lora_rank`` wide, normed) and whose rotary part
    (``qk_rope_head_dim``) is decoupled: every head's query carries its
    own, the key's is one for all heads.  ``forward(x, positions)``: x
    (B, S, units), ``positions`` the S position ids.  With
    ``rotary=False`` the layer carries no positions (``mla_use_nope`` of
    model type ``kimi_linear``): the decoupled parts stay unrotated, the
    shared key part goes to every head as it is, and ``positions`` is not
    read.

    Scopes, all under ``mla``: ``mla.q``, ``mla.kv_latent`` (down
    projection, latent norm, up projection), ``mla.rope`` (rotation and
    the assembly of the heads: `npx.mla_heads`, one pass on a TPU; where
    nothing rotates the assembly alone, under ``mla.heads``),
    ``attention`` (the flash kernels) and ``mla.out``."""

    def __init__(self, units, num_heads, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, rope_theta=10000.0,
                 rope_interleave=True, epsilon=1e-6, dtype="float32",
                 rotary=True):
        super().__init__()
        self._heads, self._rank, self._v = num_heads, kv_lora_rank, v_head_dim
        self._theta, self._interleave = float(rope_theta), \
            bool(rope_interleave)
        self._rotary = bool(rotary)

        def proj(out_units, in_units):
            return Dense(out_units, use_bias=False, flatten=False,
                         dtype=dtype, in_units=in_units)

        self.q_proj = proj(num_heads * (qk_nope_head_dim + qk_rope_head_dim),
                           units)
        self.kv_a_proj = proj(kv_lora_rank + qk_rope_head_dim, units)
        self.kv_a_norm = RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = proj(num_heads * (qk_nope_head_dim + v_head_dim),
                              kv_lora_rank)
        self.o_proj = proj(units, num_heads * v_head_dim)

    def forward(self, x, positions):
        b, s, _ = x.shape
        h, rank, v_dim = self._heads, self._rank, self._v
        with jax.named_scope("mla"):
            with jax.named_scope("mla.q"):
                q = self.q_proj(x)
            with jax.named_scope("mla.kv_latent"):
                latent = self.kv_a_proj(x)
                c, k_rope = apply_op(
                    lambda t: (t[..., :rank], t[..., rank:]), latent,
                    name="split_latent")
                kv = self.kv_b_proj(self.kv_a_norm(c))
            with jax.named_scope("mla.rope" if self._rotary
                                 else "mla.heads"):
                # the rotation, the one key part beside every head's own
                # and the move to (B, H, S, ..) are row-wise: one op,
                # straight from the projections' layout
                q, k, v = npx.mla_heads(
                    q, kv, k_rope, positions if self._rotary else None,
                    self._theta, h, self._interleave)
            out = attend(q, k, v, causal=True)
            with jax.named_scope("mla.out"):
                return self.o_proj(
                    out.transpose((0, 2, 1, 3)).reshape((b, s, h * v_dim)))


class DeepseekV3DecoderLayer(HybridBlock):
    """h = x + attention(norm(x)); y = h + ffn(norm(h)), the ffn one
    gated MLP (``dense_units``) or, with ``dense_units`` None, the expert
    layer described by ``moe`` (`DroplessMoE`'s arguments)."""

    def __init__(self, units, attention, dense_units=None, moe=None,
                 epsilon=1e-6, dtype="float32"):
        super().__init__()
        self.input_layernorm = RMSNorm(units, epsilon)
        self.self_attn = MultiHeadLatentAttention(
            units, epsilon=epsilon, dtype=dtype, **attention)
        self.post_attention_layernorm = RMSNorm(units, epsilon)
        self.mlp = GatedMLP(units, dense_units, dtype) \
            if dense_units is not None \
            else DroplessMoE(units, dtype=dtype, **moe)

    def forward(self, x, positions):
        h = x + self.self_attn(self.input_layernorm(x), positions)
        return h + self.mlp(self.post_attention_layernorm(h))


class DeepseekV3Model(HybridBlock):
    """Embedding, ``first_k_dense`` dense layers then ``num_layers -
    first_k_dense`` sparse ones, final norm: ``forward(tokens (B, S),
    positions (S,))`` -> hidden states (B, S, units).  With ``remat``
    each layer is one checkpoint segment of a training program."""

    def __init__(self, vocab_size, units, num_layers, first_k_dense,
                 dense_units, attention, moe, remat=False, epsilon=1e-6,
                 dtype="float32"):
        super().__init__()
        self._remat = bool(remat)
        self.embed_tokens = Embedding(vocab_size, units, dtype=dtype)
        self.layers = HybridSequential()
        for i in range(num_layers):
            self.layers.add(DeepseekV3DecoderLayer(
                units, attention,
                dense_units=dense_units if i < first_k_dense else None,
                moe=moe, epsilon=epsilon, dtype=dtype))
        self.norm = RMSNorm(units, epsilon)

    def forward(self, tokens, positions):
        return self.norm(run_layers(self.layers, self._remat,
                                    self.embed_tokens(tokens), positions))


class DeepseekV3ForCausalLM(HybridBlock):
    """The next-token objective around `DeepseekV3Model`.

    ``forward(tokens)``: tokens (B, S) int32 below ``vocab_size`` (the
    rows of embedding and head held here).  Position i < S - 1 is scored
    on token i + 1 over those rows, and

        loss of a sequence = (1 / (S - 1)) * sum over i < S - 1 of
                             CE(logits_i, tokens_{i+1})

    comes back per sequence, float32 — the loss itself, so a TrainStep
    takes this block with ``loss_fn=None`` and ``n_data=1``."""

    def __init__(self, vocab_size, units, num_layers, dtype="float32",
                 **model):
        super().__init__()
        self.model = DeepseekV3Model(vocab_size, units, num_layers,
                                     dtype=dtype, **model)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             dtype=dtype, in_units=units)

    def forward(self, tokens):
        seq = tokens.shape[1]
        positions = jnp.arange(seq, dtype=jnp.int32)
        hidden = self.model(tokens, NDArray(positions))
        return next_token_loss(hidden, self.lm_head.weight.data_for(tokens),
                               tokens, positions)


def deepseek_v3(vocab_size, hidden_size, num_hidden_layers,
                num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, intermediate_size,
                moe_intermediate_size, n_routed_experts, num_experts_per_tok,
                n_shared_experts=0, first_k_dense_replace=1,
                routed_scaling_factor=1.0, norm_topk_prob=True,
                scoring_func="sigmoid", rope_theta=10000.0,
                rope_interleave=True, rms_norm_eps=1e-6, q_lora_rank=None,
                n_group=1, topk_group=1, ep_size=1, ep_rank=0, remat=False,
                dtype="float32"):
    """`DeepseekV3ForCausalLM` from the keys of a ``config.json`` of model
    type ``deepseek_v3`` (``n_routed_experts`` is the router's width,
    every expert of a layer, held here or not; the ``n_shared_experts``
    are one gated MLP of ``n_shared_experts * moe_intermediate_size``).
    Query compression and grouped selection are not written:
    ``q_lora_rank`` has to be None and ``n_group`` = ``topk_group`` = 1."""
    if q_lora_rank is not None or (n_group, topk_group) != (1, 1):
        raise NotImplementedError(
            f"q_lora_rank={q_lora_rank}, n_group={n_group}, "
            f"topk_group={topk_group}: query compression and grouped "
            "expert selection are not written")
    return DeepseekV3ForCausalLM(
        vocab_size, hidden_size, num_hidden_layers, dtype=dtype,
        first_k_dense=first_k_dense_replace, dense_units=intermediate_size,
        remat=remat, epsilon=rms_norm_eps,
        attention=dict(
            num_heads=num_attention_heads, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, rope_interleave=rope_interleave),
        moe=dict(
            hidden_units=moe_intermediate_size,
            num_experts=n_routed_experts, top_k=num_experts_per_tok,
            ep_size=ep_size, ep_rank=ep_rank,
            normalize_top_k=norm_topk_prob, scoring_func=scoring_func,
            selection_bias=True,
            routed_scaling_factor=routed_scaling_factor,
            shared_units=n_shared_experts * moe_intermediate_size or None))
