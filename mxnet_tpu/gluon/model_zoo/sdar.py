"""SDAR: a decoder language model with sparse experts, trained by block
diffusion (model type ``sdar_moe``; JetLM/SDAR-30B-A3B-Chat is the
published size).

The layer is the Qwen3-MoE layer SDAR adapts: pre-norm residual blocks,
grouped-query attention with a per-head RMSNorm on queries and keys and
rotate-half rotary positions, and a mixture of gated experts in every
layer (`gluon.contrib.nn.DroplessMoE`: top-k routing that drops
nothing, and a share of the experts when the layer is divided over
chips).  Training follows block diffusion (Arriola et al.,
arXiv:2503.09573): each block of ``block_length`` positions draws a noise
level t, a position is replaced by the mask token with probability t,
and the network sees the noisy sequence and the clean one side by side —
2L positions under the block-diffusion attention mask of
`ops.pallas_attention.flash_attention` — and is scored on the masked
positions with weight 1/t.

Everything is a HybridBlock; `SDARForBlockDiffusion` takes (x0, u, t),
applies the noise inside the hybridized forward and returns the loss of
each sequence, so `gluon.TrainStep(net, None, trainer, n_data=3)` runs it
as one donated program.  bf16 through `amp.convert_hybrid_block`: norms,
the router, the softmax statistics and the loss stay float32.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...ndarray.ndarray import NDArray, apply_op
from ..block import HybridBlock
from ..contrib.nn import DroplessMoE
from ..nn import Dense, Embedding, HybridSequential
from .decoder import GroupedQueryAttention, RMSNorm, head_loss, run_layers

__all__ = ["RMSNorm", "GroupedQueryAttention", "SDARDecoderLayer",
           "SDARModel", "SDARForBlockDiffusion", "sdar_moe"]


class SDARDecoderLayer(HybridBlock):
    """h = x + attention(norm(x)); y = h + experts(norm(h))."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 expert_units, num_experts, experts_per_token, *,
                 norm_topk_prob=True, ep_size=1, ep_rank=0,
                 rope_theta=1e6, epsilon=1e-6, dtype="float32"):
        super().__init__()
        self.input_layernorm = RMSNorm(units, epsilon)
        self.self_attn = GroupedQueryAttention(
            units, num_heads, num_kv_heads, head_dim, rope_theta, epsilon,
            dtype)
        self.post_attention_layernorm = RMSNorm(units, epsilon)
        self.mlp = DroplessMoE(
            units, expert_units, num_experts, experts_per_token,
            ep_size=ep_size, ep_rank=ep_rank,
            normalize_top_k=norm_topk_prob, dtype=dtype)

    def forward(self, x, positions, block_diffusion=None):
        h = x + self.self_attn(self.input_layernorm(x), positions,
                               block_diffusion)
        return h + self.mlp(self.post_attention_layernorm(h))


class SDARModel(HybridBlock):
    """Embedding, ``num_layers`` decoder layers, final norm:
    ``forward(tokens (B, S), positions (S,), block_diffusion)`` -> hidden
    states (B, S, units).  With ``remat`` each layer is one checkpoint
    segment of a training program (`gluon.block.checkpoint_block`)."""

    def __init__(self, vocab_size, units, num_layers, remat=False,
                 epsilon=1e-6, dtype="float32", **layer):
        super().__init__()
        self._remat = bool(remat)
        self.embed_tokens = Embedding(vocab_size, units, dtype=dtype)
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(SDARDecoderLayer(units, epsilon=epsilon,
                                             dtype=dtype, **layer))
        self.norm = RMSNorm(units, epsilon)

    def forward(self, tokens, positions, block_diffusion=None):
        x = run_layers(self.layers, self._remat, self.embed_tokens(tokens),
                       positions, block_diffusion)
        return self.norm(x)


class SDARForBlockDiffusion(HybridBlock):
    """The block-diffusion training objective around `SDARModel`.

    ``forward(x0, u, t)``: clean tokens x0 (B, L) int32 below
    ``mask_token_id``, u (B, L) uniform in [0, 1) and t (B, L /
    block_length) the noise level of each block.  Position i of block
    b(i) = i // block_length is masked iff u_i < t_b(i) and then reads
    ``mask_token_id``; the model sees [noisy ; clean] (2L positions,
    rotary positions 0..L-1 twice) under the block-diffusion mask; the
    head scores the noisy half over the vocabulary rows held here, and

        loss of a sequence = (1 / L) * sum over masked i of
                             CE(logits_i, x0_i) / t_b(i)

    comes back per sequence, float32 — the loss itself, so a TrainStep
    takes this block with ``loss_fn=None`` and ``n_data=3``."""

    # u and t are noise, not activations: amp.convert_hybrid_block leaves
    # this block's floating inputs in the precision they come in
    amp_casts_inputs = False

    def __init__(self, vocab_size, units, num_layers, block_length,
                 mask_token_id, dtype="float32", **model):
        super().__init__()
        self._block = int(block_length)
        self._mask_id = int(mask_token_id)
        self.model = SDARModel(vocab_size, units, num_layers, dtype=dtype,
                               **model)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             dtype=dtype, in_units=units)

    def forward(self, x0, u, t):
        seq, blen, mask_id = x0.shape[1], self._block, self._mask_id
        if seq % blen or t.shape[1] * blen != seq:
            raise ValueError(
                f"{seq} tokens, {t.shape[1]} noise levels, blocks of "
                f"{blen}: one level per block is needed")

        def noise(x0_, u_, t_):
            t_pos = jnp.repeat(t_.astype(jnp.float32), blen, axis=1)
            masked = u_.astype(jnp.float32) < t_pos
            tokens = jnp.concatenate(
                [jnp.where(masked, mask_id, x0_), x0_], axis=1)
            weight = jnp.where(masked, 1.0 / t_pos, 0.0) / seq
            return tokens.astype(jnp.int32), weight

        tokens, weight = apply_op(noise, x0, u, t, name="block_noise")
        both = jnp.arange(seq, dtype=jnp.int32)
        positions = NDArray(jnp.concatenate([both, both]))
        hidden = self.model(tokens, positions, (blen, seq))
        return head_loss(hidden, self.lm_head.weight.data_for(x0), x0, weight,
                         "block_diffusion_loss", positions=seq)


def sdar_moe(vocab_size, units, num_layers, num_heads, num_kv_heads,
             head_dim, expert_units, num_experts, experts_per_token,
             block_length, mask_token_id, **kwargs):
    """`SDARForBlockDiffusion` from the sizes of a ``config.json`` of
    model type ``sdar_moe`` (hidden_size, num_hidden_layers,
    num_attention_heads, num_key_value_heads, head_dim,
    moe_intermediate_size, num_experts, num_experts_per_tok)."""
    return SDARForBlockDiffusion(
        vocab_size, units, num_layers, block_length, mask_token_id,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        expert_units=expert_units, num_experts=num_experts,
        experts_per_token=experts_per_token, **kwargs)
