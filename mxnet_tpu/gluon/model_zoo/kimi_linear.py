"""A decoder language model of model type ``kimi_linear``: a stack whose
layers mix their tokens by Kimi Delta Attention — a gated delta rule with
a decay for every channel, linear in the sequence — or by latent
attention that carries no positions, over a leading dense layer and then
sparse ones beside a shared expert (moonshotai/Kimi-Linear-48B-A3B-Instruct
is the published size this was written for: three delta layers to every
latent one).

Pre-norm residual layers, x <- x + Mix(RMSNorm(x)), x <- x +
FFN(RMSNorm(x)).  Layers are numbered from 1, as the two published lists
``linear_attn_config.kda_layers`` and ``.full_attn_layers`` number them.

*Kimi Delta Attention* (`KimiDeltaAttention`), H heads of width d:

    q = l2norm(silu(conv(W_q x)))   k = l2norm(silu(conv(W_k x)))
    v = silu(conv(W_v x))           conv: depthwise, causal, a few taps
    g = -exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias)    float32, <= 0
    beta = sigmoid(W_b x)                                     one a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = d ** -0.5 * S_t^T q_t
    Mix = W_o [RMSNorm_head(o_t) * sigmoid(W_gb (W_ga x))]

through `npx.short_conv` and `npx.kda_scan` (docs/linear_attention.md).

*Latent attention* is `deepseek_v3.MultiHeadLatentAttention` with
``rotary=False`` (``mla_use_nope``): the shared key part goes to every
head unrotated.

*FFN*: the first ``first_k_dense`` layers one `GatedMLP`; every other
layer `DroplessMoE` with a sigmoid router whose bias selects and never
weighs, renormalised gates times a scaling factor, and a shared expert —
on a share of ``ep_size`` chips the routed sum runs over the experts held
here and the shared expert is computed whole.

`KimiLinearForCausalLM` takes the tokens (B, S) and returns each
sequence's mean next-token cross-entropy over the vocabulary rows held
here, float32 — the loss itself, so `gluon.TrainStep(net, None, trainer,
n_data=1)` runs it as one donated program.  bf16 through
`amp.convert_hybrid_block`: norms, the convolutions' taps, ``A_log``,
``dt_bias``, g, beta, the scan's state, the router and its bias, the
softmax statistics and the loss stay float32.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

from ... import numpy_extension as npx
from ...ndarray.ndarray import apply_op
from ...telemetry import instruments as _telemetry
from ..block import HybridBlock
from ..contrib.nn import DroplessMoE, GatedMLP
from ..nn import Dense, Embedding, HybridSequential
from ..parameter import Parameter
from .decoder import RMSNorm, next_token_loss, run_layers
from .deepseek_v3 import MultiHeadLatentAttention

__all__ = ["KimiDeltaAttention", "KimiLinearDecoderLayer", "KimiLinearModel",
           "KimiLinearForCausalLM", "kimi_linear"]

_L2_EPS = 1e-6


def _l2norm_heads(x, heads):
    """(B, S, H * d) -> (B, S, H, d), every head's vector divided by its
    length, in float32, rounded once."""
    def pure(t):
        t32 = t.reshape(t.shape[:2] + (heads, -1)).astype(jnp.float32)
        return (t32 * jax.lax.rsqrt(
            jnp.sum(t32 * t32, axis=-1, keepdims=True) + _L2_EPS)
        ).astype(t.dtype)

    return apply_op(pure, x, name="l2norm_heads")


class KimiDeltaAttention(HybridBlock):
    """Kimi Delta Attention: ``num_heads`` heads of width ``head_dim``,
    each a (head_dim x head_dim) state through the sequence (the module's
    text has the equations).  ``forward(x)``: x (B, S, units) -> (B, S,
    units); no positions, no cache.

    Scopes, all under ``kda``: ``kda.proj`` (q, k, v), ``kda.conv`` (the
    taps, SiLU, the two l2 norms), ``kda.gate`` (the decay, beta, the
    output gate's pair), ``kda.scan`` (the op alone) and ``kda.out`` (the
    gated head norm and the output projection)."""

    def __init__(self, units, num_heads, head_dim, conv_kernel=4,
                 epsilon=1e-5, dtype="float32"):
        super().__init__()
        self._heads, self._hd = num_heads, head_dim
        self._eps = float(epsilon)
        width = num_heads * head_dim

        def proj(out_units, in_units):
            return Dense(out_units, use_bias=False, flatten=False,
                         dtype=dtype, in_units=in_units)

        self.q_proj = proj(width, units)
        self.k_proj = proj(width, units)
        self.v_proj = proj(width, units)
        # float32 under amp, like a norm's scale: a few numbers a channel
        self.q_conv_taps = Parameter("q_conv_taps",
                                     shape=(width, conv_kernel))
        self.k_conv_taps = Parameter("k_conv_taps",
                                     shape=(width, conv_kernel))
        self.v_conv_taps = Parameter("v_conv_taps",
                                     shape=(width, conv_kernel))
        self.f_a_proj = proj(head_dim, units)
        self.f_b_proj = proj(width, head_dim)
        self.dt_bias = Parameter("dt_bias", shape=(width,), init="zeros")
        self.A_log = Parameter("A_log", shape=(num_heads,), init="zeros")
        self.b_proj = proj(num_heads, units)
        self.g_a_proj = proj(head_dim, units)
        self.g_b_proj = proj(width, head_dim)
        self.o_norm = RMSNorm(head_dim, epsilon)
        self.o_proj = proj(units, width)

    def forward(self, x):
        b, s, _ = x.shape
        h, hd, eps = self._heads, self._hd, self._eps
        with jax.named_scope("kda"):
            with jax.named_scope("kda.proj"):
                q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            with jax.named_scope("kda.conv"):
                q = _l2norm_heads(npx.short_conv(
                    q, self.q_conv_taps.data_for(x)), h)
                k = _l2norm_heads(npx.short_conv(
                    k, self.k_conv_taps.data_for(x)), h)
                v = npx.short_conv(
                    v, self.v_conv_taps.data_for(x)).reshape((b, s, h, hd))
            with jax.named_scope("kda.gate"):
                g = apply_op(
                    lambda f, bias, a_log: -jnp.exp(a_log)[:, None]
                    * jax.nn.softplus(f.astype(jnp.float32) + bias).reshape(
                        (b, s, h, hd)),
                    self.f_b_proj(self.f_a_proj(x)),
                    self.dt_bias.data_for(x), self.A_log.data_for(x),
                    name="kda_decay")
                beta = apply_op(
                    lambda t: jax.nn.sigmoid(t.astype(jnp.float32)),
                    self.b_proj(x), name="kda_beta")
                gate = self.g_b_proj(self.g_a_proj(x))
            out = npx.kda_scan(q, k, v, g, beta)
            with jax.named_scope("kda.out"):
                def gated_norm(o, gamma, gate_):
                    o32 = o.astype(jnp.float32)
                    normed = o32 * jax.lax.rsqrt(
                        jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
                    return (normed * gamma * jax.nn.sigmoid(
                        gate_.astype(jnp.float32).reshape(o.shape))
                    ).astype(o.dtype).reshape((b, s, h * hd))

                return self.o_proj(apply_op(
                    gated_norm, out, self.o_norm.gamma.data_for(x), gate,
                    name="kda_gated_norm"))


class KimiLinearDecoderLayer(HybridBlock):
    """h = x + mix(norm(x)); y = h + ffn(norm(h)).

    ``mixer``: ("kda", `KimiDeltaAttention`'s arguments) or ("mla",
    `MultiHeadLatentAttention`'s); ``feed_forward``: ("dense",
    `GatedMLP`'s width) or ("moe", `DroplessMoE`'s arguments).  ``kind`` is
    the pair of names the gauge ``decoder_layers`` counts."""

    def __init__(self, units, mixer, feed_forward, epsilon=1e-5,
                 dtype="float32"):
        super().__init__()
        mix_kind, mix_args = mixer
        ff_kind, ff_args = feed_forward
        self.kind = (mix_kind, ff_kind)
        self.input_layernorm = RMSNorm(units, epsilon)
        self.self_attn = KimiDeltaAttention(
            units, epsilon=epsilon, dtype=dtype, **mix_args) \
            if mix_kind == "kda" else MultiHeadLatentAttention(
                units, epsilon=epsilon, dtype=dtype, rotary=False,
                **mix_args)
        self.post_attention_layernorm = RMSNorm(units, epsilon)
        self.mlp = GatedMLP(units, ff_args, dtype) if ff_kind == "dense" \
            else DroplessMoE(units, dtype=dtype, **ff_args)

    def forward(self, x, part=None):
        """``part``: ``"mix"`` or ``"ffn"`` for one half alone (the model
        runs them as two checkpoint segments), None for both."""
        if part != "ffn":
            z = self.input_layernorm(x)
            x = x + (self.self_attn(z) if self.kind[0] == "kda"
                     else self.self_attn(z, None))
        if part != "mix":
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class KimiLinearModel(HybridBlock):
    """Embedding, one layer for each of ``mixers`` ("kda" or "mla") — the
    first ``first_k_dense`` with a dense feed-forward, the others sparse —
    and the final norm: ``forward(tokens (B, S))`` -> hidden states (B, S,
    units).  With ``remat`` each layer is TWO checkpoint segments of a
    training program, its mixer half and its feed-forward half (a latent
    layer keeps its flash kernel's two results, a delta layer nothing)."""

    def __init__(self, vocab_size, units, mixers, first_k_dense, dense_units,
                 kda, attention, moe, remat=False, epsilon=1e-5,
                 dtype="float32"):
        super().__init__()
        self._remat = bool(remat)
        self.embed_tokens = Embedding(vocab_size, units, dtype=dtype)
        self.layers = HybridSequential()
        for i, kind in enumerate(mixers):
            self.layers.add(KimiLinearDecoderLayer(
                units, (kind, kda if kind == "kda" else attention),
                ("dense", dense_units) if i < first_k_dense
                else ("moe", moe), epsilon=epsilon, dtype=dtype))
        self.norm = RMSNorm(units, epsilon)

    def forward(self, tokens):
        sites = _telemetry.short_conv_sites_traced()
        # a layer's two halves are two segments: a delta layer's operands,
        # decays and kept states beside a 9,216-wide MLP's activations do
        # not fit one
        x = run_layers(self.layers, self._remat, self.embed_tokens(tokens),
                       parts=("mix", "ffn"))
        _telemetry.set_decoder_stack(
            collections.Counter(layer.kind for layer in self.layers), sites)
        return self.norm(x)


class KimiLinearForCausalLM(HybridBlock):
    """The next-token objective around `KimiLinearModel`
    (`decoder.next_token_loss`): ``forward(tokens)``, tokens (B, S) int32
    below ``vocab_size`` (the rows of embedding and head held here),
    returns each sequence's mean cross-entropy, float32 — the loss itself,
    so a TrainStep takes this block with ``loss_fn=None`` and
    ``n_data=1``."""

    def __init__(self, vocab_size, units, mixers, dtype="float32", **model):
        super().__init__()
        self.model = KimiLinearModel(vocab_size, units, mixers, dtype=dtype,
                                     **model)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             dtype=dtype, in_units=units)

    def forward(self, tokens):
        return next_token_loss(self.model(tokens),
                               self.lm_head.weight.data_for(tokens), tokens)


def kimi_linear(vocab_size, hidden_size, linear_attn_config,
                num_attention_heads, kv_lora_rank, qk_nope_head_dim,
                qk_rope_head_dim, v_head_dim, intermediate_size,
                moe_intermediate_size, num_experts, num_experts_per_token,
                num_shared_experts=1, first_k_dense_replace=1,
                routed_scaling_factor=1.0, moe_renormalize=True,
                moe_router_activation_func="sigmoid", rms_norm_eps=1e-5,
                q_lora_rank=None, num_expert_group=1, topk_group=1,
                rope_scaling=None, num_nextn_predict_layers=0,
                tie_word_embeddings=False, mla_use_nope=True,
                num_hidden_layers=None, layers=None, ep_size=1, ep_rank=0,
                remat=False, dtype="float32"):
    """`KimiLinearForCausalLM` from the keys of a ``config.json`` of model
    type ``kimi_linear`` (``num_experts`` is the router's width, every
    expert of a layer, held here or not).

    ``linear_attn_config`` gives the delta layers' ``num_heads``,
    ``head_dim`` and ``short_conv_kernel_size`` and the two lists
    ``kda_layers`` and ``full_attn_layers`` of PUBLISHED layer numbers,
    from 1.  ``layers`` — published numbers too — says which of them this
    model holds, in order (a cut configuration: a pipeline stage's, or a
    benchmark's); None holds 1 .. ``num_hidden_layers``.  A held layer
    is dense iff its published number is at most
    ``first_k_dense_replace``.

    Raises on what is not computed: query compression (``q_lora_rank``),
    grouped expert selection, ``rope_scaling``, multi-token prediction,
    tied embeddings, rotary latent attention (``mla_use_nope`` false)
    and a router that is not a sigmoid."""
    refused = {
        "q_lora_rank": q_lora_rank is not None,
        "num_expert_group / topk_group": (num_expert_group, topk_group)
        != (1, 1),
        "rope_scaling": rope_scaling is not None,
        "num_nextn_predict_layers": num_nextn_predict_layers > 0,
        "tie_word_embeddings": bool(tie_word_embeddings),
        "mla_use_nope=False": not mla_use_nope,
        "moe_router_activation_func": moe_router_activation_func
        != "sigmoid",
    }
    if any(refused.values()):
        raise NotImplementedError(
            "kimi_linear: not written: "
            + ", ".join(k for k, v in refused.items() if v))
    kda_layers = set(linear_attn_config["kda_layers"])
    full_layers = set(linear_attn_config["full_attn_layers"])
    if layers is None:
        if num_hidden_layers is None:
            raise ValueError("num_hidden_layers or layers")
        layers = range(1, num_hidden_layers + 1)
    layers = list(layers)
    if num_hidden_layers not in (None, len(layers)):
        raise ValueError(f"num_hidden_layers={num_hidden_layers} for "
                         f"{len(layers)} layers held")
    mixers = []
    for n in layers:
        if (n in kda_layers) == (n in full_layers):
            raise ValueError(
                f"layer {n}: in exactly one of kda_layers and "
                "full_attn_layers (numbered from 1)")
        mixers.append("kda" if n in kda_layers else "mla")
    dense = [n <= first_k_dense_replace for n in layers]
    if dense != sorted(dense, reverse=True):
        raise ValueError(f"layers {layers}: the dense layers (published "
                         f"number <= {first_k_dense_replace}) come first")
    return KimiLinearForCausalLM(
        vocab_size, hidden_size, mixers, dtype=dtype,
        first_k_dense=sum(dense), dense_units=intermediate_size,
        remat=remat, epsilon=rms_norm_eps,
        kda=dict(num_heads=linear_attn_config["num_heads"],
                 head_dim=linear_attn_config["head_dim"],
                 conv_kernel=linear_attn_config["short_conv_kernel_size"]),
        attention=dict(
            num_heads=num_attention_heads, kv_lora_rank=kv_lora_rank,
            qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim),
        moe=dict(
            hidden_units=moe_intermediate_size, num_experts=num_experts,
            top_k=num_experts_per_token, ep_size=ep_size, ep_rank=ep_rank,
            normalize_top_k=moe_renormalize, scoring_func="sigmoid",
            selection_bias=True,
            routed_scaling_factor=routed_scaling_factor,
            shared_units=num_shared_experts * moe_intermediate_size
            or None))
