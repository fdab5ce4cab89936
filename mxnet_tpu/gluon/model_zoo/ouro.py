"""Ouro: a looped decoder language model (model type ``ouro``;
ByteDance/Ouro-2.6B is the published size; "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741).

ONE stack of layers is run ``total_ut_steps`` times on its own output, on
the same weights; the final norm closes every pass, and its output is both
that pass's exit and the next pass's input:

    h0 = Embed(tokens);   ht = Norm(Layer_N(.. Layer_1(h(t-1))))   t = 1..R

A layer is sandwich-normed — an RMSNorm before AND after each branch:

    a = x + N2(Attn(N1(x)));    y = a + N4(MLP(N3(a)))

with multi-head attention under the causal mask, rotate-half rotary
positions on queries and keys and no per-head norm
(`decoder.GroupedQueryAttention(head_norm=False)`), and a gated MLP
(`contrib.nn.GatedMLP`); no biases.

Every exit is scored by the one head over the whole vocabulary, and a
learned gate weighs the exits (`ExitLoss`): lambda_t = sigmoid(w_g . ht +
b_g) per position, p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < R and
p_R = prod_{j<R} (1 - lambda_j), and a position's loss is the expected
cross-entropy under p less ``entropy_beta`` times p's entropy (the paper's
stage-one objective: a uniform prior over exits).

While a program is traced the R passes are one rolled loop whose body
holds the N layers once (`decoder.run_looped`), and the head runs by
blocks of positions (`decoder.token_loss`): R x S x vocabulary float32
logits are never held.  `OuroForCausalLM` takes the tokens and returns
each sequence's loss, so `gluon.TrainStep(net, None, trainer, n_data=1)`
runs it as one donated program.  bf16 through `amp.convert_hybrid_block`:
norm scales, the exit gate, the softmax statistics and the loss stay
float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ... import autograd as ag
from ...ndarray.ndarray import NDArray, apply_op
from ...telemetry import instruments as _telemetry
from ..block import HybridBlock, current_state_sink
from ..contrib.nn import GatedMLP
from ..nn import Dense, Embedding, HybridSequential
from ..parameter import Parameter
from .decoder import GroupedQueryAttention, RMSNorm, run_looped, token_loss

__all__ = ["OuroDecoderLayer", "OuroModel", "ExitLoss", "OuroForCausalLM",
           "ouro"]


class OuroDecoderLayer(HybridBlock):
    """a = x + norm(attention(norm(x))); y = a + norm(mlp(norm(a))): the
    second norm of each pair is on the branch's output, before the
    residual add."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 hidden_units, rope_theta=1e6, epsilon=1e-6,
                 dtype="float32"):
        super().__init__()
        self.input_layernorm = RMSNorm(units, epsilon)
        self.self_attn = GroupedQueryAttention(
            units, num_heads, num_kv_heads, head_dim, rope_theta, epsilon,
            dtype, head_norm=False)
        self.input_layernorm_2 = RMSNorm(units, epsilon)
        self.post_attention_layernorm = RMSNorm(units, epsilon)
        self.mlp = GatedMLP(units, hidden_units, dtype)
        self.post_attention_layernorm_2 = RMSNorm(units, epsilon)

    def forward(self, x, positions):
        a = x + self.input_layernorm_2(self.self_attn(
            self.input_layernorm(x), positions, causal=True))
        return a + self.post_attention_layernorm_2(self.mlp(
            self.post_attention_layernorm(a)))


class OuroModel(HybridBlock):
    """Embedding, then ``num_layers`` decoder layers and the final norm
    ``ut_steps`` times over: ``forward(tokens (B, S), positions (S,))`` ->
    every pass's hidden states (ut_steps, B, S, units).  With ``remat``
    each of the ut_steps x num_layers layer applications is one checkpoint
    segment of a training program."""

    def __init__(self, vocab_size, units, num_layers, ut_steps,
                 remat=False, epsilon=1e-6, dtype="float32", **layer):
        super().__init__()
        self._remat, self._steps = bool(remat), int(ut_steps)
        self.embed_tokens = Embedding(vocab_size, units, dtype=dtype)
        self.layers = HybridSequential()
        for _ in range(num_layers):
            self.layers.add(OuroDecoderLayer(units, epsilon=epsilon,
                                             dtype=dtype, **layer))
        self.norm = RMSNorm(units, epsilon)

    def forward(self, tokens, positions):
        return run_looped(self.layers, self._remat,
                          self.embed_tokens(tokens), positions,
                          steps=self._steps, after=self.norm)


class ExitLoss(HybridBlock):
    """The exit gate and the objective over the exits.

    ``forward(exits (R, B, S, units), ce (R, B, S))``: ``ce`` the
    per-token cross-entropy of each exit.  Per position, float32, in log
    space (from ``log_sigmoid(+-z)``, z = w_g . h + b_g):

        p_t  = lambda_t prod_{j<t} (1 - lambda_j)    t < R
        p_R  = prod_{j<R} (1 - lambda_j)             (lambda_R is unused)
        loss = sum_t p_t ce_t - beta H(p),   H(p) = -sum_t p_t log p_t

    and a sequence's loss is the mean over the S - 1 positions that have a
    next token: (B,) float32.  Scopes ``exit_gate`` (the gate's logits)
    and ``exit_loss``.  ``running_exit_mass`` (not trained) holds the mean
    of p_t over the last training step's positions, (R,), produced on the
    device; `telemetry.flush_exit_mass()` reads it into the gauge
    ``exit_mass{step}``."""

    def __init__(self, units, ut_steps, entropy_beta=0.1):
        super().__init__()
        self._beta = float(entropy_beta)
        # float32, and kept so by amp.convert_hybrid_block
        self.exit_gate_weight = Parameter("exit_gate_weight", shape=(units,))
        self.exit_gate_bias = Parameter("exit_gate_bias", shape=(1,),
                                        init="zeros")
        self.running_exit_mass = Parameter(
            "running_exit_mass", shape=(int(ut_steps),), init="zeros",
            grad_req="null", differentiable=False)
        self.running_exit_mass.stages_exit_mass = True

    def forward(self, exits, ce):
        beta = self._beta

        def pure(h, ce_, w, b):
            seq = h.shape[2]
            with jax.named_scope("exit_gate"):
                z = jnp.einsum("rbsd,d->rbs", h.astype(jnp.float32), w,
                               precision=lax.Precision.HIGHEST) + b
            with jax.named_scope("exit_loss"):
                stay = jax.nn.log_sigmoid(-z[:-1])
                stayed = jnp.concatenate(
                    [jnp.zeros_like(z[:1]), jnp.cumsum(stay, axis=0)])
                log_p = stayed + jnp.concatenate(
                    [jax.nn.log_sigmoid(z[:-1]), jnp.zeros_like(z[:1])])
                p = jnp.exp(log_p)
                per_token = jnp.sum(p * (ce_ + beta * log_p), axis=0)
                scored = jnp.arange(seq) < seq - 1
                return (jnp.sum(jnp.where(scored, per_token, 0.0), axis=1)
                        / (seq - 1), jnp.mean(p, axis=(1, 2)))

        loss, mass = apply_op(
            pure, exits, ce, self.exit_gate_weight.data_for(ce),
            self.exit_gate_bias.data_for(ce), name="exit_loss")
        if ag.is_training():
            sink = current_state_sink()
            if sink is not None:
                sink.record(self.running_exit_mass, mass._data)
            else:
                self.running_exit_mass.data_for(ce)._assign_from(
                    mass.detach())
                _telemetry.stage_exit_mass(mass._data)
        return loss


class OuroForCausalLM(HybridBlock):
    """The looped next-token objective around `OuroModel`.

    ``forward(tokens)``: tokens (B, S) int32 below ``vocab_size``.  Every
    exit's position i < S - 1 is scored on token i + 1 over the whole
    vocabulary, and `ExitLoss` weighs the exits: each sequence's loss
    comes back, (B,) float32 — the loss itself, so a TrainStep takes this
    block with ``loss_fn=None`` and ``n_data=1``."""

    def __init__(self, vocab_size, units, num_layers, ut_steps,
                 entropy_beta=0.1, dtype="float32", **model):
        super().__init__()
        self.model = OuroModel(vocab_size, units, num_layers, ut_steps,
                               dtype=dtype, **model)
        self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                             dtype=dtype, in_units=units)
        self.exit_loss = ExitLoss(units, ut_steps, entropy_beta)

    def forward(self, tokens):
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        exits = self.model(tokens, NDArray(positions))
        # every position is scored, so that the shapes stay whole tiles;
        # the last one, which has no next token, is left out of the mean
        target = apply_op(lambda t: jnp.roll(t, -1, axis=1), tokens,
                          name="next_token")
        ce = token_loss(exits, self.lm_head.weight.data_for(tokens), target,
                        "exit_token_loss")
        return self.exit_loss(exits, ce)


def ouro(vocab_size, hidden_size, num_hidden_layers, num_attention_heads,
         num_key_value_heads, head_dim, intermediate_size, total_ut_steps,
         rope_theta=1e6, rms_norm_eps=1e-6, entropy_beta=0.1, remat=False,
         dtype="float32"):
    """`OuroForCausalLM` from the keys of a ``config.json`` of model type
    ``ouro``; ``entropy_beta`` weighs the exit distribution's entropy in
    the training objective (config.json gives none; 0.1 is the paper's
    pre-training value)."""
    return OuroForCausalLM(
        vocab_size, hidden_size, num_hidden_layers, total_ut_steps,
        entropy_beta=entropy_beta, dtype=dtype, remat=remat,
        epsilon=rms_norm_eps, num_heads=num_attention_heads,
        num_kv_heads=num_key_value_heads, head_dim=head_dim,
        hidden_units=intermediate_size, rope_theta=rope_theta)
