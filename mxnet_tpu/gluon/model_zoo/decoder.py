"""What the zoo's decoder language models share (`sdar`, `deepseek_v3`,
`ouro`): the norm, the call into the flash kernels, the per-layer
checkpoint segments, a stack run several times on its own output as one
rolled loop, and the loss over the vocabulary rows held here."""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax import lax

from ... import numpy_extension as npx
from ...ndarray.ndarray import NDArray, apply_op
from ...ops import nn as _nn
from ...ops.pallas_attention import SAVED_BY_NAME
from ...telemetry import instruments as _telemetry
from ..block import (HybridBlock, _push_sink, _StateSink, checkpoint_block,
                     current_state_sink)
from ..nn import Dense
from ..parameter import Parameter

__all__ = ["RMSNorm", "attend", "GroupedQueryAttention", "run_layers",
           "run_looped", "token_loss", "head_loss", "next_token_loss"]

# float32 logits a head makes whole; a call that would make more makes them
# by blocks of positions of at most _BLOCK_LOGITS_BYTES each
_WHOLE_LOGITS_BYTES = 2 << 30
_BLOCK_LOGITS_BYTES = 256 << 20


class RMSNorm(HybridBlock):
    """x / rms(x) * gamma over the last axis, computed in float32 and
    returned in x's type."""

    def __init__(self, units, epsilon=1e-6):
        super().__init__()
        self._eps = float(epsilon)
        self.gamma = Parameter("gamma", shape=(units,), init="ones")

    def forward(self, x):
        eps = self._eps
        return apply_op(
            lambda a, g: _nn.rms_norm(a.astype(jnp.float32), g,
                                      eps=eps).astype(a.dtype),
            x, self.gamma.data_for(x), name="rms_norm")


def attend(q, k, v, scope=None, **mask):
    """`flash_attention` on (B, H, S, width) heads under the scope
    ``attention``; the op reads its tiles off the shapes.  ``mask``:
    ``causal=True``, ``block_diffusion=(block, half)`` or ``window=W``.
    ``scope``: a second name inside ``attention`` for a stack whose
    layers differ in mask (``attention.window``, ``attention.global``)."""
    from ...ops.pallas_attention import flash_attention

    def kernel(q_, k_, v_):
        inner = contextlib.nullcontext() if scope is None \
            else jax.named_scope(scope)
        with jax.named_scope("attention"), inner:
            return flash_attention(q_, k_, v_, **mask)

    return apply_op(kernel, q, k, v, name="flash_attention")


class GroupedQueryAttention(HybridBlock):
    """Self-attention with ``num_kv_heads`` key-value heads under
    ``num_heads`` query heads (query head h reads key-value head
    h // (num_heads // num_kv_heads)), rotary positions on queries and
    keys — after an RMSNorm over each head's ``head_dim`` unless
    ``head_norm`` is False — through the flash kernel.
    ``forward(x, positions, block_diffusion, causal)``: x (B, S, units),
    ``positions`` the S position ids, and the static mask:
    ``block_diffusion`` (block length, half length), ``causal``, or
    neither for full attention.

    What a layer of a stack with two kinds of attention chooses (the
    defaults leave the block as it was): ``window=W`` keeps, of the
    causal pairs, key j for query i iff i - j < W (whatever ``forward``'s
    mask); ``rotary=False`` carries no positions (the heads are normed and
    not turned); ``output_gate=True`` adds a fifth projection ``gate_proj``
    (units -> num_heads * head_dim) whose sigmoid multiplies the heads'
    output before ``o_proj``, under the scope ``attention.gate``;
    ``scope`` names the flash call inside the ``attention`` scope."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rope_theta=10000.0, epsilon=1e-6, dtype="float32",
                 head_norm=True, window=None, rotary=True,
                 output_gate=False, scope=None):
        super().__init__()
        self._heads, self._kv_heads, self._hd = num_heads, num_kv_heads, \
            head_dim
        self._theta, self._eps = float(rope_theta), float(epsilon)
        self._window, self._rotary, self._scope = window, bool(rotary), scope

        def proj(out_units, in_units):
            return Dense(out_units, use_bias=False, flatten=False,
                         dtype=dtype, in_units=in_units)

        self.q_proj = proj(num_heads * head_dim, units)
        self.k_proj = proj(num_kv_heads * head_dim, units)
        self.v_proj = proj(num_kv_heads * head_dim, units)
        self.o_proj = proj(units, num_heads * head_dim)
        self.q_norm = RMSNorm(head_dim, epsilon) if head_norm else None
        self.k_norm = RMSNorm(head_dim, epsilon) if head_norm else None
        self.gate_proj = proj(num_heads * head_dim, units) \
            if output_gate else None

    def forward(self, x, positions, block_diffusion=None, causal=False):
        b, s, _ = x.shape
        hd = self._hd

        def prepared(t, norm, n):
            # the head's norm, the rotation and the move to (B, n, S, hd)
            # are row-wise: one op, straight from the projection's layout
            gamma = None if norm is None else norm.gamma.data_for(t)
            return npx.rms_norm_rotary(
                t, gamma, positions if self._rotary else None, self._theta,
                n, self._eps)

        q = prepared(self.q_proj(x), self.q_norm, self._heads)
        k = prepared(self.k_proj(x), self.k_norm, self._kv_heads)
        v = self.v_proj(x).reshape((b, s, self._kv_heads, hd)).transpose(
            (0, 2, 1, 3))
        mask = {"window": self._window} if self._window is not None \
            else {"causal": True} if causal \
            else {"block_diffusion": block_diffusion}
        out = attend(q, k, v, scope=self._scope, **mask)
        out = out.transpose((0, 2, 1, 3)).reshape((b, s, self._heads * hd))
        if self.gate_proj is not None:
            with jax.named_scope("attention.gate"):
                out = apply_op(
                    lambda o, g: (o.astype(jnp.float32) * jax.nn.sigmoid(
                        g.astype(jnp.float32))).astype(o.dtype),
                    out, self.gate_proj(x), name="attention_gate")
        return self.o_proj(out)


def run_layers(layers, remat, x, *args, parts=None):
    """x through ``layers``, each called with ``args``; with ``remat``
    each layer is one checkpoint segment of a training program
    (`gluon.block.checkpoint_block`).  ``parts``: names a layer's
    ``forward`` takes as its last argument, one call a name in order (a
    layer's mixer half and its feed-forward half), each call its own
    segment: the backward then holds one HALF's activations at a time."""
    for layer in layers:
        for part in parts or [None]:
            call = args if part is None else args + (part,)
            if remat:
                # recompute the layer on the way back, all but the flash
                # kernel: its output and logsumexp are 1/16 of what the
                # layer computes and the most expensive part to redo
                x = checkpoint_block(layer, x, *call, save=SAVED_BY_NAME)
            else:
                x = layer(x, *call)
    return x


def run_looped(layers, remat, x, *args, steps, after):
    """x through ``layers`` ``steps`` times on its own output and on the
    same parameters (`run_layers` each time); ``after`` — the final norm —
    is applied to each pass's result, which is both that pass's exit and
    the next pass's input.  Returns every pass's result, stacked:
    (steps, *x.shape).

    While a program is traced the passes are ONE rolled loop, a
    `lax.scan` of length ``steps`` whose body holds the layers once (under
    the scope ``ut_step``): the trace, the lowered text and the code on
    the device are those of one pass, whatever ``steps`` is; what a
    checkpoint segment keeps is stacked over the passes and a weight's
    gradient is summed over its uses in the backward loop's carry.  State
    a layer writes through the trace's sink leaves the loop stacked, and
    the last pass's is recorded.  Untraced it is a plain loop, like
    `checkpoint_block`."""

    def one_pass(h):
        with jax.named_scope("ut_step"):
            return after(run_layers(layers, remat, h, *args))

    if not isinstance(x._data, jax.core.Tracer):
        exits = []
        for _ in range(steps):
            x = one_pass(x)
            exits.append(x)
        return apply_op(lambda *e: jnp.stack(e), *exits, name="stack_exits")
    outer, written = current_state_sink(), []

    def body(h, _):
        inner = _StateSink()
        with _push_sink(inner):
            out = one_pass(NDArray(h))._data
        written[:] = inner.params
        return out, (out, tuple(inner.values))

    _, (exits, values) = lax.scan(body, x._data, None, length=steps)
    if outer is not None:
        for p, v in zip(written, values):
            outer.record(p, v[-1])
    _telemetry.set_looped_stack(steps)
    return NDArray(exits)


def _block_length(rows, seq, vocab):
    """Positions of a head block: the sequence where the call's float32
    logits (rows x seq x vocab) are few enough to make whole, else the
    largest divisor of ``seq`` by a power of two whose block of logits
    stays under `_BLOCK_LOGITS_BYTES`."""
    if 4 * rows * seq * vocab <= _WHOLE_LOGITS_BYTES:
        return seq
    length = seq
    while length % 2 == 0 and 4 * rows * length * vocab > _BLOCK_LOGITS_BYTES:
        length //= 2
    return length


def _token_ce(h, w, target, positions):
    """CE(logits_i, target_i) of every position, float32: ``h`` (..., S,
    units) — leading axes such as several exits over one batch — its first
    ``positions`` positions if given, ``target`` broadcast over the
    leading axes.  A block of positions' logits live only inside its
    checkpoint segment."""

    def ce(h_, t_):
        logits = jnp.einsum("bld,vd->blv", h_, w,
                            preferred_element_type=jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, t_.astype(jnp.int32)[..., None], axis=-1)[..., 0]
        return lse - picked

    if positions is not None:
        h = h[..., :positions, :]
    lead, (seq, units) = h.shape[:-2], h.shape[-2:]
    rows = math.prod(lead)
    length = _block_length(rows, seq, w.shape[0])
    h = h.reshape((rows, seq, units))
    target = jnp.broadcast_to(target, lead + (seq,)).reshape((rows, seq))
    if length == seq:
        return ce(h, target).reshape(lead + (seq,))

    def blocks(t):          # (rows, seq, ...) -> (blocks, rows, length, ...)
        return jnp.moveaxis(t.reshape(
            (rows, seq // length, length) + t.shape[2:]), 1, 0)

    out = lax.map(jax.checkpoint(lambda ht: ce(*ht)),
                  (blocks(h), blocks(target)))
    return jnp.moveaxis(out, 0, 1).reshape(lead + (seq,))


def token_loss(hidden, head_weight, target, name, positions=None):
    """CE(logits_i, target_i) for every position, float32, in ``hidden``'s
    leading shape: the logits are ``hidden`` (..., B, S, units), its first
    ``positions`` positions if given, times ``head_weight`` (rows of the
    vocabulary held here, units), under the scope ``lm_head``; ``target``
    (B, S) serves every leading axis.  Where the logits of the whole call
    would pass `_WHOLE_LOGITS_BYTES` they are made by blocks of positions,
    one rolled loop whose body is a checkpoint segment, so that only one
    block's logits are ever live, forward or backward; the block length
    is read off the shapes."""

    def pure(h, w, target_):
        with jax.named_scope("lm_head"):
            return _token_ce(h, w, target_, positions)

    return apply_op(pure, hidden, head_weight, target, name=name)


def head_loss(hidden, head_weight, target, weight, name, positions=None):
    """sum over i of weight_i * CE(logits_i, target_i) for each sequence,
    float32: `token_loss`'s per-token cross-entropy of ``hidden`` (B, S,
    units), weighted and summed, under the scope ``lm_head``."""

    def pure(h, w, target_, weight_):
        with jax.named_scope("lm_head"):
            return jnp.sum(weight_ * _token_ce(h, w, target_, positions),
                           axis=1)

    return apply_op(pure, hidden, head_weight, target, weight, name=name)


def next_token_loss(hidden, head_weight, tokens, positions=None):
    """Each sequence's mean next-token cross-entropy, float32: position
    i < S - 1 of ``hidden`` (B, S, units) is scored on ``tokens``[i + 1]
    over the rows of ``head_weight`` (`head_loss`).  Every position is
    scored, so that the shapes stay whole tiles; the last one, which has no
    next token, with weight 0.  ``positions``: 0 .. S - 1 as int32, from a
    caller that made them for its layers already."""
    seq = tokens.shape[1]
    if positions is None:
        positions = jnp.arange(seq, dtype=jnp.int32)
    target = apply_op(lambda t: jnp.roll(t, -1, axis=1), tokens,
                      name="next_token")
    weight = NDArray(jnp.broadcast_to(
        (positions < seq - 1).astype(jnp.float32) / (seq - 1),
        tokens.shape))
    return head_loss(hidden, head_weight, target, weight, "causal_lm_loss")
