"""What the zoo's decoder language models share (`sdar`, `deepseek_v3`):
the norm, the call into the flash kernels, the per-layer checkpoint
segments and the loss over the vocabulary rows held here."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ndarray.ndarray import apply_op
from ...ops import nn as _nn
from ...ops.pallas_attention import SAVED_BY_NAME
from ...passes.remat import checkpoint_block
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["RMSNorm", "attend", "run_layers", "head_loss"]


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


def attend(q, k, v, **mask):
    """`flash_attention` on (B, H, S, width) heads under the scope
    ``attention``; the op reads its tiles off the shapes.  ``mask``:
    ``causal=True`` or ``block_diffusion=(block, half)``."""
    from ...ops.pallas_attention import flash_attention

    def kernel(q_, k_, v_):
        with jax.named_scope("attention"):
            return flash_attention(q_, k_, v_, **mask)

    return apply_op(kernel, q, k, v, name="flash_attention")


def run_layers(layers, remat, x, *args):
    """x through ``layers``, each called with ``args``; with ``remat``
    each layer is one checkpoint segment of a training program
    (`passes.remat.checkpoint_block`)."""
    for layer in layers:
        if remat:
            # recompute the layer on the way back, all but the flash
            # kernel: its output and logsumexp are 1/16 of what the
            # layer computes and the most expensive part to redo
            x = checkpoint_block(layer, x, *args, save=SAVED_BY_NAME)
        else:
            x = layer(x, *args)
    return x


def head_loss(hidden, head_weight, target, weight, name, positions=None):
    """sum over i of weight_i * CE(logits_i, target_i) for each sequence,
    float32: the logits are ``hidden`` (B, S, units), its first
    ``positions`` positions if given, times ``head_weight`` (rows of the
    vocabulary held here, units), under the scope ``lm_head``."""

    def pure(h, w, target_, weight_):
        with jax.named_scope("lm_head"):
            if positions is not None:
                h = h[:, :positions]
            logits = jnp.einsum("bld,vd->blv", h, w,
                                preferred_element_type=jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, target_.astype(jnp.int32)[..., None],
                axis=-1)[..., 0]
            return jnp.sum(weight_ * (lse - picked), axis=1)

    return apply_op(pure, hidden, head_weight, target, weight, name=name)
