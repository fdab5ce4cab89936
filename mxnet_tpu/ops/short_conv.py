"""The gated short convolution's mix: a token mixer that is not attention.

A layer of model type ``lfm2`` / ``lfm2_moe`` that does not attend mixes
its tokens with a depthwise causal convolution over the last few
positions, gated on both sides: a projection makes three streams B, C and
x~ of the layer's width, and

    u = B * x~
    c_t = sum over j < L of w[:, j] * u_{t - (L - 1) + j}      (u_s = 0, s < 0)
    y = C * c

(PyTorch's ``Conv1d(D, D, L, groups=D, padding=L - 1)`` cut to its first S
outputs, between two elementwise gates).  `gated_short_conv` is that mix
as ONE op: L shifted multiply-adds over a pad by L - 1 — no grouped
convolution, which on a TPU would run D one-channel convolutions' worth
of MXU passes for 2 L FLOPs an element — in float32, rounded to the
input's type once, with a hand-written VJP that keeps the op's two
operands and nothing of the tensor's size in float32: the backward
computes u and c again, turns the cotangent back through the same L
shifts the other way (anti-causal: a position's gradient comes from the
L - 1 positions after it) and hands dB, dC and dx~ back as one (B, S,
3D) cotangent; dw is summed over batch and positions in float32.

`short_conv` is the same taps without the two gates, followed by an
activation: what a linear-attention layer puts on its queries, keys and
values (``kimi_linear``: 4 taps, SiLU).  The same shifts, the same rule:
the backward keeps x and w and computes the taps' sum again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["gated_short_conv", "short_conv"]


def _shift(t, k):
    """t moved k positions along the sequence, zeros where nothing
    arrives: out_s = t_{s - k} (k > 0: from the past; k < 0: from the
    future).  A pad with a negative edge."""
    if k == 0:
        return t
    zero = jnp.zeros((), t.dtype)
    return jax.lax.pad(t, zero, ((0, 0, 0), (k, -k, 0), (0, 0, 0)))


def _streams(bcx, k=0):
    """B, C and x~ of bcx moved k positions, float32.  The shift comes
    first and is the operand's own: every tap then reads bcx itself and
    nothing of the tensor's size is shared between the taps, so XLA makes
    them all in one fusion instead of storing a float32 B * x~ first."""
    return tuple(t.astype(jnp.float32)
                 for t in jnp.split(_shift(bcx, k), 3, axis=-1))


def _conv(bcx, w):
    """c_t = sum over j of w[:, j] * (B * x~)_{t - (L - 1) + j}, float32."""
    taps, out = w.shape[1], 0.0
    for j in range(taps):
        b, _, x = _streams(bcx, taps - 1 - j)
        out = out + w[:, j] * b * x
    return out


@jax.custom_vjp
def _mix(bcx, w):
    _, c, _ = _streams(bcx)
    return (c * _conv(bcx, w.astype(jnp.float32))).astype(bcx.dtype)


def _mix_fwd(bcx, w):
    return _mix(bcx, w), (bcx, w)


def _mix_bwd(res, dy):
    bcx, w = res
    b, c, x = _streams(bcx)
    w32, taps = w.astype(jnp.float32), w.shape[1]
    dy32 = dy.astype(jnp.float32)
    du, dw = 0.0, []
    for j in range(taps):
        k = taps - 1 - j
        # the transpose of tap j: position s hears from position s + k
        _, ck, _ = _streams(bcx, -k)
        du = du + w32[:, j] * _shift(dy, -k).astype(jnp.float32) * ck
        bk, _, xk = _streams(bcx, k)
        dw.append(jnp.sum(dy32 * c * bk * xk, axis=(0, 1)))
    dbcx = jnp.concatenate([du * x, dy32 * _conv(bcx, w32), du * b], axis=-1)
    return dbcx.astype(bcx.dtype), jnp.stack(dw, axis=-1).astype(w.dtype)


_mix.defvjp(_mix_fwd, _mix_bwd)


@register_op("gated_short_conv")
def gated_short_conv(bcx, w):
    """The gated short convolution's mix, as one op.

    bcx: (B, S, 3 * D), a projection's output, three equal chunks B, C,
    x~ in this order; w: (D, L), the depthwise taps, tap L - 1 on the
    position itself.  Returns (B, S, D) in bcx's type:

        y_t = C_t * sum over j < L of w[:, j] * (B * x~)_{t - (L - 1) + j}

    with zeros before the sequence (causal: position t reads positions
    t - L + 1 .. t).  The arithmetic is float32, rounded once at the end;
    the backward (a hand-written VJP) keeps bcx and w alone, returns one
    (B, S, 3 * D) cotangent and sums dw over batch and positions in
    float32.  Forward and backward run under the scope ``short_conv.mix``
    and count one call site in the gauge ``short_conv_sites``."""
    from ..telemetry import instruments as _telemetry

    width, rest = divmod(bcx.shape[-1], 3)
    if bcx.ndim != 3 or w.ndim != 2 or rest or w.shape[0] != width:
        raise ValueError(
            f"bcx {bcx.shape}, taps {w.shape}: (B, S, 3 * D) streams and "
            "(D, L) taps")
    _telemetry.record_short_conv_site()
    with jax.named_scope("short_conv.mix"):
        return _mix(bcx, w)


def _taps(x, w):
    """c_t = sum over j of w[:, j] * x_{t - (L - 1) + j}, float32."""
    taps, out = w.shape[1], 0.0
    for j in range(taps):
        out = out + w[:, j] * _shift(x, taps - 1 - j).astype(jnp.float32)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _conv_act(x, w, silu):
    c = _taps(x, w.astype(jnp.float32))
    return (jax.nn.silu(c) if silu else c).astype(x.dtype)


def _conv_act_fwd(x, w, silu):
    return _conv_act(x, w, silu), (x, w)


def _conv_act_bwd(silu, res, dy):
    x, w = res
    w32, taps = w.astype(jnp.float32), w.shape[1]
    dc = dy.astype(jnp.float32)
    if silu:
        c = _taps(x, w32)
        sig = jax.nn.sigmoid(c)
        dc = dc * sig * (1.0 + c * (1.0 - sig))
    dx, dw = 0.0, []
    for j in range(taps):
        k = taps - 1 - j
        # the transpose of tap j: position s hears from position s + k
        dx = dx + w32[:, j] * _shift(dc, -k)
        dw.append(jnp.sum(dc * _shift(x, k).astype(jnp.float32),
                          axis=(0, 1)))
    return dx.astype(x.dtype), jnp.stack(dw, axis=-1).astype(w.dtype)


_conv_act.defvjp(_conv_act_fwd, _conv_act_bwd)


@register_op("short_conv")
def short_conv(x, w, activation="silu"):
    """A depthwise causal convolution over the last few positions, then an
    activation, as one op.

    x: (B, S, D); w: (D, L), the depthwise taps, tap L - 1 on the position
    itself; ``activation``: ``"silu"`` or None.  Returns (B, S, D) in x's
    type:

        y_t = act(sum over j < L of w[:, j] * x_{t - (L - 1) + j})

    with zeros before the sequence (PyTorch's ``Conv1d(D, D, L, groups=D,
    padding=L - 1)`` cut to its first S outputs).  L shifted multiply-adds
    in float32, rounded once; the backward (a hand-written VJP) keeps x
    and w alone, computes the taps' sum again for the activation's slope
    and sums dw over batch and positions in float32.  Forward and backward
    run under the scope ``short_conv.taps``."""
    if x.ndim != 3 or w.ndim != 2 or w.shape[0] != x.shape[-1]:
        raise ValueError(f"x {x.shape}, taps {w.shape}: (B, S, D) and "
                         "(D, L) taps")
    if activation not in ("silu", None):
        raise ValueError(f"activation {activation!r}: 'silu' or None")
    with jax.named_scope("short_conv.taps"):
        return _conv_act(x, w, activation == "silu")
