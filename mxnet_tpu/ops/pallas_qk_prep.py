"""Query / key preparation for grouped-query attention in one pass.

A decoder layer that normalises each head of its queries and keys and
turns them by rotary positions (Qwen3, SDAR) hands the flash kernel a
tensor that went through three row-wise steps: RMSNorm over each head's
width, the rotation, and the move from the projection's layout (B, S,
H * D) to the head-major (B, H, S, D) the kernel reads.  As three XLA
ops that is several float32 passes over the tensor, forward and back.
`rms_norm_rotary` is the three as ONE op: a Pallas kernel reads a
(rows, D) block of one head straight out of the projection's layout —
lane block ``h`` of the last dimension, aligned because D is a multiple
of the lane width — normalises and rotates it in float32 and stores it
into the head-major result, so the tensor is read once and written once.
Rotate-half is a lane roll by D / 2 times a sine table with the sign
folded in (no slice, no concatenate); the cos / sin tables are (S, D)
float32 operands whose block does not move while the grid walks the
heads, so a row tile fetches them once.  The backward is one kernel of
the same grid: it recomputes each row's 1 / rms from x, turns the
cotangent back by the same roll (a roll by half the width is its own
inverse), writes dx in the projection's layout and leaves dgamma as
per-grid-step partial rows that XLA sums.

Heads narrower than a lane (D = 64: LFM2) go through the same two
kernels, two whole heads to a 128-lane block: the grid walks H / 2 lane
blocks of the projection's layout, the head-major block is (1, 2, rows,
64), gamma and the tables are laid out 128 wide on the host side of the
call (a head's lanes once a head of the block), the norm's mean is a
masked sum over each head's lanes, and rotate-half is, of the two lane
rolls by D / 2 and by 128 - D / 2, the one whose source lies in the
lane's own head — still its own inverse.  dgamma's partial rows are 128
wide; XLA adds their halves after the sum over the grid.

The op chooses by what it sees, with no knob: on a TPU, for S a multiple
of 8 and D a multiple of 128, or D = 64 with an even number of heads, the
kernels run; otherwise the composition ``rms_norm`` ->
``rotary_embedding`` -> ``transpose`` runs, which is also the op's
reference.  The kernels round to x's type once, after the rotation; the
composition rounds after the norm too.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import nn as _nn
from .pallas_attention import _LANES, _VMEM_BUDGET, _pallas_call, _shared
from .registry import register_op

__all__ = ["rms_norm_rotary"]

_MAX_ROWS = 2048

# head widths under a lane that the kernels tile, whole heads to a
# 128-lane block (the widths the tests cover)
_PACKED_WIDTHS = (64,)

# what the two kernels of one call share besides their operands' shapes:
# heads in the last dimension, rows of a block, the norm's epsilon,
# whether Pallas interprets the kernels (tests, off a TPU), whether
# there is a norm at all (no gamma: the rotation and the store alone),
# the heads a block holds (1: a head is whole lane blocks; 2: 64-wide
# heads, two to a 128-lane block), and whether there is a rotation at all
# (no positions: the norm and the store alone, no table among the operands)
_Sig = collections.namedtuple(
    "_Sig", "heads rows eps interpret norm pack rotate")


def _kernel_mode():
    """False where the kernels compile (a TPU), None where none can run."""
    return False if jax.devices()[0].platform == "tpu" else None


def _row_tile(s_len, d, itemsize, pack):
    """Rows of a block: the sequence where it is one block, else the
    largest power of two up to ``_MAX_ROWS`` whose blocks in the backward
    — x, dy and dx and both float32 tables, double-buffered — fit the
    fast-memory budget (the body works a register at a time: it keeps no
    temporary of a block's size).  A head narrower than a lane fills the
    lane in fast memory: each of a packed block's ``pack`` head-major
    slices counts as one."""
    flat, major = pack * d, pack * max(d, _LANES)
    fit = _VMEM_BUDGET // (2 * itemsize * (2 * flat + major) + 4 * 4 * flat)
    rows = 1 << (max(8, min(_MAX_ROWS, fit)).bit_length() - 1)
    return s_len if s_len <= rows else rows


def _tables(positions, theta, d, pack):
    """cos and sign-folded sin of the rotate-half angles, (S, pack * D)
    float32, a head's D lanes repeated for each head of a block:
    x * cos + rotate_half(x) * sin is `rotary_embedding`'s rotation."""
    inv_freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    angle = positions.astype(jnp.float32).reshape((-1, 1)) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (jnp.concatenate([cos, cos] * pack, axis=-1),
            jnp.concatenate([-sin, sin] * pack, axis=-1))


def _head_mean(v, d):
    """The mean of float32 rows over each head's d lanes: (rows, 1) where
    a block is one head; where it holds several, every lane holds its
    head's (a masked sum over the lanes a head: whole registers)."""
    lanes = v.shape[-1]
    if d == lanes:
        return jnp.mean(v, axis=-1, keepdims=True)
    head = lax.broadcasted_iota(jnp.int32, v.shape, 1) // d
    total = None
    for h in range(lanes // d):
        mine = head == h
        part = jnp.sum(jnp.where(mine, v, 0.0), axis=-1, keepdims=True)
        total = part if total is None else jnp.where(mine, part, total)
    return total * (1.0 / d)


def _rotate_half(v, d):
    """Each head's two halves swapped: a lane roll by d / 2 where a block
    is one head; where it holds several, of the rolls by d / 2 and by
    lanes - d / 2 the one whose source lies in the lane's own head.  Its
    own inverse either way."""
    from jax.experimental.pallas import tpu as pltpu

    lanes = v.shape[-1]
    if d == lanes:
        return pltpu.roll(v, d // 2, 1)
    first = lax.broadcasted_iota(jnp.int32, v.shape, 1) % d < d // 2
    return jnp.where(first, pltpu.roll(v, lanes - d // 2, 1),
                     pltpu.roll(v, d // 2, 1))


def _normed(x, eps, d):
    """(x / rms(x), 1 / rms(x)) of float32 rows, a head's d lanes each."""
    r = lax.rsqrt(_head_mean(x * x, d) + eps)
    return x * r, r


def _fwd_kernel(x_ref, *refs, eps, norm, rotate):
    *refs, o_ref = refs
    pack, d = o_ref.shape[1], o_ref.shape[-1]
    n = x_ref[0].astype(jnp.float32)
    if norm:
        xh, _ = _normed(n, eps, d)
        n = xh * refs[0][...]
    if rotate:
        cos_ref, sin_ref = refs[-2:]
        n = n * cos_ref[...] + _rotate_half(n, d) * sin_ref[...]
    out = n.astype(o_ref.dtype)
    if pack == 1:
        o_ref[0, 0] = out
    else:
        for h in range(pack):
            o_ref[0, h] = out[:, h * d:(h + 1) * d]


def _rotated_back(dy_ref, cos_ref=None, sin_ref=None):
    """The rotation's transpose on a block of cotangents: the same swap,
    on the sine's side; without tables the block as it is, float32, in
    the projection's layout."""
    pack, d = dy_ref.shape[1], dy_ref.shape[-1]
    if pack == 1:
        dy = dy_ref[0, 0].astype(jnp.float32)
    else:
        dy = jnp.concatenate([dy_ref[0, h] for h in range(pack)],
                             axis=-1).astype(jnp.float32)
    if cos_ref is None:
        return dy
    return dy * cos_ref[...] + _rotate_half(dy * sin_ref[...], d)


def _bwd_rotation_kernel(dy_ref, cos_ref, sin_ref, dx_ref):
    dx_ref[0] = _rotated_back(dy_ref, cos_ref, sin_ref).astype(dx_ref.dtype)


def _bwd_kernel(x_ref, dy_ref, g_ref, *refs, eps, s_len):
    import jax.experimental.pallas as pl

    *tables, dx_ref, dg_ref = refs      # cos and sin, or neither
    d = dy_ref.shape[-1]
    xh, r = _normed(x_ref[0].astype(jnp.float32), eps, d)
    dn = _rotated_back(dy_ref, *tables)
    rows, lanes = dn.shape
    dgamma = dn * xh
    if s_len % rows:
        # the last block hangs over the sequence: its rows past the end
        # hold whatever the padding holds
        row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        dgamma = jnp.where(row < s_len - pl.program_id(1) * rows, dgamma, 0.0)
    # eight partial rows: adds of whole registers, no cross-sublane reduce
    dg_ref[0, 0, 0] = dgamma.reshape((rows // 8, 8, lanes)).sum(axis=0)
    dxh = dn * g_ref[...]
    dx_ref[0] = (r * (dxh - xh * _head_mean(dxh * xh, d))).astype(dx_ref.dtype)


def _specs(sig, d):
    """The blocks both kernels share: x's (lane block h of the projection's
    layout: one head, or ``pack`` heads narrower than a lane), the
    head-major one of the same heads, gamma's and a table's."""
    import jax.experimental.pallas as pl

    rows, lanes = sig.rows, sig.pack * d
    return (pl.BlockSpec((1, rows, lanes), lambda b, r, h: (b, r, h)),
            pl.BlockSpec((1, sig.pack, rows, d), lambda b, r, h: (b, h, r, 0)),
            pl.BlockSpec((1, lanes), lambda b, r, h: (0, 0)),
            pl.BlockSpec((rows, lanes), lambda b, r, h: (r, 0)))


def _grid(sig, b, s_len):
    # the head runs fastest: a table's block stays while it does
    return (b, -(-s_len // sig.rows), sig.heads // sig.pack)


def _scale(gamma, sig):
    """gamma as the float32 row a block multiplies by: once a head."""
    scale = gamma.astype(jnp.float32).reshape((1, -1))
    return jnp.concatenate([scale] * sig.pack, axis=-1) if sig.pack > 1 \
        else scale


def _qk_prep_fwd_call(sig, x, gamma, cos, sin):
    b, s_len, width = x.shape
    d = width // sig.heads
    flat, major, scale, table = _specs(sig, d)
    in_specs, operands = [flat], [x]
    if sig.norm:
        in_specs.append(scale)
        operands.append(_scale(gamma, sig))
    if sig.rotate:
        in_specs += [table, table]
        operands += [cos, sin]
    return _pallas_call(
        functools.partial(_fwd_kernel, eps=sig.eps, norm=sig.norm,
                          rotate=sig.rotate),
        name="rms_norm_rotary_fwd", grid=_grid(sig, b, s_len),
        in_specs=in_specs, out_specs=major,
        out_shape=jax.ShapeDtypeStruct((b, sig.heads, s_len, d), x.dtype),
        interpret=sig.interpret,
    )(*operands)


def _qk_prep_bwd_call(sig, x, dy, gamma, cos, sin):
    """(dx, dgamma); without a norm x and gamma are None, dx is the
    cotangent turned back and stored in the projection's layout, and
    there is no dgamma."""
    import jax.experimental.pallas as pl

    b, _, s_len, d = dy.shape
    flat, major, scale, table = _specs(sig, d)
    grid = _grid(sig, b, s_len)
    dx_shape = jax.ShapeDtypeStruct((b, s_len, sig.heads * d), dy.dtype)
    if not sig.norm:
        return _pallas_call(
            _bwd_rotation_kernel, name="rms_norm_rotary_bwd", grid=grid,
            in_specs=[major, table, table], out_specs=flat,
            out_shape=dx_shape, interpret=sig.interpret,
        )(dy, cos, sin), None
    lanes = sig.pack * d
    tables = [cos, sin] if sig.rotate else []
    dx, partial = _pallas_call(
        functools.partial(_bwd_kernel, eps=sig.eps, s_len=s_len),
        name="rms_norm_rotary_bwd", grid=grid,
        in_specs=[flat, major, scale] + [table] * len(tables),
        out_specs=[flat, pl.BlockSpec((1, 1, 1, 8, lanes),
                                      lambda b, r, h: (b, r, h, 0, 0))],
        out_shape=[dx_shape,
                   jax.ShapeDtypeStruct(grid + (8, lanes), jnp.float32)],
        interpret=sig.interpret,
    )(x, dy, _scale(gamma, sig), *tables)
    dgamma = partial.sum(axis=(0, 1, 2, 3))
    if sig.pack > 1:
        # a lane block's heads share gamma: their partial sums add
        dgamma = dgamma.reshape((sig.pack, d)).sum(axis=0)
    return dx, dgamma.astype(gamma.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _prepared(x, gamma, cos, sin, sig):
    # one traced and lowered copy of each kernel a signature (`_shared`):
    # the layers of a model share it
    return _shared(_qk_prep_fwd_call, sig)(x, gamma, cos, sin)


def _prepared_fwd(x, gamma, cos, sin, sig):
    # nothing float32 of the tensor's size is kept: the backward
    # recomputes each row's 1 / rms from x (and needs no x without a norm)
    return (_prepared(x, gamma, cos, sin, sig),
            (x if sig.norm else None, gamma, cos, sin))


def _prepared_bwd(sig, res, dy):
    x, gamma, cos, sin = res
    dx, dgamma = _shared(_qk_prep_bwd_call, sig)(x, dy, gamma, cos, sin)
    # the tables come from integer positions: nothing flows back to them
    return (dx, dgamma, None if cos is None else jnp.zeros_like(cos),
            None if sin is None else jnp.zeros_like(sin))


_prepared.defvjp(_prepared_fwd, _prepared_bwd)


def _composition(x, gamma, positions, theta, num_heads, eps):
    """The three ops one after the other (two without a norm, or without
    positions): what the kernels replace, and the op's reference."""
    b, s_len, width = x.shape
    heads = x.reshape((b, s_len, num_heads, width // num_heads))
    if gamma is not None:
        heads = _nn.rms_norm(heads.astype(jnp.float32), gamma,
                             eps=eps).astype(x.dtype)
    if positions is not None:
        heads = _nn.rotary_embedding(heads, positions.reshape((s_len, 1)),
                                     theta)
    return heads.transpose((0, 2, 1, 3))


@register_op("rms_norm_rotary")
def rms_norm_rotary(x, gamma, positions, theta=10000.0, num_heads=1,
                    eps=1e-6):
    """Per-head RMSNorm, rotate-half rotary positions and the move to the
    head-major layout, as one op.

    x: (B, S, num_heads * D), a projection's output; ``gamma``: the
    norm's (D,) scale, shared by the heads, or None for a layer that
    rotates its heads without a norm (n = x below; the same two kernels
    with the norm compiled out, and a backward that reads the cotangent
    alone); ``positions``: the S explicit position ids, shared by the
    batch, or None for a layer that carries no positions (out = n below:
    the norm and the move alone, the rotation compiled out of the same
    kernels and no table among their operands).  Returns (B, num_heads,
    S, D) in x's type, what `flash_attention` reads:

        n = x / sqrt(mean(x ** 2 over D) + eps) * gamma     (each head)
        out = n * cos(a) + concat(-n[D/2:], n[:D/2]) * sin(a)

    with the angles of `rotary_embedding`.  Norm, angles and rotation
    are float32.

    On a TPU, for S a multiple of 8 and D a multiple of 128 — or D = 64
    with an even number of heads, two to a 128-lane block — one Pallas
    kernel does all three (x read once, the result written once, rounded
    to x's type once, after the rotation), and its backward is one kernel
    too (x and the cotangent read, dx written in x's layout, dgamma as
    partial rows); the layers of a model share one lowered copy of each.
    Anywhere else (D = 192 or 96, three 64-wide heads, S = 12, no TPU)
    the composition ``rms_norm`` -> ``rotary_embedding`` -> ``transpose``
    runs (rounded after the norm and after the rotation).  The gauge
    ``qk_prep_kernel_share`` says which share of the traced call sites
    took the kernels."""
    from ..telemetry import instruments as _telemetry

    b, s_len, width = x.shape
    d, rest = divmod(width, num_heads)
    if gamma is None and positions is None:
        raise ValueError("neither a norm (gamma) nor a rotation (positions): "
                         "that is a reshape and a transpose")
    if rest or (positions is not None and positions.size != s_len) or (
            gamma is not None and gamma.shape != (d,)):
        raise ValueError(
            f"x {x.shape} as {num_heads} heads, gamma "
            f"{None if gamma is None else gamma.shape}, "
            f"{None if positions is None else positions.size} positions: "
            "the last dimension is num_heads heads of gamma's width, and "
            "there is a position a row")
    interpret = _kernel_mode()
    # heads of a block: one, or those of a tested narrower width that
    # fill a lane block; the kernels tile whole lane blocks of whole heads
    pack = _LANES // d if d in _PACKED_WIDTHS else 1
    kernels = (interpret is not None and s_len % 8 == 0
               and pack * d % _LANES == 0 and num_heads % pack == 0)
    _telemetry.record_qk_prep_site(kernels)
    if not kernels:
        return _composition(x, gamma, positions, theta, num_heads, eps)
    rotate = positions is not None
    cos, sin = _tables(positions, theta, d, pack) if rotate else (None, None)
    sig = _Sig(int(num_heads),
               _row_tile(s_len, d, jnp.dtype(x.dtype).itemsize, pack),
               float(eps), interpret, gamma is not None, pack, rotate)
    return _prepared(x, gamma, cos, sin, sig)
