"""Latent attention's heads assembled in one pass.

A `deepseek_v3` layer hands the flash kernel q, k (B, H, S, nope + rope)
and v (B, H, S, v) that it has to put together from three projections:
q (B, S, H * (nope + rope)), every head's [q_nope ; q_rope]; kv (B, S,
H * (nope + v)), every head's [k_nope ; v]; and ONE k_rope (B, S, rope)
for all heads.  The rope lanes turn by rotary positions, k_rope goes to
every head, and everything moves to the head-major layout.  As XLA ops
that is a float32 rotation, a broadcast, two concatenations and three
transposes, each a pass over the tensors, forward and back.
`mla_heads` is all of it as ONE op of two Pallas kernels, each tensor
read once and written once:

*q*: the grid walks (batch, row tile, pair of heads).  A head is nope +
64 lanes wide, so only every other head starts on a lane block: a step
reads the 2 * (nope + 64) lanes of two heads.  The first head's nope
lanes pass through as they are; the register after them holds
[q_rope of the first ; 64 nope lanes of the second], and every further
register of the second head straddles two of its own, so each of its
lane blocks is the upper half of one register beside the lower half of
the next (a lane roll by 64 and a select).  The rope lanes turn in
float32 where they lie, the other lanes of their register untouched,
and are rounded once.

*k, v*: the same grid.  Head h's block of kv is [k_nope ; v], both whole
lane blocks: they are copied as they are, k_nope beside the rotated
k_rope block (the same for every head of a row tile, turned again each
step: 64 lanes) and v on its own.

The backward is the same two grids the other way: dq back to the
projection's layout with the rotation's transpose on the rope lanes; dk's
nope lanes and dv into kv's layout, and dk's rope lanes summed over the
heads in a float32 block of fast memory that the last pair of heads
turns back and stores as dk_rope.  The rotation is linear and there is no
norm, so the rule keeps nothing but the tables.

**The kernels leave the rope lanes where they are.**  `rotary_embedding`
with ``interleaved`` moves the pairs (2i, 2i + 1) to (i, i + rope / 2)
and leaves them there, because a score q . k does not depend on an order
q and k share; the kernels use the same freedom the other way and turn
each pair in place (the partner of a lane is its neighbour: lane rolls by
1 and by 127 chosen by the lane's parity, the sign folded into the sine
table).  Without ``interleaved`` they turn lane i with lane i + rope / 2,
in place, as `rotary_embedding` does.

The op chooses by what it sees, with no knob: on a TPU, for S a multiple
of 8, nope and v multiples of 128, rope 64 and an even number of heads,
the kernels run; anywhere else the composition of XLA ops runs.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import nn as _nn
from .pallas_attention import _LANES, _VMEM_BUDGET, _pallas_call, _shared
from .pallas_qk_prep import _MAX_ROWS, _kernel_mode
from .registry import register_op

__all__ = ["mla_heads"]

# the rope width the kernels tile: half a lane block, so that two heads
# are whole lane blocks
_ROPE = _LANES // 2

# rows a kernel's body works on at a time: a few registers a value
_CHUNK = 64

# what the kernels of one call share besides their operands' shapes: the
# heads, the widths of a head's nope and v lanes, rows of a block, the
# pairing of the rope lanes, and whether Pallas interprets the kernels
# (tests, off a TPU)
_Sig = collections.namedtuple(
    "_Sig", "heads nope v rows interleaved interpret rotate")


def _row_tile(s_len, nope, v, itemsize):
    """Rows of a block: the sequence where it is one block, else the
    largest power of two up to ``_MAX_ROWS`` whose blocks in the widest
    kernel, the backward of k and v — dk (nope + 64 lanes stored as whole
    lane blocks), dv, dkv and dk_rope of two heads and both float32
    tables, double-buffered, and the float32 sum — fit the fast-memory
    budget."""
    a_row = 2 * (2 * itemsize * (2 * nope + _LANES + 2 * v)
                 + itemsize * _LANES + 2 * 4 * _LANES) + 4 * _LANES
    rows = 1 << (max(8, min(_MAX_ROWS, _VMEM_BUDGET // a_row)).bit_length()
                 - 1)
    return s_len if s_len <= rows else rows


def _tables(positions, theta, interleaved):
    """cos and sign-folded sin of the rope lanes' angles, in the order the
    lanes keep, (S, 128) float32: the 64 lanes twice, because a register
    holds a head's rope lanes in its lower or its upper half."""
    half = _ROPE // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / _ROPE)
    angle = positions.astype(jnp.float32).reshape((-1, 1)) * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if interleaved:
        cos = jnp.repeat(cos, 2, axis=-1)
        sin = jnp.stack([-sin, sin], axis=-1).reshape(cos.shape)
    else:
        cos = jnp.concatenate([cos, cos], axis=-1)
        sin = jnp.concatenate([-sin, sin], axis=-1)
    return (jnp.concatenate([cos, cos], axis=-1),
            jnp.concatenate([sin, sin], axis=-1))


def _low(shape):
    """The lanes of a register's lower half."""
    return lax.broadcasted_iota(jnp.int32, shape, 1) < _ROPE


def _partner(t, interleaved):
    """Every lane's partner in the rotation, for 64 rope lanes in either
    half of a 128-lane register: its neighbour (pairs), or the lane half
    the rope width away (halves).  Its own inverse."""
    from jax.experimental.pallas import tpu as pltpu

    lane = lax.broadcasted_iota(jnp.int32, t.shape, 1)
    if interleaved:
        return jnp.where(lane % 2 == 0, pltpu.roll(t, _LANES - 1, 1),
                         pltpu.roll(t, 1, 1))
    half = _ROPE // 2
    return jnp.where(lane % _ROPE < half, pltpu.roll(t, _LANES - half, 1),
                     pltpu.roll(t, half, 1))


def _turned(t, cos, sin, interleaved):
    return t * cos + _partner(t, interleaved) * sin


def _turned_back(t, cos, sin, interleaved):
    """The rotation's transpose: the same swap, on the sine's side."""
    return t * cos + _partner(t * sin, interleaved)


def _swapped(t):
    """A register's two halves exchanged."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(t, _ROPE, 1)


def _twice(t):
    """64 lanes as a 128-lane register that holds them in both halves."""
    return jnp.concatenate([t, t], axis=-1)


def _by_chunks(rows, body):
    """``body(rows of the block)`` for ``_CHUNK`` rows at a time where
    they divide the block, else for the block at once (a short
    sequence)."""
    import jax.experimental.pallas as pl

    if rows % _CHUNK:
        body(slice(None))
        return

    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * _CHUNK, _CHUNK), _CHUNK))
        return carry

    lax.fori_loop(0, rows // _CHUNK, step, None)


def _turn(tables, r, interleaved, back=False):
    """Rows r of the tables read, and with them ``turn(t)``: a register
    turned by their angles (``back``: the rotation's transpose).  Without
    tables — a layer that carries no positions — nothing is read and a
    register stays as it is."""
    if not tables:
        return lambda t: t
    cos, sin = tables[0][r], tables[1][r]
    how = _turned_back if back else _turned
    return lambda t: how(t, cos, sin, interleaved)


def _q_fwd_kernel(x_ref, *refs, nope, interleaved):
    *tables, o_ref = refs       # cos and sin, or neither

    def body(r):
        turn = _turn(tables, r, interleaved)
        o_ref[0, 0, r, :nope] = x_ref[0, r, :nope]
        # [rope of the first head ; the second head's first 64 lanes]
        t = x_ref[0, r, nope:nope + _LANES].astype(jnp.float32)
        low = _low(t.shape)
        t = jnp.where(low, turn(t), t)
        o_ref[0, 0, r, nope:] = t[:, :_ROPE].astype(o_ref.dtype)
        # the second head: each lane block is the upper half of one
        # register beside the lower half of the next; its rope lanes are
        # the upper half of the last
        last = _swapped(t)
        for at in range(0, nope, _LANES):
            t = x_ref[0, r, nope + _LANES + at:nope + 2 * _LANES + at
                      ].astype(jnp.float32)
            if at + _LANES == nope:
                t = jnp.where(low, t, turn(t))
            t = _swapped(t)
            o_ref[0, 1, r, at:at + _LANES] = jnp.where(low, last, t).astype(
                o_ref.dtype)
            last = t
        o_ref[0, 1, r, nope:] = last[:, :_ROPE].astype(o_ref.dtype)

    _by_chunks(x_ref.shape[1], body)


def _q_bwd_kernel(dy_ref, *refs, nope, interleaved):
    *tables, dx_ref = refs

    def body(r):
        turn_back = _turn(tables, r, interleaved, back=True)

        def rope_back(head):
            return turn_back(
                _twice(dy_ref[0, head, r, nope:].astype(jnp.float32)))

        dx_ref[0, r, :nope] = dy_ref[0, 0, r, :nope]
        last = rope_back(0)
        low = _low(last.shape)
        for at in range(0, nope, _LANES):
            t = _swapped(dy_ref[0, 1, r, at:at + _LANES].astype(jnp.float32))
            dx_ref[0, r, nope + at:nope + at + _LANES] = jnp.where(
                low, last, t).astype(dx_ref.dtype)
            last = t
        dx_ref[0, r, 2 * nope:] = jnp.where(
            low, last, rope_back(1)).astype(dx_ref.dtype)

    _by_chunks(dx_ref.shape[1], body)


def _kv_fwd_kernel(kv_ref, kr_ref, *refs, nope, interleaved):
    *tables, k_ref, v_ref = refs
    width = nope + v_ref.shape[-1]

    def body(r):
        rope = kr_ref[0, r]
        if tables:
            rope = _twice(rope.astype(jnp.float32))
            rope = _turn(tables, r, interleaved)(rope)[:, :_ROPE].astype(
                k_ref.dtype)
        for head in range(2):
            k_ref[0, head, r, :nope] = kv_ref[0, r, head * width:
                                              head * width + nope]
            k_ref[0, head, r, nope:] = rope
            v_ref[0, head, r] = kv_ref[0, r, head * width + nope:
                                       (head + 1) * width]

    _by_chunks(kv_ref.shape[1], body)


def _kv_bwd_kernel(dk_ref, dv_ref, *refs, nope, interleaved):
    import jax.experimental.pallas as pl

    *tables, dkv_ref, dkr_ref, sum_ref = refs

    width, rows = nope + dv_ref.shape[-1], dkv_ref.shape[1]
    pair = pl.program_id(2)

    @pl.when(pair == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)

    def body(r):
        for head in range(2):
            dkv_ref[0, r, head * width:head * width + nope] = \
                dk_ref[0, head, r, :nope]
            dkv_ref[0, r, head * width + nope:(head + 1) * width] = \
                dv_ref[0, head, r]
        sum_ref[r] += (dk_ref[0, 0, r, nope:].astype(jnp.float32)
                       + dk_ref[0, 1, r, nope:].astype(jnp.float32))

    _by_chunks(rows, body)

    @pl.when(pair == pl.num_programs(2) - 1)
    def _():
        def store(r):
            total = sum_ref[r]
            if tables:
                total = _twice(total)
                total = _turn(tables, r, interleaved, back=True)(
                    total)[:, :_ROPE]
            dkr_ref[0, r] = total.astype(dkr_ref.dtype)

        _by_chunks(rows, store)


def _specs(sig):
    """The blocks the four kernels share, all on the grid (batch, row
    tile, pair of heads) with the pair fastest, so that a table's block
    and k_rope's stay while it runs: a flat one (two heads' lanes of a
    projection's layout) and a head-major one by a head's width, k_rope's
    and a table's."""
    import jax.experimental.pallas as pl

    rows = sig.rows

    def flat(width):
        return pl.BlockSpec((1, rows, 2 * width), lambda b, r, p: (b, r, p))

    def major(width):
        return pl.BlockSpec((1, 2, rows, width), lambda b, r, p: (b, p, r, 0))

    return (flat, major,
            pl.BlockSpec((1, rows, _ROPE), lambda b, r, p: (b, r, 0)),
            pl.BlockSpec((rows, _LANES), lambda b, r, p: (r, 0)))


def _grid(sig, b, s_len):
    return (b, -(-s_len // sig.rows), sig.heads // 2)


def _heads_fwd_call(sig, q, kv, k_rope, cos, sin):
    b, s_len, _ = q.shape
    heads, nope, v = sig.heads, sig.nope, sig.v
    flat, major, shared, table = _specs(sig)
    grid = _grid(sig, b, s_len)

    def head_major(width):
        return jax.ShapeDtypeStruct((b, heads, s_len, width), q.dtype)

    kernel = dict(nope=nope, interleaved=sig.interleaved)
    # a layer without positions: no table among the operands
    tables = (cos, sin) if sig.rotate else ()
    q_out = _pallas_call(
        functools.partial(_q_fwd_kernel, **kernel), name="mla_heads_q_fwd",
        grid=grid, in_specs=[flat(nope + _ROPE)] + [table] * len(tables),
        out_specs=major(nope + _ROPE), out_shape=head_major(nope + _ROPE),
        interpret=sig.interpret,
    )(q, *tables)
    k_out, v_out = _pallas_call(
        functools.partial(_kv_fwd_kernel, **kernel), name="mla_heads_kv_fwd",
        grid=grid,
        in_specs=[flat(nope + v), shared] + [table] * len(tables),
        out_specs=[major(nope + _ROPE), major(v)],
        out_shape=[head_major(nope + _ROPE), head_major(v)],
        interpret=sig.interpret,
    )(kv, k_rope, *tables)
    return q_out, k_out, v_out


def _heads_bwd_call(sig, dq, dk, dv, cos, sin):
    from jax.experimental.pallas import tpu as pltpu

    b, heads, s_len, _ = dq.shape
    nope, v = sig.nope, sig.v
    flat, major, shared, table = _specs(sig)
    grid = _grid(sig, b, s_len)

    def projected(width):
        return jax.ShapeDtypeStruct((b, s_len, width), dq.dtype)

    kernel = dict(nope=nope, interleaved=sig.interleaved)
    tables = (cos, sin) if sig.rotate else ()
    dx = _pallas_call(
        functools.partial(_q_bwd_kernel, **kernel), name="mla_heads_q_bwd",
        grid=grid, in_specs=[major(nope + _ROPE)] + [table] * len(tables),
        out_specs=flat(nope + _ROPE),
        out_shape=projected(heads * (nope + _ROPE)), interpret=sig.interpret,
    )(dq, *tables)
    dkv, dk_rope = _pallas_call(
        functools.partial(_kv_bwd_kernel, **kernel), name="mla_heads_kv_bwd",
        grid=grid,
        in_specs=[major(nope + _ROPE), major(v)] + [table] * len(tables),
        out_specs=[flat(nope + v), shared],
        out_shape=[projected(heads * (nope + v)), projected(_ROPE)],
        scratch_shapes=[pltpu.VMEM((sig.rows, _ROPE), jnp.float32)],
        interpret=sig.interpret,
    )(dk, dv, *tables)
    return dx, dkv, dk_rope


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _assembled(q, kv, k_rope, cos, sin, sig):
    # one traced and lowered copy of the kernels a signature (`_shared`):
    # the layers of a model share it
    return _shared(_heads_fwd_call, sig)(q, kv, k_rope, cos, sin)


def _assembled_fwd(q, kv, k_rope, cos, sin, sig):
    # the op is linear in q, kv and k_rope: its backward reads the
    # cotangents and the tables alone
    return _assembled(q, kv, k_rope, cos, sin, sig), (cos, sin)


def _assembled_bwd(sig, res, cotangents):
    cos, sin = res
    dx, dkv, dk_rope = _shared(_heads_bwd_call, sig)(*cotangents, cos, sin)
    # the tables come from integer positions: nothing flows back to them
    return (dx, dkv, dk_rope, None if cos is None else jnp.zeros_like(cos),
            None if sin is None else jnp.zeros_like(sin))


_assembled.defvjp(_assembled_fwd, _assembled_bwd)


def _composition(q, kv, k_rope, positions, theta, num_heads, interleaved):
    """The XLA ops one after the other: what the kernels replace, and the
    op's reference (with ``interleaved`` to `rotary_embedding`'s lane
    order)."""
    b, s_len, _ = q.shape
    rope = k_rope.shape[-1]
    q = q.reshape((b, s_len, num_heads, -1))
    nope = q.shape[-1] - rope
    kv = kv.reshape((b, s_len, num_heads, -1))

    def turned(t):
        if positions is None:
            return t
        return _nn.rotary_embedding(t, positions.reshape((s_len, 1)), theta,
                                    interleaved=interleaved)

    q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])], axis=-1)
    k_rope = jnp.broadcast_to(turned(k_rope[:, :, None, :]),
                              (b, s_len, num_heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    return tuple(t.transpose((0, 2, 1, 3)) for t in (q, k, kv[..., nope:]))


@register_op("mla_heads")
def mla_heads(q, kv, k_rope, positions, theta=10000.0, num_heads=1,
              interleaved=False):
    """Latent attention's projections as the heads the flash kernel reads:
    the rotary part turned, the shared key part given to every head, and
    the move to the head-major layout, as one op.

    q: (B, S, num_heads * (nope + rope)), every head's [q_nope ; q_rope];
    kv: (B, S, num_heads * (nope + v)), every head's [k_nope ; v];
    k_rope: (B, S, rope), one for all heads; ``positions``: the S position
    ids, shared by the batch, or None for a layer that carries no positions
    (``turn`` below is then the identity: the rotation is compiled out of
    the same kernels and of the composition, no table is among their
    operands, and ``theta`` and ``interleaved`` say nothing).  Returns q and k (B, num_heads, S, nope +
    rope) and v (B, num_heads, S, v) in q's type:

        q_h = [q_nope_h ; turn(q_rope_h)]
        k_h = [k_nope_h ; turn(k_rope)]
        v_h = v_h

    ``turn`` is `rotary_embedding`'s rotation with its angles, in
    float32, rounded once.  **The order of the rope lanes:** without
    ``interleaved`` lanes (i, i + rope / 2) turn as a pair by frequency i
    and stay where they are.  With it lanes (2i, 2i + 1) do, and come
    out at (2i, 2i + 1) from the kernels and at (i, i + rope / 2), where
    `rotary_embedding` leaves them, from the composition; q and k share
    the order either way, which is all a score q . k depends on.

    On a TPU, for S a multiple of 8, nope and v multiples of 128, rope 64
    and an even number of heads, two Pallas kernels do it (q; k and v),
    each tensor read once and written once, and the backward is two
    kernels of the same grids (dk_rope the float32 sum over the heads,
    turned back and rounded once); the layers of a model share one
    lowered copy of each.  Anywhere else (an odd number of heads, rope 96,
    S = 12, no TPU) the composition of XLA ops runs.  The gauge
    ``mla_heads_kernel_share`` says which share of the traced call sites
    took the kernels."""
    from ..telemetry import instruments as _telemetry

    b, s_len, width = q.shape
    rope = k_rope.shape[-1]
    nope = width // num_heads - rope
    v = kv.shape[-1] // num_heads - nope
    if (nope <= 0 or v <= 0 or width != num_heads * (nope + rope)
            or kv.shape != (b, s_len, num_heads * (nope + v))
            or k_rope.shape != (b, s_len, rope) or rope % 2
            or (positions is not None and positions.size != s_len)):
        raise ValueError(
            f"q {q.shape}, kv {kv.shape}, k_rope {k_rope.shape} as "
            f"{num_heads} heads, "
            f"{None if positions is None else positions.size} positions: "
            "q's last "
            "dimension is num_heads heads of nope + rope, kv's num_heads "
            "heads of nope + v, k_rope's an even rope, and there is a "
            "position a row")
    interpret = _kernel_mode()
    kernels = (interpret is not None and s_len % 8 == 0 and rope == _ROPE
               and nope % _LANES == 0 and v % _LANES == 0
               and num_heads % 2 == 0)
    _telemetry.record_mla_heads_site(kernels)
    if not kernels:
        return _composition(q, kv, k_rope, positions, theta, num_heads,
                            interleaved)
    rotate = positions is not None
    interleaved = bool(interleaved) and rotate
    cos, sin = _tables(positions, theta, interleaved) if rotate \
        else (None, None)
    sig = _Sig(int(num_heads), nope, v,
               _row_tile(s_len, nope, v, jnp.dtype(q.dtype).itemsize),
               interleaved, interpret, rotate)
    return _assembled(q, kv, k_rope, cos, sin, sig)
