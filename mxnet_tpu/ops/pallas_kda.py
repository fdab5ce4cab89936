"""Kimi Delta Attention's scan: a gated delta rule with a decay for every
channel, run chunk by chunk with a hand-written backward.

A head keeps a state S (d_k x d_v) through the sequence:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t                                    S_0 = 0

with g_t <= 0 a log-decay for every channel of d_k and beta_t one number
a head (`kda_recurrence` is that, position by position).  `kda_scan`
computes the same thing a chunk of C tokens at a time.  With G_t the sum of
g from the chunk's start to t, Gamma_t = exp(G_t), E_tj = exp(G_t - G_j)
(a vector over d_k) and S0 the state that enters the chunk:

    Akk_tj = sum_c k_tc k_jc E_tjc   (j < t)     A = Diag(beta) Akk
    P_tj   = sum_c q_tc k_jc E_tjc   (j <= t)
    U = (I + A)^-1 [beta * (V - (K * Gamma) S0)]
    o = scale * [(Q * Gamma) S0 + P U]
    S1 = Diag(Gamma_C) S0 + sum_j Diag(Gamma_C / Gamma_j) k_j u_j^T

**Every exponent is taken of a difference that is <= 0.**  exp(G_t) *
exp(-G_j) overflows at the decays the gate reaches, so E is never
factored through the chunk's start.  A pair t > j is given the reference
point between them that its binary position picks: with h the highest
bit in which t and j differ, t lies in the second half and j in the first
half of one aligned block of 2 h tokens, and

    E_tj = exp(sum of g over that second half up to t)        (a row factor)
         * exp(sum of g over the first half after j)          (a column factor)

Both sums hold only terms between j and t, so both factors are <= 1 and
their product is E_tj exactly: no clamp, no floor, and nothing cancels.
There are log2 C such levels; each is one matrix product of the scaled rows
with the scaled columns, masked to the pairs whose highest differing bit
is h.  The blocked prefix and suffix sums of every level come from one
butterfly over the chunk (`_scans`: log2 C steps of a roll and an add).

(I + A)^-1 is built by the same halving: the inverse of a block-diagonal
part T, and T <- T - T L T with L the level's off-diagonal blocks, in
float32.

**The backward** walks the chunks from the last to the first with dS, the
state's cotangent, as its carry.  It keeps q, k, v, g, beta and the
state that entered each chunk (float32: B * H * S / C * d_k * d_v * 4
bytes, the gauge ``kda_scan_kept_bytes``), computes the chunk's P, A, T
and U again and takes every gradient in closed form; the E-weighted
products turn back through the same levels, and dg is the suffix sum of
dG over the chunk.

On a TPU, for d_k and d_v multiples of 128, forward and backward are one
Pallas kernel each: the grid walks (batch, head, blocks of 8 chunks), the
last axis in order, the state float32 in fast memory across it, the
chunk's products on the MXU in the operands' type with float32
accumulation.  Off a TPU, and for widths the kernels cannot tile, the
same chunk functions run as a `lax.scan` of XLA ops (on a TPU: said once
in a warning, and counted).
"""
from __future__ import annotations

import collections
import functools
import warnings

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_attention import _LANES, _pallas_call, _shared
from .pallas_qk_prep import _kernel_mode
from .registry import register_op

__all__ = ["kda_scan", "kda_recurrence"]

F32 = jnp.float32
_HI = lax.Precision.HIGHEST

# chunks a grid step of the kernels walks: 8 rows of beta's lane-dense
# block
_CHUNKS = 8

# what the kernels of one call share besides their operands' shapes
_Sig = collections.namedtuple("_Sig", "chunk scale kernels interpret")


def _nn(a, b, precision=None):
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=F32, precision=precision)


def _nt(a, b, precision=None):
    """a b^T: (m, c), (n, c) -> (m, n)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=F32, precision=precision)


def _tn(a, b, precision=None):
    """a^T b: (r, m), (r, n) -> (m, n)."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=F32, precision=precision)


def _jnp_roll(x, shift):
    return jnp.roll(x, shift, axis=0)


def _tpu_roll(x, shift):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[0], 0)


def _levels(chunk):
    """Half-lengths of the blocks a chunk is halved into: 1, 2, .. C / 2."""
    return [1 << i for i in range(chunk.bit_length() - 1)]


def _scans(g, roll):
    """The blocked sums of g (C, d) along the chunk that the levels need:
    for each half-length h the inclusive prefix sums inside aligned blocks
    of h (what a row of a second half has decayed since that half began)
    and the exclusive suffix sums inside them (what is still to decay after
    a column until its first half ends); then the inclusive prefix and the
    exclusive suffix over the whole chunk.  One butterfly: ``total`` holds
    every block's sum at each of its positions."""
    chunk = g.shape[0]
    pos = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    prefix, suffix, total = g, jnp.zeros_like(g), g
    rows, cols = [], []
    for h in _levels(chunk):
        rows.append(prefix)
        cols.append(suffix)
        upper = (pos & h) != 0
        before, after = roll(total, h), roll(total, -h)
        prefix = prefix + jnp.where(upper, before, 0.0)
        suffix = suffix + jnp.where(upper, 0.0, after)
        total = total + jnp.where(upper, before, after)
    return rows, cols, prefix, suffix


def _suffix_sum(x, roll):
    """sum over t >= s of x_t, along the chunk."""
    return x + _scans(x, roll)[3]


def _pairs(chunk):
    """(row index, column index, row ^ column) of a (C, C) matrix."""
    t = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return t, j, t ^ j


def _level_mask(t, j, x, h):
    """The pairs t > j whose highest differing bit is h."""
    return (t > j) & (x >= h) & (x < 2 * h)


def _column(row, eye):
    """A (1, C) row as a (C, 1) column."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column, eye):
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _inverse(a, t, j, x):
    """(I + a)^-1 for a strictly lower (C, C) float32: the inverse of the
    diagonal blocks, doubled a level at a time."""
    inv = jnp.where(t == j, 1.0, 0.0)
    for h in _levels(a.shape[0]):
        low = jnp.where(_level_mask(t, j, x, h), a, 0.0)
        # the first level's blocks are the identity's: I L I is L
        inv = inv - (low if h == 1 else _nn(_nn(inv, low, _HI), inv, _HI))
    return inv


def _chunk_parts(q, k, v, g, beta, state, roll):
    """What forward and backward of a chunk share.  q, k (C, d_k), v
    (C, d_v) in the operands' type, g (C, d_k) float32, beta (1, C)
    float32, ``state`` (d_v, d_k) float32: S0 transposed."""
    chunk, mm = q.shape[0], q.dtype
    t, j, x = _pairs(chunk)
    eye = t == j
    rows, cols, g_sum, g_left = _scans(g, roll)
    q32, k32 = q.astype(F32), k.astype(F32)
    b_col = _column(beta, eye)
    levels = []
    p = jnp.where(eye, _nt(q, k), 0.0)
    akk = jnp.zeros((chunk, chunk), F32)
    for h, r_sum, c_sum in zip(_levels(chunk), rows, cols):
        rf, cf = jnp.exp(r_sum), jnp.exp(c_sum)
        scaled_rows = jnp.concatenate([q32 * rf, k32 * rf], 0).astype(mm)
        scaled_cols = (k32 * cf).astype(mm)
        mask = _level_mask(t, j, x, h)
        prod = _nt(scaled_rows, scaled_cols)
        p = jnp.where(mask, prod[:chunk], p)
        akk = jnp.where(mask, prod[chunk:], akk)
        levels.append((mask, rf, cf, scaled_rows, scaled_cols))
    inv = _inverse(b_col * akk, t, j, x)
    gamma = jnp.exp(g_sum)
    qg, kg = q32 * gamma, k32 * gamma
    left = jnp.exp(g_left)
    kd = k32 * left
    w0 = v.astype(F32) - _nt(kg.astype(mm), state.astype(mm))
    u = _nn(inv, b_col * w0, _HI)
    return dict(t=t, j=j, eye=eye, levels=levels, p=p, akk=akk, inv=inv,
                gamma=gamma, qg=qg, kg=kg, kd=kd, w0=w0, u=u, b_col=b_col,
                q32=q32, k32=k32, left=left, g_end=g_sum[chunk - 1:chunk])


def _chunk_fwd(q, k, v, g, beta, state, scale, roll):
    """(o (C, d_v) float32, the state that leaves the chunk)."""
    mm = q.dtype
    c = _chunk_parts(q, k, v, g, beta, state, roll)
    u = c["u"].astype(mm)
    o = scale * (_nt(c["qg"].astype(mm), state.astype(mm))
                 + _nn(c["p"].astype(mm), u))
    state = state * jnp.exp(c["g_end"]) + _tn(u, c["kd"].astype(mm))
    return o, state


def _chunk_bwd(q, k, v, g, beta, state, do, d_state, scale, roll):
    """The chunk's gradients (dq, dk, dv, dg, dbeta (1, C), the cotangent
    of the state that entered), float32, from ``do`` (C, d_v) and
    ``d_state`` (d_v, d_k), the cotangent of the state that left."""
    chunk, mm = q.shape[0], q.dtype
    c = _chunk_parts(q, k, v, g, beta, state, roll)
    t, j, eye, b_col = c["t"], c["j"], c["eye"], c["b_col"]
    q32, k32, gamma = c["q32"], c["k32"], c["gamma"]
    u, state_mm = c["u"].astype(mm), state.astype(mm)
    do = (scale * do.astype(F32)).astype(mm)
    d_state_mm = d_state.astype(mm)
    kd = c["kd"]

    du = _tn(c["p"].astype(mm), do) + _nt(kd.astype(mm), d_state_mm)
    dw = _tn(c["inv"], du, _HI)
    da = jnp.where(t > j, -_nt(dw.astype(mm), u), 0.0)
    dbeta = (jnp.sum(dw * c["w0"], axis=1, keepdims=True)
             + jnp.sum(da * c["akk"], axis=1, keepdims=True))
    dwb = b_col * dw
    dwb_mm = dwb.astype(mm)
    dkg = -_nn(dwb_mm, state_mm)
    dqg = _nn(do, state_mm)
    dkd = _nn(u, d_state_mm)
    gamma_end = jnp.exp(c["g_end"])
    d_entering = (d_state * gamma_end - _tn(dwb_mm, c["kg"].astype(mm))
                  + _tn(do, c["qg"].astype(mm)))
    dp = jnp.where(t >= j, _nt(do, u), 0.0)
    dakk = b_col * da

    # back through the products that E weighs, level by level
    dp_diag = jnp.sum(jnp.where(eye, dp, 0.0), axis=1, keepdims=True)
    dq = dp_diag * k32
    dk_row = jnp.zeros_like(k32)
    dk_col = dp_diag * q32
    for mask, rf, cf, scaled_rows, scaled_cols in c["levels"]:
        d_both = jnp.concatenate([jnp.where(mask, dp, 0.0),
                                  jnp.where(mask, dakk, 0.0)], 0).astype(mm)
        to_rows = _nn(d_both, scaled_cols)
        dq = dq + rf * to_rows[:chunk]
        dk_row = dk_row + rf * to_rows[chunk:]
        dk_col = dk_col + cf * _tn(d_both, scaled_rows)

    dg_sum = (q32 * dq + k32 * (dk_row - dk_col)
              + dqg * c["qg"] + dkg * c["kg"] - dkd * kd)
    at_end = (jnp.sum(dkd * kd, axis=0, keepdims=True)
              + jnp.sum(state * d_state, axis=0, keepdims=True) * gamma_end)
    last = lax.broadcasted_iota(jnp.int32, dg_sum.shape, 0) == chunk - 1
    dg = _suffix_sum(dg_sum + jnp.where(last, at_end, 0.0), roll)
    dq = dq + dqg * gamma
    dk = dk_row + dk_col + dkg * gamma + dkd * c["left"]
    return dq, dk, dwb, dg, _row(dbeta, eye), d_entering


def kda_recurrence(q, k, v, g, beta, scale=None):
    """The recurrence itself, one position at a time in float32: what
    `kda_scan` has to equal.  Shapes as `kda_scan`'s."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))

    def step(state, x):
        qt, kt, vt, gt, bt = x                      # (B, H, d) and (B, H)
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.einsum("bhk,bhkv->bhv", kt, state, precision=_HI)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - seen)[..., None, :]
        return state, scale * jnp.einsum("bhk,bhkv->bhv", qt, state,
                                         precision=_HI)

    b, _, h, dk = q.shape
    first = jnp.zeros((b, h, dk, v.shape[-1]), F32)
    _, out = lax.scan(step, first, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


# -- the composition: the chunk functions as a scan of XLA ops ------------

def _chunked(x, chunk):
    """(B, S, H, d) -> (S / C, B, H, C, d)."""
    b, s, h, d = x.shape
    return x.reshape((b, s // chunk, chunk, h, d)).transpose((1, 0, 3, 2, 4))


def _unchunked(x):
    n, b, h, chunk, d = x.shape
    return x.transpose((1, 0, 3, 2, 4)).reshape((b, n * chunk, h, d))


def _beta_rows(beta, chunk):
    """(B, S, H) -> (S / C, B, H, 1, C)."""
    b, s, h = beta.shape
    return beta.reshape((b, s // chunk, chunk, h)).transpose(
        (1, 0, 3, 2))[:, :, :, None, :]


def _over_heads(fn):
    return jax.vmap(jax.vmap(fn))


def _composition_fwd(sig, q, k, v, g, beta):
    chunk = sig.chunk
    one = _over_heads(functools.partial(_chunk_fwd, scale=sig.scale,
                                        roll=_jnp_roll))

    def step(state, x):
        o, left = one(*x, state)
        return left, (o, state)

    b, _, h, dk = q.shape
    first = jnp.zeros((b, h, v.shape[-1], dk), F32)
    _, (o, states) = lax.scan(step, first, (
        _chunked(q, chunk), _chunked(k, chunk), _chunked(v, chunk),
        _chunked(g, chunk), _beta_rows(beta, chunk)))
    return _unchunked(o).astype(v.dtype), states


def _composition_bwd(sig, q, k, v, g, beta, states, do):
    chunk = sig.chunk
    one = _over_heads(functools.partial(_chunk_bwd, scale=sig.scale,
                                        roll=_jnp_roll))

    def step(d_state, x):
        *operands, state, do_ = x
        dq, dk, dv, dg, db, d_state = one(*operands, state, do_, d_state)
        return d_state, (dq, dk, dv, dg, db)

    _, (dq, dk, dv, dg, db) = lax.scan(
        step, jnp.zeros_like(states[0]),
        (_chunked(q, chunk), _chunked(k, chunk), _chunked(v, chunk),
         _chunked(g, chunk), _beta_rows(beta, chunk), states,
         _chunked(do, chunk)), reverse=True)
    n, b, h = db.shape[:3]
    db = db[:, :, :, 0, :].transpose((1, 0, 3, 2)).reshape(
        (b, n * chunk, h))
    return (_unchunked(dq), _unchunked(dk), _unchunked(dv), _unchunked(dg),
            db)


# -- the kernels -----------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, s_ref, state, *,
                chunk, scale):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    def step(c, carry):
        r = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        entering = state[...]
        s_ref[0, 0, c] = entering
        o, left = _chunk_fwd(q_ref[0, r, :], k_ref[0, r, :], v_ref[0, r, :],
                             g_ref[0, r, :], b_ref[0, 0, pl.ds(c, 1), :],
                             entering, scale, _tpu_roll)
        o_ref[0, r, :] = o.astype(o_ref.dtype)
        state[...] = left
        return carry

    lax.fori_loop(0, b_ref.shape[2], step, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, db_ref, d_state, *, chunk, scale):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    chunks = b_ref.shape[2]

    def step(i, carry):
        c = chunks - 1 - i
        r = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        dq, dk, dv, dg, db, entering = _chunk_bwd(
            q_ref[0, r, :], k_ref[0, r, :], v_ref[0, r, :], g_ref[0, r, :],
            b_ref[0, 0, pl.ds(c, 1), :], s_ref[0, 0, c], do_ref[0, r, :],
            d_state[...], scale, _tpu_roll)
        dq_ref[0, r, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, r, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, r, :] = dv.astype(dv_ref.dtype)
        dg_ref[0, r, :] = dg
        db_ref[0, 0, pl.ds(c, 1), :] = db
        d_state[...] = entering
        return carry

    lax.fori_loop(0, chunks, step, None)


def _specs(sig, n_chunks, dk, dv, backward=False):
    """The blocks of a call on the grid (batch, head, block of chunks):
    a head's lanes of a (B, S, H * d) tensor, beta's rows (B, H, S / C,
    C) and the kept states (B, H, S / C, d_v, d_k); the backward walks
    the blocks from the last."""
    import jax.experimental.pallas as pl

    per = min(_CHUNKS, n_chunks)
    rows, steps = per * sig.chunk, n_chunks // per

    def order(i):
        return steps - 1 - i if backward else i

    def flat(d):
        return pl.BlockSpec((1, rows, d), lambda b, h, i: (b, order(i), h))

    rows_of_beta = pl.BlockSpec((1, 1, per, sig.chunk),
                                lambda b, h, i: (b, h, order(i), 0))
    states = pl.BlockSpec((1, 1, per, dv, dk),
                          lambda b, h, i: (b, h, order(i), 0, 0))
    return flat, rows_of_beta, states, steps


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


def _lane_dense(beta, chunk):
    """(B, S, H) -> (B, H, S / C, C): a chunk's betas as one row."""
    b, s, h = beta.shape
    return beta.transpose((0, 2, 1)).reshape((b, h, s // chunk, chunk))


def _kernel_fwd(sig, q, k, v, g, beta):
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, dk = q.shape
    dv, chunk = v.shape[-1], sig.chunk
    n = s // chunk
    flat, rows_of_beta, states, steps = _specs(sig, n, dk, dv)
    o, kept = _pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, scale=sig.scale),
        name="kda_scan_fwd", grid=(b, h, steps),
        in_specs=[flat(dk), flat(dk), flat(dv), flat(dk), rows_of_beta],
        out_specs=[flat(dv), states],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h, n, dv, dk), F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), F32)],
        interpret=sig.interpret,
    )(_flat(q), _flat(k), _flat(v), _flat(g), _lane_dense(beta, chunk))
    return o.reshape((b, s, h, dv)), kept


def _kernel_bwd(sig, q, k, v, g, beta, kept, do):
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, dk = q.shape
    dv, chunk = v.shape[-1], sig.chunk
    n = s // chunk
    flat, rows_of_beta, states, steps = _specs(sig, n, dk, dv,
                                                backward=True)

    def like(x, dtype=None):
        return jax.ShapeDtypeStruct(_flat(x).shape, dtype or x.dtype)

    dq, dk_, dv_, dg, db = _pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, scale=sig.scale),
        name="kda_scan_bwd", grid=(b, h, steps),
        in_specs=[flat(dk), flat(dk), flat(dv), flat(dk), rows_of_beta,
                  flat(dv), states],
        out_specs=[flat(dk), flat(dk), flat(dv), flat(dk), rows_of_beta],
        out_shape=[like(q), like(k), like(v), like(g, F32),
                   jax.ShapeDtypeStruct((b, h, n, chunk), F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), F32)],
        interpret=sig.interpret,
    )(_flat(q), _flat(k), _flat(v), _flat(g), _lane_dense(beta, chunk),
      _flat(do), kept)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), db.reshape((b, h, s)).transpose((0, 2, 1)))


# -- the op ----------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, sig):
    return _scan_fwd(q, k, v, g, beta, sig)[0]


def _scan_fwd(q, k, v, g, beta, sig):
    fwd = _kernel_fwd if sig.kernels else _composition_fwd
    # one traced and lowered copy a signature: the layers of a model share it
    o, kept = _shared(fwd, sig)(q, k, v, g, beta)
    return o, (q, k, v, g, beta, kept)


def _scan_bwd(sig, res, do):
    bwd = _kernel_bwd if sig.kernels else _composition_bwd
    dq, dk, dv, dg, db = _shared(bwd, sig)(*res, do)
    q, k, v, g, beta, _ = res
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dg.astype(g.dtype), db.astype(beta.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _record_fallback(reason, shape, dv, chunk):
    """The kernels were there and the composition runs: counted, and
    said."""
    warnings.warn(
        f"kda_scan: q {tuple(shape)}, value width {dv}, chunk {chunk} "
        f"cannot be tiled ({reason}); the composition of XLA ops, a scan "
        "over the chunks, runs instead of the kernels", RuntimeWarning,
        stacklevel=3)


@register_op("kda_scan")
def kda_scan(q, k, v, g, beta, scale=None, chunk=64):
    """Kimi Delta Attention's scan as one op: the gated delta rule with a
    decay for every channel, chunk by chunk, with a hand-written VJP.

    q, k: (B, S, H, d_k); v: (B, S, H, d_v); g: (B, S, H, d_k), the
    log-decay of every channel, **<= 0**, float32; beta: (B, S, H).
    Returns o (B, S, H, d_v) in v's type:

        S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = scale * S_t^T q_t              S_0 = 0, a state a head

    ``scale`` defaults to d_k ** -0.5; ``chunk`` is a power of two (the
    kernels: at least 8).  A length that is no multiple of the chunk is
    padded with g = 0, beta = 0, k = 0, which leaves the state as it is.
    No exponent of a positive number is ever taken (see the module's
    text), so any g <= 0 is safe, however strong the decay.

    The VJP gives dq, dk, dv, dg and dbeta.  It keeps the five operands
    and the float32 state that entered each chunk (the gauge
    ``kda_scan_kept_bytes``) and recomputes the rest.

    On a TPU, for d_k and d_v multiples of 128 and operands in one
    floating type, forward and backward are one Pallas kernel each
    (``kda_scan_fwd``, ``kda_scan_bwd``); the layers of a model share one
    lowered copy.  Elsewhere the same chunk arithmetic runs as a
    `lax.scan` of XLA ops: off a TPU silently, on one with a warning.
    The gauge ``kda_scan_calls{path}`` counts the traced call sites on
    either path, ``kda_scan_chunks`` the chunks of the last."""
    from ..telemetry import instruments as _telemetry

    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if (k.shape != q.shape or v.shape[:3] != q.shape[:3]
            or g.shape != q.shape or beta.shape != q.shape[:3]):
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta "
            f"{beta.shape}: q, k and g (B, S, H, d_k), v (B, S, H, d_v), "
            "beta (B, S, H)")
    chunk = int(chunk)
    if chunk < 2 or chunk & (chunk - 1):
        raise ValueError(f"chunk {chunk}: a power of two")
    scale = float(dk ** -0.5 if scale is None else scale)
    # the products' type: bfloat16 or float32 (float64 is float32 here)
    out_dtype = v.dtype
    mm = q.dtype if q.dtype in (jnp.bfloat16, jnp.float32) else F32
    q, k, v = q.astype(mm), k.astype(mm), v.astype(mm)
    g, beta = g.astype(F32), beta.astype(F32)

    interpret = _kernel_mode()
    kernels = interpret is not None
    if kernels:
        reason = ("width" if dk % _LANES or dv % _LANES
                  else "chunk" if chunk % 8 else None)
        if reason is not None:
            kernels = False
            _record_fallback(reason, q.shape, dv, chunk)
    # whole chunks, and whole blocks of chunks where there are several
    unit = chunk * _CHUNKS if kernels and s > chunk * _CHUNKS else chunk
    s_pad = -(-s // unit) * unit
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s)]
        q, k, v, g = (jnp.pad(x, pad + [(0, 0), (0, 0)])
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, pad + [(0, 0)])
    n_chunks = s_pad // chunk
    _telemetry.record_kda_scan(kernels, b * h * n_chunks,
                               4 * b * h * n_chunks * dk * dv)
    sig = _Sig(chunk, scale, kernels, bool(interpret))
    with jax.named_scope("kda.scan"):
        out = _scan(q, k, v, g, beta, sig)
    return out[:, :s].astype(out_dtype)
