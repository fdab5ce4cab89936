"""Flash attention as a Pallas TPU kernel.

The hot op of the transformer family (BERT zoo, ring/Ulysses sequence
parallelism): fused QK^T → online-softmax → PV with O(S) memory instead of
materializing the (S, S) score matrix in HBM. Reference framework analog:
the fused attention the reference lacked (its transformer era predated it);
TPU design per /opt/skills/guides/pallas_guide.md — q blocks stay resident
in VMEM, k/v blocks stream through the grid's inner dimension, the MXU sees
(block_q, d) x (d, block_k) matmuls, and the online-softmax running max /
sum live in VMEM scratch across the inner grid steps.

`flash_attention` is differentiable via custom_vjp with a block-streamed
Pallas backward (FlashAttention-2): the forward saves only (out, lse);
backward recomputes P tiles per block from (q, k, lse), so training is
O(S) memory end to end — dQ accumulates over streaming K/V blocks, dK/dV
over streaming Q blocks, and delta = rowsum(dO*O) supplies the softmax
correction.

Off a TPU the jnp reference implementation runs instead (tests run the
kernel in interpret mode for numerics); on a TPU the kernel runs and a
compile failure raises.
"""
from __future__ import annotations

import functools
import math
import warnings

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["flash_attention", "attention_reference", "SAVED_BY_NAME"]

# names (jax.ad_checkpoint.checkpoint_name) of the forward kernel's output
# and logsumexp among the residuals of `flash_attention`'s backward
SAVED_BY_NAME = ("flash_attention_out", "flash_attention_lse")


def _pallas_call(kernel, *, name, **kwargs):
    """`pl.pallas_call` under a stable `name` (what a device trace and
    the HLO show instead of `jvp__.N`), whose kernel body and BlockSpec
    index maps trace with x64 off.  The package turns `jax_enable_x64`
    on for user arrays (the 64-bit dtype contract); under it a Python
    `0` in an index map is an i64, which Mosaic refuses to legalize.
    Kernel operands are bf16/f32/int32, so nothing 64-bit crosses this
    boundary."""
    import jax.experimental.pallas as pl

    def call(*operands):
        with jax.enable_x64(False):
            return pl.pallas_call(kernel, name=name, **kwargs)(*operands)

    return call


_H1 = 0x9E3779B1
_H2 = 0x85EBCA6B
_H3 = 0xC2B2AE35


def _dropout_keep(seed, bh, q_pos, k_pos, dropout_p):
    """Deterministic per-element keep mask: murmur3-finalizer counter
    hash of (seed, batch·head, global q position, global k position).

    Pure uint32 jnp arithmetic, so the SAME mask materializes inside
    Pallas kernel tiles (fwd and both bwd passes), in interpret mode,
    and on the full matrix of the jnp reference path — dropout is
    exactly reproducible across all of them."""
    h = (q_pos.astype(jnp.uint32) * jnp.uint32(_H1)
         + k_pos.astype(jnp.uint32) * jnp.uint32(_H2)
         + jnp.asarray(seed).astype(jnp.uint32)
         + jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(_H3))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_H2)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_H3)
    h = h ^ (h >> 16)
    thresh = jnp.uint32(max(int((1.0 - dropout_p) * 4294967296.0) - 1, 0))
    return h <= thresh


_BIG = 2 ** 30


def _mask_codes(causal, block_diffusion, s_len, valid_len=None):
    """The mask as two static codes per position, or None when nothing
    is masked: ``(qc, kc)``, int32 arrays of shape (s_len, 2) and
    (2, s_len), with

        keep[i, j] = (kc[0, j] == qc[i, 0]) | (kc[1, j] <= qc[i, 1])

    One rule for every mask the kernels know, evaluated from a (block_q,
    2) column and a (2, block_k) row per tile:

    * padding (``valid_len``): a padded key's codes match no query;
    * ``causal``: the threshold term alone, position against position;
    * ``block_diffusion=(B, L)``, the vectorised training mask of block
      diffusion (Arriola et al., arXiv:2503.09573) over the 2L positions
      [noisy ; clean] with block b(i) = (i mod L) // B: noisy->noisy iff
      same block (the equality term), noisy->clean iff b(j) < b(i),
      clean->clean iff b(j) <= b(i) (the threshold term), clean->noisy
      never.  Every query keeps its own block, so no row is empty.
    """
    import numpy as onp

    pos = onp.arange(s_len)
    valid = pos < (s_len if valid_len is None else valid_len)
    never_q, never_k = onp.full(s_len, -1), onp.full(s_len, -2)
    if block_diffusion is not None:
        if causal:
            raise ValueError("causal and block_diffusion exclude each other")
        blen, half = (int(x) for x in block_diffusion)
        if (s_len if valid_len is None else valid_len) != 2 * half:
            raise ValueError(
                f"block_diffusion=(block {blen}, half {half}) needs a "
                f"sequence of {2 * half} positions [noisy ; clean]")
        noisy = pos < half
        blk = onp.where(noisy, pos, pos - half) // blen
        q_eq = onp.where(noisy, blk, -1)
        q_thr = onp.where(noisy, blk - 1, blk)
        k_eq = onp.where(noisy & valid, blk, -2)
        k_thr = onp.where(~noisy & valid, blk, _BIG)
    elif causal:
        q_eq, q_thr, k_eq = never_q, pos, never_k
        k_thr = onp.where(valid, pos, _BIG)
    elif valid_len is not None:
        q_eq, q_thr, k_eq = never_q, onp.zeros(s_len, int), never_k
        k_thr = onp.where(valid, 0, _BIG)
    else:
        return None
    return (onp.stack([q_eq, q_thr], 1).astype(onp.int32),
            onp.stack([k_eq, k_thr], 0).astype(onp.int32))


def _keep(qc, kc, use_eq=True):
    """The rule of ``_mask_codes`` on (n, 2) query and (2, m) key codes
    (jnp or numpy): an (n, m) boolean."""
    keep = kc[1:2, :] <= qc[:, 1:2]
    if use_eq:
        keep = keep | (kc[0:1, :] == qc[:, 0:1])
    return keep


def _tile_schedule(codes, nq, nk, block_q, block_k, by_key=False):
    """The live (q tile, k tile) pairs of a mask as one int32 per grid
    step, in q-major order (forward, dQ) or k-major (dK/dV): bits 17..
    the q tile, bits 2..16 the k tile, bit 1 set on the first pair of
    its row (column), bit 0 on the last.  A tile the mask empties is not
    in the list, so the grid never visits it; every row and every column
    keeps at least one pair, so every output block is written."""
    import numpy as onp

    if nq >= 1 << 14 or nk >= 1 << 15:
        raise ValueError(f"too many tiles for the schedule: {nq} x {nk}")
    if codes is None:
        live = onp.ones((nq, nk), bool)
    else:
        qc = codes[0].astype(onp.int64).reshape(nq, block_q, 2)
        kc = codes[1].astype(onp.int64).reshape(2, nk, block_k)
        q_eq, k_eq = qc[:, :, 0], kc[0]
        q_lo = onp.where(q_eq >= 0, q_eq, _BIG).min(1)
        q_hi = onp.where(q_eq >= 0, q_eq, -_BIG).max(1)
        k_lo = onp.where(k_eq >= 0, k_eq, _BIG).min(1)
        k_hi = onp.where(k_eq >= 0, k_eq, -_BIG).max(1)
        live = ((k_lo[None] <= q_hi[:, None]) & (k_hi[None] >= q_lo[:, None])
                | (kc[1].min(1)[None] <= qc[:, :, 1].max(1)[:, None]))
        live[:, 0] |= ~live.any(1)
        live[0, :] |= ~live.any(0)
    qi, kj = onp.nonzero(live.T)[::-1] if by_key else onp.nonzero(live)
    major = kj if by_key else qi
    edge = onp.concatenate([[True], major[1:] != major[:-1], [True]])
    return ((qi << 17) | (kj << 2) | (edge[:-1] << 1) | edge[1:]).astype(
        onp.int32)


def _head_div(bh, group):
    """Row of the key-value head that query-head row ``bh`` reads."""
    return bh if group == 1 else jax.lax.div(bh, jnp.int32(group))


def _tiles_of(e):
    """(q tile, k tile) of one word of the schedule."""
    return (jax.lax.shift_right_logical(e, 17),
            jax.lax.shift_right_logical(e, 2) & 0x7FFF)


def _step_of(sched_ref, t):
    """(q tile, k tile, first of its row, last of its row) of grid step
    ``t``: scalar arithmetic on one prefetched word."""
    e = sched_ref[t]
    return _tiles_of(e) + ((e & 2) != 0, (e & 1) != 0)


def attention_reference(q, k, v, causal=False, scale=None,
                        dropout_p=0.0, dropout_seed=None,
                        block_diffusion=None):
    """Plain jnp attention (the numeric oracle + off-TPU fallback).
    q: (B, H, S, D); k: (B, Hkv, S, D); v: (B, Hkv, S, Dv) with H a
    multiple of Hkv (query head h reads key-value head h // (H // Hkv));
    the result is (B, H, S, Dv).  ``block_diffusion`` as in
    `flash_attention`.  dropout uses the same counter-hash mask as
    the Pallas kernel, applied to the normalized probabilities
    (numerator only, inverted scaling) — bit-identical semantics to the
    kernel."""
    b, h, s, d = q.shape
    group = h // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k.astype(q.dtype)) * scale
    codes = _mask_codes(causal, block_diffusion, s)
    if codes is not None:
        scores = jnp.where(_keep(*codes), scores, -jnp.inf)
    p = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    if dropout_p > 0.0:
        bh = jnp.arange(b * h, dtype=jnp.int32).reshape(b, h, 1, 1)
        q_pos = jnp.arange(s, dtype=jnp.int32).reshape(1, 1, s, 1)
        k_pos = jnp.arange(s, dtype=jnp.int32).reshape(1, 1, 1, s)
        keep = _dropout_keep(dropout_seed, bh, q_pos, k_pos, dropout_p)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


def _scores(q, k, qc_ref, kc_ref, scale, masked, use_eq):
    """One tile of QK^T * scale with the mask's dead pairs at -inf."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (block_q, block_k)
    if masked:
        s = jnp.where(_keep(qc_ref[...], kc_ref[...], use_eq), s, -jnp.inf)
    return s


def _tile_keep(seed_ref, bh, q_idx, kv_idx, block_q, block_k, shape,
               dropout_p):
    """The dropout keep mask of one tile, the same in all three
    kernels."""
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return _dropout_keep(seed_ref[0], bh, q_pos, k_pos, dropout_p)


def _fwd_kernel(seed_ref, sched_ref, q_ref, k_ref, v_ref, qc_ref, kc_ref,
                o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale, masked, use_eq, block_q, block_k, dropout_p=0.0):
    import jax.experimental.pallas as pl

    # hoisted: program_id inside pl.when bodies breaks interpret mode
    bh_idx = pl.program_id(0)
    q_idx, kv_idx, first, last = _step_of(sched_ref, pl.program_id(1))

    @pl.when(first)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    v = v_ref[0]
    s = _scores(q_ref[0], k_ref[0], qc_ref, kc_ref, scale, masked, use_eq)

    m_prev = m_ref[:]                                # (block_q, 1)
    l_prev = l_ref[:]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard rows with nothing live so far (a tile the mask half empties)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(m_new), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    # dropout masks the numerator only (the softmax denominator l stays
    # un-dropped): out = Σ M·p·v / (l·(1−p)) — FlashAttention dropout
    p_v = p
    if dropout_p > 0.0:
        keep = _tile_keep(seed_ref, bh_idx, q_idx, kv_idx, block_q,
                          block_k, p.shape, dropout_p)
        p_v = jnp.where(keep, p, 0.0)
    acc = acc_ref[:] * alpha + jax.lax.dot_general(
        p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new
    l_ref[:] = l_new
    acc_ref[:] = acc

    @pl.when(last)
    def _finish():
        denom = jnp.maximum(l_ref[:], 1e-30)
        # lse records the TRUE softmax normalizer (backward recomputes
        # p̂ from it); only the output division carries the inverted
        # dropout scale
        o_denom = denom * (1.0 - dropout_p) if dropout_p > 0.0 else denom
        o_ref[0] = (acc_ref[:] / o_denom).astype(o_ref.dtype)
        # logsumexp per row: m + log l (-inf for fully-masked rows).
        # Stored as a (block_q, 1) column — the trailing singleton keeps
        # the block's last two dims (block_q, 1) legal for Mosaic tiling
        # (block_q % 8 == 0; 1 == array dim), where a 2-D (1, block_q)
        # block is not (sublane dim 1 is neither 8-aligned nor full).
        lse_ref[0] = jnp.where(jnp.isfinite(m_ref[:]),
                               m_ref[:] + jnp.log(denom), -jnp.inf)


def _seed_arr(dropout_seed):
    if dropout_seed is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(dropout_seed, jnp.int32).reshape((1,))


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


class _Plan:
    """What the three kernels share for one call: shapes, tile sizes,
    the mask's codes and the BlockSpecs that follow the tile schedule.
    An index map gets the grid indices, then the two prefetched scalar
    operands (dropout seed, schedule)."""

    def __init__(self, q, k, v, causal, block_q, block_k, valid_len,
                 block_diffusion):
        import numpy as onp

        b, h, s_len, dk = q.shape
        self.group = h // k.shape[1]
        if h != self.group * k.shape[1]:
            raise ValueError(f"{h} query heads over {k.shape[1]} key-value "
                             "heads: not a multiple")
        self.bh, self.bkv, self.s_len = b * h, b * k.shape[1], s_len
        # queries and keys share one width, values and the output another
        self.dk, self.dv = dk, v.shape[-1]
        self.block_q, self.block_k = min(block_q, s_len), min(block_k, s_len)
        self.nq, self.nk = s_len // self.block_q, s_len // self.block_k
        codes = _mask_codes(causal, block_diffusion, s_len, valid_len)
        self.masked = codes is not None
        self.use_eq = block_diffusion is not None
        self._codes = codes
        self.codes = codes if codes is not None else (
            onp.zeros((s_len, 2), onp.int32), onp.zeros((2, s_len), onp.int32))

    def schedule(self, by_key=False):
        return jnp.asarray(_tile_schedule(
            self._codes, self.nq, self.nk, self.block_q, self.block_k,
            by_key))

    def flat(self, x, kv=False):
        return x.reshape(self.bkv if kv else self.bh, self.s_len, -1)

    def specs(self, head_of):
        """(q-sized block, k-sized block, q codes, k codes) BlockSpecs
        for a grid whose axis 1 walks the schedule; ``head_of(*grid
        indices)`` gives (query head row, key-value head row).  The first
        two take the block's width."""
        import jax.experimental.pallas as pl

        def q_of(ids, sched):
            return _tiles_of(sched[ids[1]])[0]

        def k_of(ids, sched):
            return _tiles_of(sched[ids[1]])[1]

        def q_tile(width):
            return pl.BlockSpec(
                (1, self.block_q, width),
                lambda *a: (head_of(*a[:-2])[0], q_of(a, a[-1]), 0))

        def k_tile(width):
            return pl.BlockSpec(
                (1, self.block_k, width),
                lambda *a: (head_of(*a[:-2])[1], k_of(a, a[-1]), 0))

        return (q_tile, k_tile,
                pl.BlockSpec((self.block_q, 2),
                             lambda *a: (q_of(a, a[-1]), 0)),
                pl.BlockSpec((2, self.block_k),
                             lambda *a: (0, k_of(a, a[-1]))))

    def call(self, kernel, name, grid, in_specs, out_specs, out_shape,
             scratch, interpret, **static):
        from jax.experimental.pallas import tpu as pltpu

        return _pallas_call(
            functools.partial(
                kernel, masked=self.masked, use_eq=self.use_eq,
                block_q=self.block_q, block_k=self.block_k, **static),
            name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            out_shape=out_shape, interpret=interpret)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               valid_len=None, dropout_p=0.0, dropout_seed=None,
               block_diffusion=None):
    plan = _Plan(q, k, v, causal, block_q, block_k, valid_len,
                 block_diffusion)
    group, dk, dv = plan.group, plan.dk, plan.dv
    rows = plan.schedule()
    q_tile, k_tile, q_code, k_code = plan.specs(
        lambda b, t: (b, _head_div(b, group)))
    out, lse = plan.call(
        _fwd_kernel, "flash_attention_fwd",
        grid=(plan.bh, len(rows)),
        in_specs=[q_tile(dk), k_tile(dk), k_tile(dv), q_code, k_code],
        out_specs=[q_tile(dv), q_tile(1)],
        out_shape=[
            jax.ShapeDtypeStruct((plan.bh, plan.s_len, dv), q.dtype),
            jax.ShapeDtypeStruct((plan.bh, plan.s_len, 1), jnp.float32),
        ],
        scratch=[
            _scratch((plan.block_q, 1)),   # running max m
            _scratch((plan.block_q, 1)),   # running sum l
            _scratch((plan.block_q, dv)),  # output accumulator
        ],
        interpret=interpret, scale=scale, dropout_p=dropout_p,
    )(_seed_arr(dropout_seed), rows, plan.flat(q),
      plan.flat(k, True), plan.flat(v, True), *plan.codes)
    return out.reshape(q.shape[:-1] + (dv,)), lse[..., 0]


def _recompute_p(q, k, lse_col, qc_ref, kc_ref, scale, masked, use_eq):
    """exp(QK^T * scale - lse) for one (q block, k block) tile.
    lse_col: (block_q, 1) column (see _finish in _fwd_kernel)."""
    s = _scores(q, k, qc_ref, kc_ref, scale, masked, use_eq)
    return jnp.where(jnp.isfinite(lse_col), jnp.exp(s - lse_col), 0.0)


def _bwd_dq_kernel(seed_ref, sched_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, qc_ref, kc_ref, dq_ref, dq_acc, *,
                   scale, masked, use_eq, block_q, block_k, dropout_p=0.0):
    import jax.experimental.pallas as pl

    bh_idx = pl.program_id(0)
    q_idx, kv_idx, first, last = _step_of(sched_ref, pl.program_id(1))

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    p = _recompute_p(q_ref[0], k_ref[0], lse_ref[0], qc_ref, kc_ref,
                     scale, masked, use_eq)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (bq, bk)
    if dropout_p > 0.0:
        # dP̂ = M/(1−p)·(dO V^T); delta already equals
        # rowsum(P̂∘dP̂) because delta = rowsum(dO∘O)
        keep = _tile_keep(seed_ref, bh_idx, q_idx, kv_idx,
                          block_q, block_k, p.shape, dropout_p)
        dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout_p)
    ds = p * (dp - delta_ref[0]) * scale
    dq_acc[:] += jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, sched_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, qc_ref, kc_ref, dk_ref, dv_ref,
                    dk_acc, dv_acc, *, scale, masked, use_eq, block_q,
                    block_k, group, dropout_p=0.0):
    import jax.experimental.pallas as pl

    # grid: (key-value head, live pair in k-major order, query head of
    # the group): the pairs of one k tile and, inside each, the query
    # heads that share this key-value head all add into one dK and dV
    g_idx = pl.program_id(2)
    bh_idx = pl.program_id(0) * group + g_idx
    q_idx, kv_idx, first, last = _step_of(sched_ref, pl.program_id(1))

    @pl.when(first & (g_idx == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    p = _recompute_p(q_ref[0], k_ref[0], lse_ref[0], qc_ref, kc_ref,
                     scale, masked, use_eq)
    if dropout_p > 0.0:
        keep = _tile_keep(seed_ref, bh_idx, q_idx, kv_idx,
                          block_q, block_k, p.shape, dropout_p)
        p_d = jnp.where(keep, p, 0.0) / (1.0 - dropout_p)
    else:
        keep = None
        p_d = p
    # dV += P_d^T dO (P_d = dropped+rescaled probs, what fwd used)
    dv_acc[:] += jax.lax.dot_general(
        p_d.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if dropout_p > 0.0:
        dp = jnp.where(keep, dp, 0.0) / (1.0 - dropout_p)
    ds = p * (dp - delta_ref[0]) * scale
    # dK += dS^T Q
    dk_acc[:] += jax.lax.dot_general(
        ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last & (g_idx == group - 1))
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
               interpret, valid_len=None, dropout_p=0.0,
               dropout_seed=None, block_diffusion=None):
    """Block-streamed FlashAttention-2 backward: O(S) memory, no (S, S)
    residual — P tiles are recomputed from (q, k, lse) per block (and
    the dropout keep mask from its counter hash)."""
    plan = _Plan(q, k, v, causal, block_q, block_k, valid_len,
                 block_diffusion)
    group, dk_, dv_ = plan.group, plan.dk, plan.dv
    qr, kr, vr = plan.flat(q), plan.flat(k, True), plan.flat(v, True)
    do, orr = plan.flat(g), plan.flat(out)
    # delta = rowsum(dO * O) — the softmax-grad correction term (with
    # dropout it still equals rowsum(P̂∘dP̂) since O = P_d V).
    # lse/delta ride as (bh, s_len, 1) columns so their (block_q, 1)
    # blocks satisfy Mosaic's last-two-dims tiling rule.
    delta = jnp.sum(do.astype(jnp.float32) * orr.astype(jnp.float32),
                    axis=-1)[..., None]             # (bh, s_len, 1)
    lse = lse.reshape(plan.bh, plan.s_len, 1)
    seed = _seed_arr(dropout_seed)

    rows = plan.schedule()
    q_tile, k_tile, q_code, k_code = plan.specs(
        lambda b, t: (b, _head_div(b, group)))
    dq = plan.call(
        _bwd_dq_kernel, "flash_attention_bwd_dq",
        grid=(plan.bh, len(rows)),
        in_specs=[q_tile(dk_), k_tile(dk_), k_tile(dv_), q_tile(dv_),
                  q_tile(1), q_tile(1), q_code, k_code],
        out_specs=q_tile(dk_),
        out_shape=jax.ShapeDtypeStruct((plan.bh, plan.s_len, dk_), q.dtype),
        scratch=[_scratch((plan.block_q, dk_))],
        interpret=interpret, scale=scale, dropout_p=dropout_p,
    )(seed, rows, qr, kr, vr, do, lse, delta, *plan.codes)

    by_key = plan.schedule(by_key=True)
    q_tile, k_tile, q_code, k_code = plan.specs(
        lambda b, t, j: (b * group + j, b))
    dk, dv = plan.call(
        _bwd_dkv_kernel, "flash_attention_bwd_dkv",
        grid=(plan.bkv, len(by_key), group),
        in_specs=[q_tile(dk_), k_tile(dk_), k_tile(dv_), q_tile(dv_),
                  q_tile(1), q_tile(1), q_code, k_code],
        out_specs=[k_tile(dk_), k_tile(dv_)],
        out_shape=[
            jax.ShapeDtypeStruct((plan.bkv, plan.s_len, dk_), k.dtype),
            jax.ShapeDtypeStruct((plan.bkv, plan.s_len, dv_), v.dtype),
        ],
        scratch=[_scratch((plan.block_k, dk_)),
                 _scratch((plan.block_k, dv_))],
        interpret=interpret, scale=scale, dropout_p=dropout_p, group=group,
    )(seed, by_key, qr, kr, vr, do, lse, delta, *plan.codes)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, seed, causal, scale, block_q, block_k, interpret,
           dropout_p=0.0, valid_len=None, block_diffusion=None):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, valid_len, dropout_p, seed,
                        block_diffusion)
    return out


def _flash_vjp_fwd(q, k, v, seed, causal, scale, block_q, block_k,
                   interpret, dropout_p=0.0, valid_len=None,
                   block_diffusion=None):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret, valid_len, dropout_p, seed,
                          block_diffusion)
    # named, so that a checkpoint policy can keep the kernel's two results
    # (SAVED_BY_NAME) and not run the forward kernel a second time
    out = checkpoint_name(out, SAVED_BY_NAME[0])
    lse = checkpoint_name(lse, SAVED_BY_NAME[1])
    return out, (q, k, v, seed, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, dropout_p,
                   valid_len, block_diffusion, res, g):
    import numpy as _onp

    q, k, v, seed, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q,
                            block_k, interpret, valid_len, dropout_p,
                            seed, block_diffusion)
    # integer seed takes a float0 cotangent
    return dq, dk, dv, _onp.zeros(seed.shape, jax.dtypes.float0)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@register_op("flash_attention")
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=None, dropout_p=0.0,
                    dropout_seed=None, block_diffusion=None):
    """Fused multi-head attention: softmax(QK^T * scale + mask) V.

    q: (B, H, S, D); k: (B, Hkv, S, D); v: (B, Hkv, S, Dv), H a multiple
    of Hkv (grouped queries: query head h reads key-value head
    h // (H // Hkv), and the dK/dV kernel sums over the group).  Queries
    and keys share the width D, values and the result (B, H, S, Dv) the
    width Dv, and the two may differ (latent attention: 192 and 128):
    every tile, accumulator and gradient has the width of its tensor, so
    nothing is padded to the wider one.  The default scale is
    1 / sqrt(D).  Runs the Pallas kernel on TPU (or anywhere with
    interpret=True); off a TPU the jnp reference runs instead.  Ragged S
    is tile-padded and the kernel masks the padded keys (static
    `valid_len`).

    What the kernel cannot tile takes the reference path LOUDLY — the
    counter ``attention_kernel_fallback_total{reason}`` and a
    RuntimeWarning — wherever the kernel was asked for (on a TPU, or with
    interpret=True): ``reason="width"`` where D or Dv is not a multiple
    of 8 (a tile's rows would not be whole sublanes), ``reason="tile"``
    where ``block_q`` / ``block_k`` do not divide the padded length or
    are not multiples of 8.

    The mask is static: nothing, ``causal``, or ``block_diffusion=
    (block length B, half length L)`` for a sequence of S = 2L positions
    [noisy ; clean] (`_mask_codes` has the rule).  It is evaluated per
    tile from two small code vectors, and the tiles it empties are left
    out of the grid in the forward, dQ and dK/dV kernels alike.

    dropout_p > 0 with an int32 `dropout_seed` applies attention-prob
    dropout inside the kernel (numerator-masked, inverted scaling; the
    counter-hash mask regenerates identically in the backward kernels
    and the reference path — see _dropout_keep).
    """
    d = q.shape[-1]
    if k.shape[-1] != d or v.shape[:-1] != k.shape[:-1]:
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}: q and k share their "
            "last dimension, k and v every other")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    dropout_p = float(dropout_p)
    if block_diffusion is not None:
        block_diffusion = tuple(int(x) for x in block_diffusion)

    def _fallback(qq, kk, vv, reason=None):
        if reason is not None:
            _record_fallback(reason, q.shape, v.shape[-1], block_q, block_k)
        return attention_reference(qq, kk, vv, causal=causal, scale=scale,
                                   dropout_p=dropout_p,
                                   dropout_seed=dropout_seed,
                                   block_diffusion=block_diffusion)

    if interpret is None:
        interpret = False
        if jax.devices()[0].platform != "tpu":
            return _fallback(q, k, v)
    if d % 8 or v.shape[-1] % 8:
        return _fallback(q, k, v, "width")
    s_len = q.shape[2]
    s_pad = _tile_pad_len(s_len, block_q)
    bq = min(block_q, s_pad)
    bk = min(block_k, s_pad)
    if s_pad % bq or s_pad % bk or bq % 8 or bk % 8:
        return _fallback(q, k, v, "tile")
    seed = _seed_arr(dropout_seed)
    if s_pad == s_len:
        return _flash(q, k, v, seed, causal, scale, bq, bk, interpret,
                      dropout_p, None, block_diffusion)
    pad = [(0, 0), (0, 0), (0, s_pad - s_len), (0, 0)]
    out = _flash(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                 seed, causal, scale, bq, bk, interpret, dropout_p, s_len,
                 block_diffusion)
    return out[:, :, :s_len]


def _record_fallback(reason, q_shape, v_width, block_q, block_k):
    """The kernel was asked for and the plain reference runs: counted,
    and said."""
    from ..telemetry import instruments as _telemetry

    _telemetry.record_attention_fallback(reason)
    warnings.warn(
        f"flash_attention: q {tuple(q_shape)}, value width {v_width}, "
        f"blocks ({block_q}, {block_k}) cannot be tiled ({reason}); the "
        "plain reference, which materialises the scores, runs instead",
        RuntimeWarning, stacklevel=3)


def _tile_pad_len(s_len, block):
    """Smallest padded length that tiles: multiple of 8 below one block,
    multiple of the block size above."""
    if s_len >= block:
        return -(-s_len // block) * block
    return -(-s_len // 8) * 8
