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

import jax
import jax.numpy as jnp

from .registry import register_op

__all__ = ["flash_attention", "attention_reference"]


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


def attention_reference(q, k, v, causal=False, scale=None,
                        dropout_p=0.0, dropout_seed=None):
    """Plain jnp attention (the numeric oracle + off-TPU fallback).
    q/k/v: (B, H, S, D). dropout uses the same counter-hash mask as the
    Pallas kernel, applied to the normalized probabilities (numerator
    only, inverted scaling) — bit-identical semantics to the kernel."""
    b, h, s, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k.astype(q.dtype)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
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


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                l_ref, acc_ref, *,
                scale, causal, block_q, block_k, valid_len=None,
                dropout_p=0.0):
    import jax.experimental.pallas as pl

    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # (block_q, d)
    k = k_ref[0]                                     # (block_k, d)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (block_q, block_k)

    if causal or valid_len is not None:
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        keep = jnp.ones(s.shape, bool)
        if causal:
            q_idx = pl.program_id(1)
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            keep &= q_pos >= k_pos
        if valid_len is not None:
            # S was padded up to a tile multiple; padded keys are dead
            keep &= k_pos < valid_len
        s = jnp.where(keep, s, -jnp.inf)

    m_prev = m_ref[:]                                # (block_q, 1)
    l_prev = l_ref[:]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (causal blocks above the diagonal)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(m_new), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    # dropout masks the numerator only (the softmax denominator l stays
    # un-dropped): out = Σ M·p·v / (l·(1−p)) — FlashAttention dropout
    p_v = p
    if dropout_p > 0.0:
        q_idx = pl.program_id(1)
        q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
            jnp.int32, p.shape, 0)
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, p.shape, 1)
        keep = _dropout_keep(seed_ref[0], pl.program_id(0), q_pos, k_pos,
                             dropout_p)
        p_v = jnp.where(keep, p, 0.0)
    acc = acc_ref[:] * alpha + jax.lax.dot_general(
        p_v.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = m_new
    l_ref[:] = l_new
    acc_ref[:] = acc

    @pl.when(kv_idx == pl.num_programs(2) - 1)
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


def _smem_spec():
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               valid_len=None, dropout_p=0.0, dropout_seed=None):
    import jax.experimental.pallas as pl

    b, h, s_len, d = q.shape
    bh = b * h
    qr = q.reshape(bh, s_len, d)
    kr = k.reshape(bh, s_len, d)
    vr = v.reshape(bh, s_len, d)
    block_q = min(block_q, s_len)
    block_k = min(block_k, s_len)
    grid = (bh, s_len // block_q, s_len // block_k)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, valid_len=valid_len, dropout_p=dropout_p)
    out, lse = _pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            _smem_spec(),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, 1)),   # running max m
            _scratch((block_q, 1)),   # running sum l
            _scratch((block_q, d)),   # output accumulator
        ],
        interpret=interpret,
    )(_seed_arr(dropout_seed), qr, kr, vr)
    return out.reshape(b, h, s_len, d), lse[..., 0]


def _scratch(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


def _recompute_p(q, k, lse_col, scale, causal, q_idx, kv_idx, block_q,
                 block_k, valid_len=None):
    """exp(QK^T * scale - lse) for one (q block, k block) tile.
    lse_col: (block_q, 1) column (see _finish in _fwd_kernel)."""
    import jax.experimental.pallas as pl  # noqa: F401

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal or valid_len is not None:
        k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        keep = jnp.ones(s.shape, bool)
        if causal:
            q_pos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            keep &= q_pos >= k_pos
        if valid_len is not None:
            keep &= k_pos < valid_len
        s = jnp.where(keep, s, -jnp.inf)
    return jnp.where(jnp.isfinite(lse_col), jnp.exp(s - lse_col), 0.0)


def _tile_keep(seed_ref, bh, q_idx, kv_idx, block_q, block_k, shape,
               dropout_p):
    """Regenerate the forward pass's keep mask for one tile."""
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kv_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return _dropout_keep(seed_ref[0], bh, q_pos, k_pos, dropout_p)


def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, scale, causal, block_q,
                   block_k, valid_len=None, dropout_p=0.0):
    import jax.experimental.pallas as pl

    kv_idx = pl.program_id(2)

    @pl.when(kv_idx == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_idx = pl.program_id(1)
    bh_idx = pl.program_id(0)  # hoisted: program_id inside pl.when
    # bodies breaks interpret mode
    # causal: tiles strictly above the diagonal are all-zero P — skip
    if causal:
        live = kv_idx * block_k <= q_idx * block_q + block_q - 1
    else:
        live = kv_idx >= 0  # always true (traced predicate)
    if valid_len is not None:
        # k tiles entirely inside the padding are all-zero P — skip
        live &= kv_idx * block_k < valid_len

    @pl.when(live)
    def _accum():
        p = _recompute_p(q_ref[0], k_ref[0], lse_ref[0], scale, causal,
                         q_idx, kv_idx, block_q, block_k, valid_len)
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

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                    causal, block_q, block_k, valid_len=None,
                    dropout_p=0.0):
    import jax.experimental.pallas as pl

    q_idx = pl.program_id(2)       # q blocks stream in the inner axis

    @pl.when(q_idx == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    kv_idx = pl.program_id(1)
    bh_idx = pl.program_id(0)  # hoisted: program_id inside pl.when
    # bodies breaks interpret mode
    if causal:
        # q tiles strictly above this k tile's diagonal see zero P
        live = kv_idx * block_k <= q_idx * block_q + block_q - 1
    else:
        live = q_idx >= 0  # always true (traced predicate)
    if valid_len is not None:
        live &= kv_idx * block_k < valid_len

    @pl.when(live)
    def _accum():
        p = _recompute_p(q_ref[0], k_ref[0], lse_ref[0], scale, causal,
                         q_idx, kv_idx, block_q, block_k, valid_len)
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

    @pl.when(q_idx == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_k,
               interpret, valid_len=None, dropout_p=0.0,
               dropout_seed=None):
    """Block-streamed FlashAttention-2 backward: O(S) memory, no (S, S)
    residual — P tiles are recomputed from (q, k, lse) per block (and
    the dropout keep mask from its counter hash)."""
    import jax.experimental.pallas as pl

    b, h, s_len, d = q.shape
    bh = b * h
    block_q = min(block_q, s_len)
    block_k = min(block_k, s_len)
    qr = q.reshape(bh, s_len, d)
    kr = k.reshape(bh, s_len, d)
    vr = v.reshape(bh, s_len, d)
    do = g.reshape(bh, s_len, d)
    orr = out.reshape(bh, s_len, d)
    # delta = rowsum(dO * O) — the softmax-grad correction term (with
    # dropout it still equals rowsum(P̂∘dP̂) since O = P_d V).
    # lse/delta ride as (bh, s_len, 1) columns so their (block_q, 1)
    # blocks satisfy Mosaic's last-two-dims tiling rule.
    delta = jnp.sum(do.astype(jnp.float32) * orr.astype(jnp.float32),
                    axis=-1)[..., None]             # (bh, s_len, 1)
    lse = lse[..., None]                            # (bh, s_len, 1)
    seed = _seed_arr(dropout_seed)

    dq = _pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          valid_len=valid_len, dropout_p=dropout_p),
        name="flash_attention_bwd_dq",
        grid=(bh, s_len // block_q, s_len // block_k),
        in_specs=[
            _smem_spec(),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_len, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d))],
        interpret=interpret,
    )(seed, qr, kr, vr, do, lse, delta)

    dk, dv = _pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          valid_len=valid_len, dropout_p=dropout_p),
        name="flash_attention_bwd_dkv",
        grid=(bh, s_len // block_k, s_len // block_q),
        in_specs=[
            _smem_spec(),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s_len, d), v.dtype),
        ],
        scratch_shapes=[_scratch((block_k, d)), _scratch((block_k, d))],
        interpret=interpret,
    )(seed, qr, kr, vr, do, lse, delta)
    shape = (b, h, s_len, d)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, seed, causal, scale, block_q, block_k, interpret,
           dropout_p=0.0, valid_len=None):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                        interpret, valid_len, dropout_p, seed)
    return out


def _flash_vjp_fwd(q, k, v, seed, causal, scale, block_q, block_k,
                   interpret, dropout_p=0.0, valid_len=None):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret, valid_len, dropout_p, seed)
    return out, (q, k, v, seed, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, dropout_p,
                   valid_len, res, g):
    import numpy as _onp

    q, k, v, seed, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q,
                            block_k, interpret, valid_len, dropout_p,
                            seed)
    # integer seed takes a float0 cotangent
    return dq, dk, dv, _onp.zeros(seed.shape, jax.dtypes.float0)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@register_op("flash_attention")
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=None, dropout_p=0.0,
                    dropout_seed=None):
    """Fused multi-head attention: softmax(QK^T * scale) V.

    q/k/v: (B, H, S, D). Runs the Pallas kernel on TPU (or anywhere with
    interpret=True); falls back to the jnp reference otherwise. Ragged S
    is tile-padded and the kernel masks the padded keys (static
    `valid_len`) — only a ragged head dim D takes the reference path.

    dropout_p > 0 with an int32 `dropout_seed` applies attention-prob
    dropout inside the kernel (numerator-masked, inverted scaling; the
    counter-hash mask regenerates identically in the backward kernels
    and the reference path — see _dropout_keep).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p > 0.0 and dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    dropout_p = float(dropout_p)

    def _fallback(qq, kk, vv):
        return attention_reference(qq, kk, vv, causal=causal, scale=scale,
                                   dropout_p=dropout_p,
                                   dropout_seed=dropout_seed)

    if interpret is None:
        interpret = False
        if jax.devices()[0].platform != "tpu":
            return _fallback(q, k, v)
    if d % 8:
        # ragged head dim: blocks can't stay lane-aligned
        return _fallback(q, k, v)
    s_len = q.shape[2]
    s_pad = _tile_pad_len(s_len, block_q)
    bq = min(block_q, s_pad)
    bk = min(block_k, s_pad)
    if s_pad % bq or s_pad % bk or bq % 8 or bk % 8:
        # non-dividing custom block sizes: reference path
        return _fallback(q, k, v)
    seed = _seed_arr(dropout_seed)
    if s_pad == s_len:
        return _flash(q, k, v, seed, causal, scale, bq, bk, interpret,
                      dropout_p)
    pad = [(0, 0), (0, 0), (0, s_pad - s_len), (0, 0)]
    out = _flash(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                 seed, causal, scale, bq, bk, interpret, dropout_p, s_len)
    return out[:, :, :s_len]


def _tile_pad_len(s_len, block):
    """Smallest padded length that tiles: multiple of 8 below one block,
    multiple of the block size above."""
    if s_len >= block:
        return -(-s_len // block) * block
    return -(-s_len // 8) * 8
