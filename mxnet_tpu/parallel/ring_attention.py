"""Ring attention — sequence/context parallelism over the ICI ring.

A capability the reference lacks entirely (SURVEY.md §5 "Long-context /
sequence parallelism — absent"), built TPU-first: the sequence axis is
sharded over a mesh axis; each device holds a Q/K/V shard and K/V blocks
rotate around the ring via lax.ppermute while a numerically-stable streaming
softmax (online max/denominator) accumulates the output. Compute on each hop
overlaps the neighbor exchange (XLA schedules ppermute async), so the
attention cost is flat in the number of devices while max sequence length
scales linearly with them.

References (public): Liu et al., "Ring Attention with Blockwise
Transformers" (2023); the streaming-softmax recurrence is the
FlashAttention online-softmax.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["ring_attention", "ring_attention_sharded",
           "ring_flash_attention", "ring_flash_attention_sharded"]


def _stable_block(q, k, v, o, m, l, scale, mask=None):
    """One blockwise-attention accumulation step (online softmax)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m, m_blk)
    # guard -inf rows (fully masked block): exp(-inf - -inf) -> use where
    p = jnp.exp(s - jnp.where(jnp.isneginf(m_new), 0.0, m_new))
    corr = jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m)
                   - jnp.where(jnp.isneginf(m_new), 0.0, m_new))
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Per-device body: full attention over a sequence sharded on
    `axis_name`. Call inside shard_map/pjit; q,k,v are local shards
    (batch, heads, seq_local, head_dim)."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    if scale is None:
        scale = q.shape[-1] ** -0.5

    o = jnp.zeros_like(q, dtype=jnp.float32)
    m = jnp.full(q.shape[:3] + (1,), -jnp.inf, jnp.float32)
    l = jnp.zeros(q.shape[:3] + (1,), jnp.float32)  # noqa: E741

    qf = q.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def accum(i, o, m, l, k_blk, v_blk):  # noqa: E741
        src = (my - i) % n  # which device's K/V block we now hold
        if causal:
            q_idx = my * s_local + jnp.arange(s_local)[:, None]
            k_idx = src * s_local + jnp.arange(k_blk.shape[2])[None, :]
            mask = (q_idx >= k_idx)[None, None]
        else:
            mask = None
        return _stable_block(
            qf, k_blk.astype(jnp.float32), v_blk.astype(jnp.float32),
            o, m, l, scale, mask)

    def body(i, carry):
        o, m, l, k_blk, v_blk = carry  # noqa: E741
        o, m, l = accum(i, o, m, l, k_blk, v_blk)  # noqa: E741
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk

    # n-1 hops with permute; the final block accumulates outside the loop
    # so the ring doesn't pay a wasted last-iteration ppermute pair
    o, m, l, k_last, v_last = jax.lax.fori_loop(  # noqa: E741
        0, n - 1, body, (o, m, l, k, v))
    o, m, l = accum(n - 1, o, m, l, k_last, v_last)  # noqa: E741
    out = o / jnp.where(l == 0, 1.0, l)
    return out.astype(q.dtype)


_jit_cache = {}


def ring_attention_sharded(q, k, v, mesh, axis="sp", causal=False,
                           scale=None):
    """Convenience wrapper: shard (b, h, S, d) arrays on the sequence dim
    over `axis` and run ring attention as one jitted shard_map program.
    The jitted program is cached per (mesh, axis, causal, scale) so training
    loops hit the compile cache."""

    key = (mesh, axis, causal, scale)
    run = _jit_cache.get(key)
    if run is None:
        spec = P(None, None, axis, None)

        @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec, check_vma=False)
        def body(ql, kl, vl):
            return ring_attention(ql, kl, vl, axis, causal=causal,
                                  scale=scale)

        run = jax.jit(body)
        _jit_cache[key] = run
    return run(q, k, v)


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, interpret,
                         valid_len=None):
    """Ring attention with the Pallas flash kernel as the per-hop block
    compute. Each hop runs the O(S_local)-memory fused kernel on the
    resident K/V block and merges normalized partials exactly via their
    logsumexp:

        lse = logaddexp(lse_a, lse_b)
        out = exp(lse_a - lse) * out_a + exp(lse_b - lse) * out_b

    Causal mode: hops from future devices contribute lse = -inf (skipped
    by the merge); the diagonal hop runs the causal kernel under lax.cond.
    Same contract as `ring_attention` (call inside shard_map; q/k/v are
    (B, H, S_local, D) shards).
    """
    import jax as _jax

    from ..ops.pallas_attention import _flash_fwd, saved_lse

    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    bq = min(128, s_local)
    bk = min(128, s_local)

    out = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full(q.shape[:3], -jnp.inf, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def merge(out_a, lse_a, out_b, lse_b):
        lse_new = jnp.logaddexp(lse_a, lse_b)
        safe = jnp.where(jnp.isneginf(lse_new), 0.0, lse_new)
        w_a = jnp.where(jnp.isneginf(lse_a), 0.0,
                        jnp.exp(lse_a - safe))[..., None]
        w_b = jnp.where(jnp.isneginf(lse_b), 0.0,
                        jnp.exp(lse_b - safe))[..., None]
        return w_a * out_a + w_b * out_b, lse_new

    def hop(i, out, lse, k_blk, v_blk):
        src = (my - i) % n

        def block(is_causal):
            # the kernel on the resident block; its lse by position
            blk_out, blk_lse = _flash_fwd(q, k_blk, v_blk, is_causal, scale,
                                          bq, bk, interpret, valid_len)
            return blk_out, saved_lse(blk_lse, q.shape)

        if causal:
            def _skip():
                # future keys: no kernel launch, zero contribution
                return (jnp.zeros(q.shape, q.dtype),
                        jnp.full(q.shape[:3], -jnp.inf, jnp.float32))

            blk_out, blk_lse = _jax.lax.cond(
                src > my,
                _skip,
                lambda: _jax.lax.cond(
                    src == my, lambda: block(True), lambda: block(False)),
            )
        else:
            blk_out, blk_lse = block(False)
        return merge(out, lse, blk_out.astype(jnp.float32), blk_lse)

    def body(i, carry):
        out, lse, k_blk, v_blk = carry
        out, lse = hop(i, out, lse, k_blk, v_blk)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return out, lse, k_blk, v_blk

    out, lse, k_last, v_last = jax.lax.fori_loop(
        0, n - 1, body, (out, lse, k, v))
    out, lse = hop(n - 1, out, lse, k_last, v_last)
    return out.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_flash(q, k, v, axis_name, causal, scale, interpret,
                valid_len=None):
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                  interpret, valid_len)
    return out


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal, scale, interpret,
                        valid_len=None):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                    interpret, valid_len)
    return out, (q, k, v, out, lse)


def _ring_flash_vjp_bwd(axis_name, causal, scale, interpret, valid_len,
                        res, g):
    """Ring backward: one full rotation; each hop runs the block-streamed
    Pallas flash backward (_flash_bwd) between the local Q and the
    resident K/V block using the saved GLOBAL lse, so memory stays
    O(S_local) — no (S_local, S_local) score matrix. Each hop's dK/dV is
    carried around the ring back to the block's owner; dQ accumulates
    locally. Cross-hop causal structure maps onto the kernel's flag:
    past hops run it un-causal, the diagonal hop causal, future hops are
    skipped entirely."""
    from ..ops.pallas_attention import _flash_bwd, stored_lse

    q, k, v, out, lse = res
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    bq = min(128, s_local)
    bk = min(128, s_local)
    perm = [(i, (i + 1) % n) for i in range(n)]
    lse = stored_lse(lse, bq)      # the merged one, as the kernels read it

    def grads_for(k_blk, v_blk, is_causal):
        return _flash_bwd(q, k_blk, v_blk, out, lse, g, is_causal,
                          scale, bq, bk, interpret, valid_len)

    def body(i, carry):
        dq, k_blk, v_blk, dk, dv = carry
        src = (my - i) % n
        if causal:
            def _skip():
                return (jnp.zeros(q.shape, q.dtype),
                        jnp.zeros(k.shape, k.dtype),
                        jnp.zeros(v.shape, v.dtype))

            dq_h, dk_blk, dv_blk = jax.lax.cond(
                src > my,
                _skip,
                lambda: jax.lax.cond(
                    src == my,
                    lambda: grads_for(k_blk, v_blk, True),
                    lambda: grads_for(k_blk, v_blk, False)),
            )
        else:
            dq_h, dk_blk, dv_blk = grads_for(k_blk, v_blk, False)
        dq = dq + dq_h.astype(jnp.float32)
        # rotate the K/V blocks AND their accumulated grads together so
        # every block's dK/dV arrives home after the full cycle
        dk = jax.lax.ppermute(dk + dk_blk.astype(jnp.float32),
                              axis_name, perm)
        dv = jax.lax.ppermute(dv + dv_blk.astype(jnp.float32),
                              axis_name, perm)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return dq, k_blk, v_blk, dk, dv

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, _, _, dk, dv = jax.lax.fori_loop(
        0, n, body, (dq0, k, v, jnp.zeros(k.shape, jnp.float32),
                     jnp.zeros(v.shape, jnp.float32)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention(q, k, v, axis_name, causal=False, scale=None,
                         interpret=None):
    """Ring attention with the Pallas flash kernel per forward hop and a
    blockwise ring backward (custom_vjp) — trainable end to end. See
    _ring_flash_fwd_impl for the forward schedule and _ring_flash_vjp_bwd
    for the gradient rotation."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if q.shape[-1] % 8:
        # ragged head dim: blocks can't stay lane-aligned
        return ring_attention(q, k, v, axis_name, causal=causal,
                              scale=scale)
    from ..ops.pallas_attention import _tile_pad_len

    s_local = q.shape[2]
    s_pad = _tile_pad_len(s_local, 128)
    if s_pad == s_local:
        return _ring_flash(q, k, v, axis_name, causal, scale, interpret)
    # Ragged local shard: tile-pad; the kernel masks padded keys of every
    # hop's resident block via the static valid_len (padding sits at the
    # tail of each device's block, so hop-granular causality is unchanged).
    pad = [(0, 0), (0, 0), (0, s_pad - s_local), (0, 0)]
    out = _ring_flash(jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
                      axis_name, causal, scale, interpret, s_local)
    return out[:, :, :s_local]


def ring_flash_attention_sharded(q, k, v, mesh, axis="sp", causal=False,
                                 scale=None, interpret=None):
    """shard_map wrapper: sequence axis sharded over `axis`, flash kernel
    per hop (the production long-context path on TPU). Jitted program
    cached per (mesh, axis, causal, scale, interpret) like
    ring_attention_sharded."""

    key = ("flash", mesh, axis, causal, scale, interpret)
    run = _jit_cache.get(key)
    if run is None:
        spec = P(None, None, axis, None)

        @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec, check_vma=False)
        def body(ql, kl, vl):
            return ring_flash_attention(ql, kl, vl, axis, causal=causal,
                                        scale=scale, interpret=interpret)

        run = jax.jit(body)
        _jit_cache[key] = run
    return run(q, k, v)
