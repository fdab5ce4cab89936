"""Ulysses-style sequence parallelism: all-to-all head↔sequence resharding.

The second long-context strategy next to ring_attention (SURVEY.md §5 —
absent in the reference, green-field here). Where ring attention streams
K/V blocks around the ICI ring, Ulysses keeps attention *local*: activations
arrive sharded on the sequence axis, an all-to-all reshards them to
head-sharded/full-sequence, each device runs plain attention over its head
group (one big MXU matmul chain — no streaming softmax), and a second
all-to-all restores sequence sharding.

Cost model (scaling-book): 2 all-to-alls of the qkv/out tensors vs ring's
(n-1) K/V ppermute hops — all-to-all rides ICI at full bisection bandwidth,
so Ulysses wins when heads >= devices and sequence lengths are moderate;
ring wins for extreme sequence lengths (memory: Ulysses materializes full-S
scores per head group).

Reference (public): Jacobs et al., "DeepSpeed Ulysses" (2023).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None):
    """Per-device body (call inside shard_map): q/k/v are
    (batch, heads, seq_local, head_dim) shards on the sequence axis;
    heads must divide the axis size evenly.
    """
    n = jax.lax.axis_size(axis_name)
    b, h, s_local, d = q.shape
    if h % n:
        raise ValueError(f"heads {h} not divisible by axis size {n}")
    if scale is None:
        scale = d ** -0.5

    def seq_to_heads(x):
        # (b, h, s_loc, d) -> all-to-all -> (b, h/n, S, d): split heads
        # into n peer groups; the exchange removes the split axis and
        # inserts the received peer axis at concat_axis, giving
        # (b, h/n, n, s_loc, d) whose flatten is the full ordered sequence
        x = x.reshape(b, n, h // n, s_local, d)
        x = jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                               tiled=False)
        return x.reshape(b, h // n, n * s_local, d)

    def heads_to_seq(x):
        # inverse: (b, h/n, S, d) -> (b, h, s_local, d)
        x = x.reshape(b, h // n, n, s_local, d)
        x = jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                               tiled=False)
        return x.reshape(b, h, s_local, d)

    qh = seq_to_heads(q.astype(jnp.float32))
    kh = seq_to_heads(k.astype(jnp.float32))
    vh = seq_to_heads(v.astype(jnp.float32))

    s_full = qh.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * scale
    if causal:
        idx = jnp.arange(s_full)
        scores = jnp.where(idx[None, None, :, None] >= idx[None, None,
                                                          None, :],
                           scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    oh = jnp.einsum("bhqk,bhkd->bhqd", att, vh)
    return heads_to_seq(oh).astype(q.dtype)


_jit_cache = {}


def ulysses_attention_sharded(q, k, v, mesh, axis="sp", causal=False,
                              scale=None):
    """Convenience wrapper mirroring ring_attention_sharded: (b, h, S, d)
    arrays sharded on the sequence dim over `axis`; one jitted shard_map
    program cached per (mesh, axis, causal, scale)."""

    key = (mesh, axis, causal, scale)
    run = _jit_cache.get(key)
    if run is None:
        spec = P(None, None, axis, None)

        @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec, check_vma=False)
        def body(ql, kl, vl):
            return ulysses_attention(ql, kl, vl, axis, causal=causal,
                                     scale=scale)

        run = jax.jit(body)
        _jit_cache[key] = run
    return run(q, k, v)
