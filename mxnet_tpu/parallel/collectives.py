"""Collective wrappers over XLA (psum/all_gather/reduce_scatter/ppermute).

These replace the reference's entire comm layer: CommCPU/CommDevice reduce
(src/kvstore/comm.h), tree allreduce (comm_tree.h), NCCL (kvstore_nccl.h) and
ps-lite push/pull — all become XLA collectives that ride ICI within a slice
and DCN across slices, scheduled asynchronously by the compiler.

Every wrapper records call count / input bytes / dispatch wall-time into
the telemetry registry (`collective_*` counters labeled by op — see
docs/telemetry.md). Dispatch time, not completion: the returned arrays are
async like everything else on the device stream.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from ..diagnostics import spans as _spans
from ..diagnostics import watchdog as _watchdog
from ..telemetry import instruments as _telemetry

__all__ = ["psum_tree", "psum_tree_flat", "psum_tree_flat_traced",
           "allreduce_mean", "all_gather", "reduce_scatter",
           "ring_permute"]


def _tree_bytes(tree):
    return sum(_telemetry.nbytes_of(x)
               for x in jax.tree_util.tree_leaves(tree))


def psum_tree(tree, mesh, axis="dp"):
    """Allreduce-sum a pytree of per-device arrays sharded over `axis`.

    Inputs are arrays sharded batch-first over the mesh axis; output is the
    sum, replicated. This is one jitted shard_map — XLA emits a single fused
    all-reduce for the whole tree (the multi-tensor aggregation the reference
    implements by hand in CommDevice::ReduceImpl).
    """

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis),),
        out_specs=P(),
    )
    def _reduce(t):
        return jax.tree_util.tree_map(lambda x: jax.lax.psum(x, axis), t)

    t0 = time.perf_counter()
    with _spans.span("psum", cat="collective"), _watchdog.guard("psum"):
        out = jax.jit(_reduce)(tree)
    _telemetry.record_collective("psum", _tree_bytes(tree),
                                 time.perf_counter() - t0)
    return out


def _flat_buckets(leaves, cap_bytes):
    """Partition leaf indices into dtype-homogeneous buckets of roughly
    `cap_bytes` each (order-preserving within a dtype). A leaf larger
    than the cap gets its own bucket — never split, never dropped."""
    buckets, open_by_dtype = [], {}
    for i, leaf in enumerate(leaves):
        nb = _telemetry.nbytes_of(leaf)
        cur = open_by_dtype.get(leaf.dtype)
        if cur is not None and cur[1] + nb <= cap_bytes:
            cur[0].append(i)
            open_by_dtype[leaf.dtype] = (cur[0], cur[1] + nb)
        else:
            fresh = [i]
            buckets.append(fresh)
            open_by_dtype[leaf.dtype] = (fresh, nb)
    return buckets


def _resolve_bucket_mb(bucket_mb):
    if bucket_mb is not None:
        return int(bucket_mb)
    from .. import env as _env

    return int(_env.get("MXTPU_FUSED_BUCKET_MB"))


def psum_tree_flat_traced(tree, axis, bucket_mb=None):
    """TRACED bucketed flat allreduce — the inside-the-program form of
    :func:`psum_tree_flat`, callable from code already running under
    ``shard_map`` (the whole-step compiled path threads its gradient
    allreduce through this, so reduce + optimizer update share one XLA
    program and one dispatch). Leaves are concatenated into
    dtype-homogeneous ~`bucket_mb` MB buffers, ONE ``lax.psum`` per
    buffer, split back to the original shapes in the same trace. No
    dispatch/telemetry bookkeeping here — the enclosing dispatch owns
    that; bucket sizes come from the (static) aval shapes."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    buckets = _flat_buckets(leaves, _resolve_bucket_mb(bucket_mb) << 20)
    outs = [None] * len(leaves)
    for bucket in buckets:
        flat = (leaves[bucket[0]].reshape(-1) if len(bucket) == 1
                else jnp.concatenate(
                    [leaves[i].reshape(-1) for i in bucket]))
        red = jax.lax.psum(flat, axis)
        off = 0
        for i in bucket:
            n = leaves[i].size
            outs[i] = red[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree_util.tree_unflatten(treedef, outs)


_flat_jit_cache = {}


def psum_tree_flat(tree, mesh, axis="dp", bucket_mb=None):
    """Bucketed flat allreduce of a pytree (the DDP-style multi-tensor
    path): leaves are flattened and concatenated into dtype-homogeneous
    buffers of ~`bucket_mb` MB, ONE ``lax.psum`` launches per buffer, and
    the buffer is split back to the original leaf shapes inside the SAME
    jitted shard_map — so a whole gradient tree costs O(buckets)
    collectives (typically 1-3) instead of O(leaves), with no extra
    dispatch for pack/unpack. Semantics match :func:`psum_tree`.
    `bucket_mb` defaults to ``MXTPU_FUSED_BUCKET_MB``.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    bucket_mb = _resolve_bucket_mb(bucket_mb)
    buckets = _flat_buckets(leaves, bucket_mb << 20)
    sig = (id(mesh), tuple(mesh.shape.items()), axis, bucket_mb,
           treedef, tuple((x.shape, str(x.dtype)) for x in leaves))
    fn = _flat_jit_cache.get(sig)
    if fn is None:
        @partial(shard_map, mesh=mesh, in_specs=(P(axis),), out_specs=P())
        def _reduce(ls):
            return psum_tree_flat_traced(ls, axis, bucket_mb)

        fn = jax.jit(_reduce)
        _flat_jit_cache[sig] = fn
    t0 = time.perf_counter()
    with _spans.span("psum_flat", cat="collective"), \
            _watchdog.guard("psum_flat"):
        outs = fn(leaves)
    _telemetry.record_collective("psum_flat", _tree_bytes(leaves),
                                 time.perf_counter() - t0)
    for bucket in buckets:
        _telemetry.record_fused_bucket("allreduce", len(bucket))
    return jax.tree_util.tree_unflatten(treedef, outs)


def allreduce_mean(tree, mesh, axis="dp"):
    n = mesh.shape[axis]
    summed = psum_tree(tree, mesh, axis)
    return jax.tree_util.tree_map(lambda x: x / n, summed)


def all_gather(x, mesh, axis="dp", tiled=True):
    """All-gather along a mesh axis (reference analog: broadcast fan-out)."""

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(),
             check_vma=False)
    def _ag(v):
        return jax.lax.all_gather(v, axis, tiled=tiled)

    t0 = time.perf_counter()
    with _spans.span("all_gather", cat="collective"), \
            _watchdog.guard("all_gather"):
        out = jax.jit(_ag)(x)
    _telemetry.record_collective("all_gather", _tree_bytes(x),
                                 time.perf_counter() - t0)
    return out


def reduce_scatter(x, mesh, axis="dp"):
    """Reduce-scatter along a mesh axis (ZeRO-style sharded grads).

    Input: per-device full copies (replicated layout); output: each device
    keeps the reduced 1/n slice, laid out sharded over `axis`.
    """

    @partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(axis),
             check_vma=False)
    def _rs(v):
        return jax.lax.psum_scatter(v, axis, tiled=True)

    t0 = time.perf_counter()
    with _spans.span("reduce_scatter", cat="collective"), \
            _watchdog.guard("reduce_scatter"):
        out = jax.jit(_rs)(x)
    _telemetry.record_collective("reduce_scatter", _tree_bytes(x),
                                 time.perf_counter() - t0)
    return out


def ring_permute(x, mesh, axis="sp", shift=1):
    """Neighbor exchange along a ring — the building block of ring attention
    / context parallelism (a capability the reference lacks; SURVEY.md §5)."""
    n = mesh.shape[axis]
    perm = [(i, (i + shift) % n) for i in range(n)]

    @partial(shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    def _pp(v):
        return jax.lax.ppermute(v, axis, perm)

    t0 = time.perf_counter()
    with _spans.span("ppermute", cat="collective"), \
            _watchdog.guard("ppermute"):
        out = jax.jit(_pp)(x)
    _telemetry.record_collective("ppermute", _tree_bytes(x),
                                 time.perf_counter() - t0)
    return out
