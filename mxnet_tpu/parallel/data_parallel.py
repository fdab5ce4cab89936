"""Sharded training-step builders.

Two styles, both idiomatic on TPU:

  * GSPMD (default): params replicated / batch sharded over 'dp'; one jit
    with sharding annotations — XLA's SPMD partitioner inserts the gradient
    all-reduce and overlaps it with backprop. This subsumes the reference's
    P3 priority-based push/pull overlap (src/kvstore/p3store_dist.h) —
    the latency-hiding scheduler does it per-HLO instead of per-layer.

  * explicit shard_map: per-device code with explicit lax.psum — useful when
    composing with tensor/sequence parallel inner collectives.
"""
from __future__ import annotations

from functools import partial

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["make_data_parallel_step", "make_shard_map_step"]


def make_data_parallel_step(loss_fn, update_fn, mesh, axis="dp",
                            param_specs=None, donate=True):
    """Build `step(params, opt_state, batch, lr) -> (params, opt_state, loss)`.

    loss_fn(params, batch) -> scalar; update_fn(params, grads, opt_state, lr)
    -> (new_params, new_opt_state). Batch is sharded over `axis` (leading
    dim); params replicated unless `param_specs` (a PartitionSpec pytree
    prefix) shards them (tensor parallelism).
    """
    repl = NamedSharding(mesh, P())
    batch_sh = NamedSharding(mesh, P(axis))
    if param_specs is None:
        param_sh = repl
    else:
        param_sh = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), param_specs,
            is_leaf=lambda x: isinstance(x, P))

    def step(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_opt = update_fn(params, grads, opt_state, lr)
        return new_params, new_opt, loss

    kwargs = {}
    if donate:
        kwargs["donate_argnums"] = (0, 1)
    return jax.jit(
        step,
        in_shardings=(param_sh, param_sh, batch_sh, None),
        out_shardings=(param_sh, param_sh, repl),
        **kwargs,
    )


def make_shard_map_step(loss_fn, update_fn, mesh, axis="dp"):
    """Explicit-collective variant: per-device bodies + lax.psum on grads."""

    # check_vma=False: jax's replication checker rewrites grads of
    # replicated (P()) inputs with an extra psum, inflating them by the
    # axis size; with it off we own the collectives (explicit pmean).
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(axis), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def body(params, opt_state, batch, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = jax.lax.pmean(grads, axis)
        loss = jax.lax.pmean(loss, axis)
        new_params, new_opt = update_fn(params, grads, opt_state, lr)
        return new_params, new_opt, loss

    return jax.jit(body, donate_argnums=(0, 1))
