"""Pipeline parallelism over a 'pp' mesh axis: interleaved-GPipe forward
and a 1F1B training step.

New capability beyond the reference (SURVEY §2.4: its closest artifact is
a manual model-parallel LSTM recipe). Stage parameters are stacked on a
leading stage dimension and sharded over 'pp'; inside `shard_map` each
device runs its stage(s) and hands activations around a ring with
`ppermute` over ICI.

Two schedules:
  - `pipeline_apply` — interleaved GPipe (Megatron-style virtual stages):
    device s holds `num_virtual` chunks (virtual stage j*S + s is chunk j
    on device s), shrinking the fill/drain bubble from (S-1) ticks to
    (S-1)/v relative: efficiency M·v/(M·v + S - 1). Differentiable —
    jax.grad reverses the scan into the mirrored pipelined backward.
  - `pipeline_step_1f1b` — explicit one-forward-one-backward training
    step: forward inputs live in a ring buffer of depth S+1 and the
    backward RECOMPUTES the stage forward inside jax.vjp, so activation
    memory is O(S) per device instead of GPipe's O(M). Closed-form
    schedule: tau_f(m,s) = s+m (warmup m < S-s) else 2m+s;
    tau_b(m,s) = 2m + 2S - 1 - s; fwd and bwd land on opposite tick
    parities so each device runs at most one compute per tick.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["pipeline_apply", "pipeline_apply_sharded",
           "pipeline_step_1f1b", "pipeline_step_1f1b_sharded",
           "interleave_stages"]


def interleave_stages(params_list, n_stages):
    """Reorder a list of V = S*v per-virtual-stage param pytrees from
    natural order (virtual stage k) into the device-major stacking
    `pipeline_apply` expects (device s holds rows [s*v, (s+1)*v): chunk j
    of device s is virtual stage j*S + s)."""
    V = len(params_list)
    if V % n_stages:
        raise ValueError(f"{V} virtual stages not divisible by "
                         f"{n_stages} devices")
    v = V // n_stages
    order = [j * n_stages + s for s in range(n_stages) for j in range(v)]
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[params_list[k] for k in order])


def pipeline_apply(stage_fn, stacked_params, microbatches, axis_name,
                   num_virtual=1):
    """Run inside shard_map/pmap over `axis_name`.

    stage_fn(params, x) -> y applies one (virtual) stage; stacked_params
    has a leading dim of num_virtual chunks per device (shard_map gives
    the local slice WITH the dim), stacked device-major — see
    `interleave_stages`. microbatches: (M, ...) replicated; with
    num_virtual > 1, M must divide into groups of S (the Megatron
    interleave contract). Returns (M, ...) outputs of the final virtual
    stage (psum-broadcast to every device).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage_id = jax.lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    v = num_virtual

    out_shape = jax.eval_shape(
        stage_fn, jax.tree_util.tree_map(lambda p: p[0], stacked_params),
        microbatches[0])
    if v > 1 and n_micro % n_stages:
        raise ValueError(f"interleaved schedule needs M % S == 0, got "
                         f"M={n_micro}, S={n_stages}")
    carry = jnp.zeros(out_shape.shape, out_shape.dtype)
    outputs = jnp.zeros((n_micro,) + out_shape.shape, out_shape.dtype)

    def tick(state, t):
        carry, outputs = state
        # schedule: device s's u-th unit (u = t - s) is chunk j of
        # microbatch m, processed group-by-group (groups of S microbatches)
        u = t - stage_id
        g = u // (v * n_stages)
        r = u % (v * n_stages)
        j = r // n_stages
        m = g * n_stages + (r % n_stages)
        valid = (u >= 0) & (u < v * n_micro) & (m < n_micro)
        mb_idx = jnp.clip(m, 0, n_micro - 1)
        # chunk 0 on device 0 eats fresh microbatches; everything else
        # eats the ring
        x = jnp.where((stage_id == 0) & (j == 0), microbatches[mb_idx],
                      carry)
        local = jax.tree_util.tree_map(
            lambda p: jax.lax.dynamic_index_in_dim(
                p, jnp.clip(j, 0, p.shape[0] - 1), keepdims=False),
            stacked_params)
        y = stage_fn(local, x)
        y = jnp.where(valid, y, jnp.zeros_like(y))
        record = valid & (stage_id == n_stages - 1) & (j == v - 1)
        outputs = jax.lax.cond(
            record,
            lambda o: o.at[mb_idx].set(y),
            lambda o: o,
            outputs)
        # ring: stage s feeds s+1; the wrap S-1 -> 0 carries chunk
        # j -> j+1 activations back to device 0
        carry = jax.lax.ppermute(
            y, axis_name,
            [(i, (i + 1) % n_stages) for i in range(n_stages)])
        return (carry, outputs), None

    total = v * n_micro + n_stages - 1
    # scan (not fori_loop) so the schedule is reverse-differentiable —
    # pipelined BACKWARD falls out of jax.grad through the same loop
    (_, outputs), _ = jax.lax.scan(tick, (carry, outputs),
                                   jnp.arange(total, dtype=jnp.int32))
    # make the final outputs visible on every stage (callers usually
    # need the loss everywhere); sum works since other stages hold zeros
    return jax.lax.psum(outputs, axis_name)


def pipeline_apply_sharded(stage_fn, stacked_params, microbatches, mesh,
                           axis="pp", num_virtual=1):
    """Jit pipeline_apply under shard_map over `axis`.

    stacked_params: pytree with leading dim S*num_virtual (device-major,
    see `interleave_stages`). microbatches: (M, ...) replicated across
    stages; with num_virtual > 1, M must be a multiple of S.
    """

    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        assert leaf.shape[0] == n_stages * num_virtual, \
            f"stage dim {leaf.shape[0]} != S*v = {n_stages * num_virtual}"
    if num_virtual > 1 and microbatches.shape[0] % n_stages:
        raise ValueError(
            f"interleaved schedule needs M % S == 0, got "
            f"M={microbatches.shape[0]}, S={n_stages}")

    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    fn = jax.shard_map(
        lambda params, mb: pipeline_apply(stage_fn, params, mb, axis,
                                          num_virtual=num_virtual),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    stacked_params = jax.tree_util.tree_map(
        lambda p, spec: jax.device_put(p, NamedSharding(mesh, spec)),
        stacked_params, param_specs)
    microbatches = jax.device_put(microbatches, NamedSharding(mesh, P()))
    with mesh:
        return jax.jit(fn)(stacked_params, microbatches)


def pipeline_step_1f1b(stage_fn, loss_fn, stacked_params, microbatches,
                       labels, axis_name):
    """One-forward-one-backward training step inside shard_map.

    stage_fn(params, x) -> y (homogeneous activation contract);
    loss_fn(y, label) -> scalar, applied on the last stage and MEANED over
    microbatches. Returns (loss_mean, local_param_grads).

    Memory: a depth-(S+1) ring buffer of stage INPUTS is the only saved
    state — the backward slot recomputes the stage forward inside jax.vjp
    (rematerialization: FLOPs for HBM, the TPU trade). In-flight
    microbatches per device never exceed S, so the buffer never aliases.
    Schedule (derivation in module docstring): fwd(m,s) at s+m (warmup)
    else 2m+s; bwd(m,s) at 2m+2S-1-s; opposite parities => one compute
    per device per tick; makespan 2(M+S-1).
    """
    S = jax.lax.psum(1, axis_name)
    s = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    local_params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)

    act = jax.eval_shape(stage_fn, local_params, microbatches[0])
    # S is concrete under shard_map (named axis sizes are static), so the
    # ring depth and permutation tables are compile-time constants
    depth = int(S) + 1

    def zeros_act():
        return jnp.zeros(act.shape, act.dtype)

    # two depth-(S+1) ring buffers: stage INPUTS saved for the recompute
    # backward, and RECEIVED activations awaiting their fwd slot (at the
    # warmup->steady boundary an activation waits up to S-s+1 ticks, so a
    # single carry register would be clobbered; the bwd hop is exactly
    # tick-aligned — tau_b(m,s) = tau_b(m,s+1)+1 — and needs no buffer)
    in_buf0 = jnp.zeros((depth,) + act.shape, act.dtype)
    rcv_buf0 = jnp.zeros((depth,) + act.shape, act.dtype)
    grads0 = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), local_params)

    def _fwd_sched(tau):
        """(microbatch, valid) this device forwards at tick tau."""
        warm = tau < S
        m_f = jnp.where(warm, tau - s, (tau - s) // 2)
        ok = jnp.where(warm,
                       (m_f >= 0) & (m_f < M),
                       ((tau - s) % 2 == 0) & (m_f >= S - s) & (m_f < M))
        return jnp.clip(m_f, 0, M - 1), ok

    def tick(state, tau):
        in_buf, rcv_buf, carry_bwd, grads, loss_sum, msg_in = state
        msg_y, msg_m, msg_ok = msg_in

        # bank the activation that arrived this tick (sender: stage s-1,
        # tick tau-1; the message carries its microbatch id)
        slot = msg_m % depth
        rcv_buf = rcv_buf.at[slot].set(
            jnp.where(msg_ok & (s > 0), msg_y, rcv_buf[slot]))

        mf_c, f_ok = _fwd_sched(tau)
        num = tau + s + 1 - 2 * S
        m_b = num // 2
        b_ok = (num % 2 == 0) & (m_b >= 0) & (m_b < M)
        mb_c = jnp.clip(m_b, 0, M - 1)
        x_in = jnp.where(s == 0, microbatches[mf_c],
                         rcv_buf[mf_c % depth])

        def do_fwd(in_buf, grads):
            y = stage_fn(local_params, x_in)
            in_buf = in_buf.at[mf_c % depth].set(x_in)
            return in_buf, grads, y, zeros_act(), jnp.float32(0.0)

        def do_bwd(in_buf, grads):
            x = in_buf[mb_c % depth]

            def f(p, xx):
                y = stage_fn(p, xx)
                return y, loss_fn(y, labels[mb_c])

            (y, l), vjp = jax.vjp(f, local_params, x)
            is_last = s == S - 1
            dy = jnp.where(is_last, jnp.zeros_like(carry_bwd), carry_bwd)
            dl = jnp.where(is_last, jnp.float32(1.0 / M), jnp.float32(0.0))
            dp, dx = vjp((dy.astype(y.dtype), dl.astype(l.dtype)))
            grads = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), grads, dp)
            l_add = jnp.where(is_last, l.astype(jnp.float32) / M, 0.0)
            return in_buf, grads, zeros_act(), dx, l_add

        def idle(in_buf, grads):
            return (in_buf, grads, zeros_act(), zeros_act(),
                    jnp.float32(0.0))

        in_buf, grads, y_send, dx_send, l_add = jax.lax.cond(
            f_ok, do_fwd,
            lambda b, g: jax.lax.cond(b_ok, do_bwd, idle, b, g),
            in_buf, grads)

        loss_sum = loss_sum + l_add
        fwd_ring = [(i, (i + 1) % S) for i in range(S)]
        msg = (jax.lax.ppermute(y_send, axis_name, fwd_ring),
               jax.lax.ppermute(mf_c, axis_name, fwd_ring),
               jax.lax.ppermute(f_ok, axis_name, fwd_ring))
        carry_bwd = jax.lax.ppermute(
            dx_send, axis_name, [((i + 1) % S, i) for i in range(S)])
        return (in_buf, rcv_buf, carry_bwd, grads, loss_sum, msg), None

    total = 2 * (M + S - 1)
    state0 = (in_buf0, rcv_buf0, zeros_act(), grads0, jnp.float32(0.0),
              (zeros_act(), jnp.int32(0), jnp.bool_(False)))
    (_, _, _, grads, loss_sum, _), _ = jax.lax.scan(
        tick, state0, jnp.arange(total, dtype=jnp.int32))
    loss = jax.lax.psum(loss_sum, axis_name)  # only last stage added
    return loss, grads


def pipeline_step_1f1b_sharded(stage_fn, loss_fn, stacked_params,
                               microbatches, labels, mesh, axis="pp"):
    """Jit pipeline_step_1f1b over `axis`; returns (loss, stacked_grads)
    with grads sharded like the params."""

    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        assert leaf.shape[0] == n_stages

    param_specs = jax.tree_util.tree_map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stacked_params)
    grad_specs = param_specs

    def run(params, mb, lb):
        loss, g = pipeline_step_1f1b(stage_fn, loss_fn, params, mb, lb,
                                     axis)
        # re-add the local stage dim so out_specs can shard it
        g = jax.tree_util.tree_map(lambda a: a[None], g)
        return loss, g

    fn = jax.shard_map(run, mesh=mesh,
                   in_specs=(param_specs, P(), P()),
                   out_specs=(P(), grad_specs),
                   check_vma=False)
    stacked_params = jax.tree_util.tree_map(
        lambda p, spec: jax.device_put(p, NamedSharding(mesh, spec)),
        stacked_params, param_specs)
    microbatches = jax.device_put(microbatches, NamedSharding(mesh, P()))
    labels = jax.device_put(labels, NamedSharding(mesh, P()))
    with mesh:
        return jax.jit(fn)(stacked_params, microbatches, labels)
