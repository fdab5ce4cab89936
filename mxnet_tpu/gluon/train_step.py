"""Whole-step compiled training: ONE donated jit dispatch per step.

The legacy loop costs three dispatch families per iteration — the
CachedOp forward, its vjp backward, and the fused optimizer buckets
(plus an allreduce per bucket under tpu_dist). `TrainStep` captures the
entire iteration — loss forward, autograd backward, gradient allreduce,
and the PR-4 fused optimizer update — into a single `jax.jit` program:

  * parameter weights and optimizer state are DONATED, so XLA updates
    them in place (no second copy of the model in HBM);
  * per-param lr/wd/update-count and the rule's hyper-parameters enter
    as four host arrays (float32[n], float32[n], int32[n] in trained-
    parameter order, and `Optimizer._packed_hyper`) — the same operands
    as `Optimizer.update_fused`, read out by `_weak_elems` as weak
    scalars — so LR schedules change values, never signatures (zero
    retraces after the first step) and a call makes four small
    transfers however many parameters train;
  * the forward runs through the exact `_traced_forward` body the
    CachedOp jit uses, the backward is `jax.vjp` seeded with ones (the
    `loss.backward()` contract), and the update unrolls
    `Optimizer._fused_step_body` per (dtype, multi-precision) bucket —
    so the result is BITWISE identical to the three-phase sequence;
  * with a device mesh, forward+backward run under `shard_map` with the
    batch sharded over the data-parallel axis and gradients reduced
    in-program via the kvstore's `traced_allreduce`
    (`collectives.psum_tree_flat_traced`) — reduce and update compile
    into the same XLA program, zero extra collective dispatches;
  * with a TENSOR/FSDP-sharded plan (any plan whose rules or SpecLayout
    shard a parameter dim — `plan.shards_params(...)`), the same step
    body compiles as one donated GSPMD program over the plan's mesh
    instead of `shard_map`: operands enter committed under the plan's
    shardings, gradients are pinned to the ZeRO state specs
    (`plan.state_spec_for`) so XLA lowers the reduce to reduce-scatter,
    updated params are pinned back to the param specs (all-gather), and
    optimizer state stays 1/fsdp per device end to end — ZeRO sharding
    of the fused optimizer buckets with zero eager collectives.

`MXTPU_WHOLE_STEP=0` (or any ineligibility: sparse grads, an optimizer
overriding `update`, `clip_global_norm`, multi-copy params, gradient
compression, a multi-worker store without a mesh) falls back to the
legacy three-phase path — `TrainStep` remains a drop-in way to run a
step either way. Telemetry: `step_dispatch_total{path}` counts
whole_step vs phased executions, `step_donated_bytes` the in-place
buffer reuse; the compile registry gains a `whole_step` entry with the
program's flops and peak-HBM estimate (docs/performance.md).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as _np

from .. import _random
from .. import autograd as ag
from ..diagnostics import introspect as _introspect
from ..diagnostics import spans as _spans
from ..diagnostics import startup as _startup
from ..diagnostics import watchdog as _watchdog
from ..ndarray.ndarray import NDArray
from ..optimizer.optimizer import (Optimizer, _cache_size, _donate_enabled,
                                   _donated_bytes, _donation_safe, _specs,
                                   _unpack_hyper, _unwrap, _weak_elems,
                                   _write_state)
from ..telemetry import instruments as _telemetry
from .block import HybridBlock, _traced_forward
from .parameter import Parameter

__all__ = ["TrainStep"]


def _wrap_tree(datas):
    """Raw-array pytree -> NDArray pytree (what a loss_fn expects)."""
    return jax.tree_util.tree_map(NDArray, datas)


def _numerics_mode():
    """Live MXTPU_NUMERICS mode; 'off' when observability is broken —
    the check layer must never take the training step down."""
    try:
        from ..observability import numerics as _numerics

        return _numerics.mode()
    except Exception:
        return "off"


class TrainStep:
    """One training iteration as a single compiled, donated dispatch.

    ``step = TrainStep(net, loss_fn, trainer)`` then per batch
    ``loss = step(x, y)`` replaces::

        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch_size)

    `net` is a HybridBlock; `loss_fn(out, *labels)` maps the network
    output and the remaining batch elements to a loss NDArray (None
    means the net's output IS the loss). The first `n_data` positional
    batch elements feed the net, the rest feed the loss. `batch_size`
    defaults to the first input's `batch_axis` extent and drives the
    legacy `rescale_grad = scale / batch_size` contract.

    With `mesh=`/`axis=`, forward+backward run under shard_map with the
    batch sharded over `axis` and params replicated; the loss must keep
    its batch dimension (per-sample losses, the gluon convention) so
    shards concatenate back to the global loss. Gradients are summed
    across shards in-program (`kvstore.traced_allreduce` when the
    trainer has a capable store, else the collectives helper directly),
    matching the single-device sum over the full batch.
    """

    def __init__(self, net, loss_fn, trainer, *, n_data=1, batch_axis=0,
                 mesh=None, axis="dp"):
        self._net = net
        self._loss = loss_fn
        self._trainer = trainer
        self._n_data = int(n_data)
        self._batch_axis = int(batch_axis)
        # a trainer carrying a ShardingPlan makes its mesh this step's
        # default — Trainer(kvstore='tpu_dist', mesh=(('dp', -1),)) then
        # trains sharded through this path with no TrainStep arguments.
        # An EXPLICIT mesh= predates the plan subsystem and keeps its
        # exact old semantics (no plan, no ShardingPass).
        self._plan = None
        if mesh is None:
            plan = getattr(trainer, "sharding_plan", None)
            if plan is not None:
                self._plan = plan
                mesh = plan.mesh
                axis = plan.batch_axis
        self._mesh = mesh
        self._axis = axis
        self._built = False
        self._jit_variants = {}     # donate(bool) -> jitted step
        self._traces = 0            # whole-step jit traces (= compiles)
        self._sink_params = []      # aux-updated params, set at trace time
        self._introspecting = False
        self._ineligible = None     # cached reason string, None = eligible
        self._eligibility_checked = False
        self._variant = None
        # True when the plan tensor/FSDP-shards params: the whole-step
        # program then compiles as one GSPMD partition over the plan's
        # mesh instead of the manual-collective shard_map body
        self._tensor_plan = False

    # -- introspection ----------------------------------------------------
    @property
    def last_path(self):
        """'whole_step' or 'phased' — how the most recent call executed."""
        return getattr(self, "_last_path", None)

    def jit_trace_count(self):
        """Whole-step compiles so far — the zero-retrace proof counter
        (mirrors HybridBlock.jit_trace_count)."""
        return self._traces

    def ineligible_reason(self):
        """Why this step permanently runs phased (None when eligible)."""
        return self._ineligible

    def rebuild(self, mesh=None, axis="dp"):
        """Discard the compiled whole-step program and re-adopt the
        trainer's (possibly new) plan — the elastic re-entry hook
        (mxnet_tpu/elastic/reentry.py; docs/elasticity.md). The jitted
        variants, fused buckets, GSPMD shardings, and the cached
        eligibility verdict all bake the old mesh/world in, so a
        topology change must drop them; the next call re-traces ONCE
        for the new world (jit_trace_count() keeps accumulating — the
        zero-retrace proof is 'exactly one more trace after rebuild').
        An explicit ``mesh=`` keeps the legacy no-plan semantics, as in
        __init__."""
        self._plan = None
        if mesh is None:
            plan = getattr(self._trainer, "sharding_plan", None)
            if plan is not None:
                self._plan = plan
                mesh = plan.mesh
                axis = plan.batch_axis
        self._mesh = mesh
        self._axis = axis
        self._built = False
        self._jit_variants = {}
        self._eligibility_checked = False
        self._ineligible = None
        self._variant = None
        self._tensor_plan = False
        self._step_fn = None
        return self

    # -- eligibility -------------------------------------------------------
    def _check_eligibility(self):
        tr = self._trainer
        opt = tr._optimizer
        if not isinstance(self._net, HybridBlock):
            return "net is not a HybridBlock"
        if getattr(self._net, "_dynamic_graph", False):
            return "net fell back to dynamic-graph execution"
        if not opt._supports_fused():
            return (f"{type(opt).__name__} overrides update/"
                    "update_multi_precision or lacks _rule")
        if opt.clip_global_norm is not None:
            return "clip_global_norm needs the host-combined norm pre-pass"
        if tr._update_on_kvstore:
            return "update_on_kvstore runs the optimizer inside the store"
        kv = tr._kvstore
        if kv is not None:
            if getattr(kv, "_compression", None) is not None:
                return "gradient compression is eager-only"
            distributed = getattr(kv, "num_workers", 1) > 1
            if distributed and self._mesh is None:
                return "multi-worker kvstore without a mesh"
            if self._mesh is not None and \
                    not hasattr(kv, "traced_allreduce") and \
                    kv.is_capable("pushpull"):
                return f"kvstore {type(kv).__name__} has no traced reduce"
        block_params = {id(p): n
                        for n, p in self._net.collect_params().items()}
        seen = set()
        for p in tr._params:
            if p.grad_req == "null":
                continue
            if p.grad_req != "write":
                return (f"param {p.name}: grad_req={p.grad_req!r} "
                        "(grad accumulation is eager-only)")
            if getattr(p, "grad_stype", "default") != "default":
                return f"param {p.name}: sparse gradient"
            if id(p) not in block_params:
                return f"param {p.name} is not owned by the net"
            if id(p) in seen:
                return f"param {p.name} appears twice in the trainer"
            seen.add(id(p))
            if p._data_map is not None and len(p.list_ctx()) > 1:
                return f"param {p.name} is replicated across devices"
        if self._plan is not None:
            # a plan that tensor/FSDP-shards params takes the GSPMD
            # whole-step variant: the step body compiles as ONE donated
            # program over the plan's mesh with every operand entering
            # under its plan sharding — XLA's partitioner inserts the
            # tp psums (and the ZeRO reduce-scatter/allgather the state
            # specs demand) IN-TRACE, where the replicated-params
            # shard_map body would need hand-written model collectives
            names_shapes = [(n, p.shape) for n, p in
                            zip(tr._param_names, tr._params)
                            if p.shape is not None]
            self._tensor_plan = self._plan.shards_params(names_shapes)
        return None

    def _eligible(self):
        if not self._eligibility_checked:
            self._ineligible = self._check_eligibility()
            self._eligibility_checked = True
        return self._ineligible is None

    # -- build -------------------------------------------------------------
    def _build(self):
        tr = self._trainer
        net = self._net
        params = sorted(net.collect_params().items())
        self._block_params = params
        self._name_of = name_of = {id(p): n for n, p in params}
        items = []  # (trainer index, block param name, Parameter)
        for i, p in enumerate(tr._params):
            if p.grad_req == "null":
                continue
            p._check_initialized()
            items.append((i, name_of[id(p)], p))
        tr._ensure_states([(i, p.data()) for i, _n, p in items])
        self._train_items = items
        self._train_index = [i for i, _n, _p in items]
        # bucket by (weight dtype, multi-precision) in trainer order —
        # the exact bucketing update_fused(multi_precision=True) builds,
        # so the unrolled update is the same program member-for-member
        buckets = {}
        for i, n, p in items:
            s = tr._states[i]
            w = p.data()
            use_mp = (isinstance(s, tuple) and len(s) == 2
                      and isinstance(s[0], NDArray)
                      and s[0].dtype == _np.float32
                      and w.dtype != _np.float32)
            buckets.setdefault((str(w.dtype), use_mp), []).append(n)
        self._buckets = [(k, names) for k, names in buckets.items()]
        opt = tr._optimizer
        mode_tag = ("gspmd" if self._tensor_plan
                    else "mesh" if self._mesh is not None else "local")
        self._variant = (f"{type(opt).__name__.lower()}"
                         f"-p{len(items)}-b{len(self._buckets)}"
                         f"-{mode_tag}")
        # layout of the packed hyper-parameter operand, fixed for the
        # life of the compiled program
        self._hyper_keys = tuple(sorted(opt._hyper()))
        self._step_fn = self._make_step_fn()
        self._built = True

    def _make_step_fn(self):
        tstep = self
        net = self._net
        loss_fn = self._loss
        n_data = self._n_data
        params = self._block_params
        tr = self._trainer
        opt = tr._optimizer
        cls = type(opt)
        clip = opt.clip_gradient
        wdtype = {n: p.data().dtype for _i, n, p in self._train_items}
        # position of each trained parameter in the lrs / wds / ts operands
        pos = {n: k for k, (_i, n, _p) in enumerate(self._train_items)}
        hyper_keys = self._hyper_keys
        bucket_specs = self._buckets
        mesh, axis = self._mesh, self._axis
        kv = tr._kvstore
        tensor = self._tensor_plan
        if tensor:
            # GSPMD whole-step (tensor/FSDP plans): the body computes the
            # GLOBAL batch as one logical program — no manual psum; the
            # partitioner derives every collective from the operand
            # shardings plus these in-trace pins. Pinning grads to the
            # ZeRO state layout is what turns the backward's gradient
            # allreduce into reduce-scatter + local fused update +
            # allgather of the new params (docs/sharding.md).
            from jax.sharding import NamedSharding

            plan = self._plan
            pmesh = plan.mesh
            wshape = {n: p.shape for _i, n, p in self._train_items}
            w_shard = {n: NamedSharding(pmesh, plan.spec_for(n, s))
                       for n, s in wshape.items()}
            s_shard = {n: NamedSharding(pmesh, plan.state_spec_for(n, s))
                       for n, s in wshape.items()}
            self._w_shard, self._s_shard = w_shard, s_shard

            def _pin_state(n, st):
                return jax.tree_util.tree_map(
                    lambda v: jax.lax.with_sharding_constraint(
                        v, s_shard[n])
                    if getattr(v, "shape", None) == wshape[n] else v, st)
        elif mesh is not None:
            reduce_tree = (kv.traced_allreduce
                           if kv is not None
                           and hasattr(kv, "traced_allreduce")
                           else None)
            n_shards = mesh.shape[axis]

        from .. import passes as _passes

        # the forward body enters the whole-step program through the
        # graph-pass pipeline (kind=whole_step_fwd): the passes
        # registered on the block rewrite exactly the part of the
        # program they understand, while optimizer state stays outside
        # their reach.  Explicit args (no closure captures) so the
        # pipeline can trace it standalone; resolves to the raw body
        # when no passes apply.
        def block_body(tws_, frozen_, key_, *data_ins):
            pd = dict(frozen_)
            pd.update(tws_)
            out_datas, sink = _traced_forward(
                net, params, True, pd, key_, data_ins)
            # trace-time side effect: which params get aux updates
            tstep._sink_params = list(sink.params)
            return out_datas, tuple(sink.values)

        block_fwd = _passes.wrap_forward(block_body, _passes.PassContext(
            block=net, label="whole_step", variant=self._variant,
            kind="whole_step_fwd", training=True))

        def fwd_bwd(tws, frozen, key, inputs):
            # names on the device: jax.vjp turns these scopes into
            # "jvp(forward)" on the forward's operations and
            # "transpose(jvp(forward))" on the backward's, in the op_name
            # of every HLO instruction (metadata only: the optimized
            # program is the same program)
            def block_of(t):
                with jax.named_scope("forward"):
                    return block_fwd(t, frozen, key, *inputs[:n_data])

            def loss_of(out_datas):
                with jax.named_scope("loss"):
                    out = _wrap_tree(out_datas)
                    labels = [NDArray(x) for x in inputs[n_data:]]
                    loss = loss_fn(out, *labels) if loss_fn is not None \
                        else out
                    if not isinstance(loss, NDArray):
                        raise TypeError(
                            "loss_fn must return a single NDArray, got "
                            f"{type(loss).__name__}")
                    return loss._data

            # the tape differentiates the COMPILED block as one vjp node
            # and the loss ops outside it; splitting the vjp here mirrors
            # that, and the optimization barriers pin the same program
            # boundaries so XLA's excess-precision pass cannot skip the
            # low-precision rounding the eager path performs at each
            # boundary — that elision is where bf16 runs lose bitwise
            # parity with the three-phase path (fp32 is unaffected: the
            # barriers only forbid cross-boundary fusion of two cheap
            # edge tensors, not the matmul fusion inside each segment)
            out_datas, block_vjp, aux = jax.vjp(
                block_of, tws, has_aux=True)
            out_datas = jax.lax.optimization_barrier(out_datas)
            # loss.backward() contract: seed the cotangent with ones of
            # the loss's own shape/dtype (sum-over-elements gradient)
            loss_data, loss_vjp = jax.vjp(loss_of, out_datas)
            (dout,) = loss_vjp(jnp.ones_like(loss_data))
            (gd,) = block_vjp(jax.lax.optimization_barrier(dout))
            # parity: backward lands cotangents in grad buffers of the
            # PARAM dtype before the optimizer sees them — barrier so the
            # multi-precision update's f32 cast cannot fold back into the
            # grad matmuls and skip this rounding
            gd = jax.lax.optimization_barrier(
                {n: g.astype(wdtype[n]) for n, g in gd.items()})
            return loss_data, gd, aux

        # the function's name is the compiled module's (`jit_whole_step`)
        # and so part of the persistent compile cache's key, which leaves
        # every op_name out: a cached executable keeps the scopes it was
        # compiled with, however the program names its work later.
        # Rename it when the scopes below change their meaning.
        def whole_step(tws, frozen, states, key, lrs, wds, ts, hyper,
                       *inputs):
            # host side effect: runs once per jit trace (one XLA
            # compile), never on cache hits — except AOT introspection
            # re-lowers, which must not count as a user-visible retrace
            if not tstep._introspecting:
                tstep._bump_trace()
            if mesh is None:
                # single copy per param: the tpu_dist pushpull of one
                # replica is an identity sum — nothing to reduce
                loss_data, gd, aux = fwd_bwd(tws, frozen, key, inputs)
            elif tensor:
                # global-batch GSPMD: the backward's cross-dp gradient
                # sum is implicit (the partitioner inserts the psum);
                # pin each grad to its state's ZeRO sharding so the
                # update computes on the LOCAL 1/N shard — grads arrive
                # by reduce-scatter instead of full allreduce
                loss_data, gd, aux = fwd_bwd(tws, frozen, key, inputs)
                gd = {n: jax.lax.with_sharding_constraint(g, s_shard[n])
                      if g.shape == wshape[n] else g
                      for n, g in gd.items()}
            else:
                from jax.sharding import PartitionSpec as P

                from ..parallel.collectives import psum_tree_flat_traced

                def sharded(tws_, frozen_, key_, *ins):
                    # params enter replicated; differentiate a VARYING
                    # view of them so the vjp returns per-shard sums —
                    # the cotangent of an unvarying primal is psum'd by
                    # the transpose itself, and the explicit bucketed
                    # psum below would then count each shard n_shards
                    # times
                    tws_ = jax.lax.pcast(tws_, axis, to="varying")
                    loss_d, gd_, aux_ = fwd_bwd(tws_, frozen_, key_, ins)
                    if loss_d.ndim == 0:
                        raise ValueError(
                            "TrainStep with a mesh needs a per-sample "
                            "loss (batch dim kept) so shards concatenate "
                            "back to the global loss; got a scalar")
                    # grads: per-shard sums over local samples — one
                    # flat-bucketed psum completes the global batch sum
                    # inside the SAME program
                    with jax.named_scope("grad_reduce"):
                        if reduce_tree is not None:
                            gd_ = reduce_tree(gd_, axis)
                        else:
                            gd_ = psum_tree_flat_traced(gd_, axis)
                    # aux (BN running stats): cross-replica mean, the
                    # sync-BN convention for data-parallel stats
                    aux_ = jax.tree_util.tree_map(
                        lambda v: jax.lax.psum(v, axis) / n_shards, aux_)
                    return loss_d, gd_, aux_

                sm = jax.shard_map(
                    sharded, mesh=mesh,
                    in_specs=(P(), P(), P(),
                              *([P(axis)] * len(inputs))),
                    out_specs=(P(axis), P(), P()))
                loss_data, gd, aux = sm(tws, frozen, key, *inputs)
            # fused optimizer update, unrolled per bucket — the exact
            # _fused_jitted math (shared body), fused into this program
            new_ws, new_states = {}, {}
            with jax.named_scope("optimizer"):
                lr_of, wd_of, t_of = (_weak_elems(v) for v in (lrs, wds, ts))
                h, scale = _unpack_hyper(hyper_keys, hyper)
                for (_dtype_s, use_mp), names in bucket_specs:
                    nws, nsts = Optimizer._fused_step_body(
                        cls, clip, False, use_mp,
                        [tws[n] for n in names],
                        [states[n] for n in names],
                        [gd[n] for n in names],
                        [lr_of[pos[n]] for n in names],
                        [wd_of[pos[n]] for n in names],
                        [t_of[pos[n]] for n in names],
                        scale, h)
                    for n, nw, ns in zip(names, nws, nsts):
                        new_ws[n] = nw
                        new_states[n] = ns
            if tensor:
                # pin outputs to their operand shardings: the updated
                # params allgather back to the plan's layout (closing
                # the ZeRO reduce_scatter -> local rule -> allgather
                # cycle inside this one program) and state stays 1/N —
                # in == out shardings is also what lets donation reuse
                # the buffers and the jit cache never re-specialize
                new_ws = {n: jax.lax.with_sharding_constraint(
                    w, w_shard[n]) for n, w in new_ws.items()}
                new_states = {n: _pin_state(n, st)
                              for n, st in new_states.items()}
            return loss_data, new_ws, new_states, aux

        return whole_step

    def _bump_trace(self):
        self._traces += 1
        _telemetry.record_trace("whole_step", self._variant)

    def _jitted(self, donate):
        fn = self._jit_variants.get(donate)
        if fn is None:
            from .. import passes as _passes

            # the whole-step program compiles through the pipeline seam
            # too; the forward body was already rewritten via
            # wrap_forward, and the passes that claim kind=whole_step
            # (numerics, sharding) join only when asked for — otherwise
            # this resolves to the plain donated jit
            fn = _passes.apply(self._step_fn, _passes.PassContext(
                label="whole_step", variant=self._variant,
                kind="whole_step", training=True,
                donate_argnums=(0, 2) if donate else (),
                plan=self._plan))
            self._jit_variants[donate] = fn
        return fn

    def _numerics_boundary(self, loss_data, step_args):
        """MXTPU_NUMERICS trip check at the step boundary, BEFORE results
        are written back — a rejected step leaves params/state at their
        pre-step values. ``step`` mode pays no extra host sync: the step
        boundary already waits on the loss, and the effects barrier just
        flushes the callback the device has by then delivered. On a trip
        the recorded program is re-run eagerly (:func:`numerics.bisect`)
        on the live dispatch operands, the attribution lands in an atomic
        postmortem bundle, and :class:`NonFiniteError` carries all of it.
        """
        from ..observability import numerics as _numerics

        jax.block_until_ready(loss_data)
        _numerics.effects_barrier()
        trip = _numerics.take_trip(label_prefix="whole_step")
        if trip is None:
            return
        report = trip.get("equation")  # op mode attributes at the callback
        if report is None:
            with _spans.span("numerics_bisect", cat="sync"):
                self._introspecting = True  # the re-trace is not a retrace
                try:
                    report = _numerics.bisect_callable(
                        self._step_fn, *step_args)
                except Exception:
                    report = None
                finally:
                    self._introspecting = False
            if report is not None:
                trip["equation"] = report
        bundle = None
        try:
            from ..observability import postmortem as _postmortem

            bundle = _postmortem.dump(
                reason="numerics", extra={"numerics_bisect": report})
        except Exception:
            pass
        raise _numerics.NonFiniteError(
            f"non-finite values in the whole-step program at step "
            f"{trip.get('step')}: {_numerics.format_report(report)} "
            f"(postmortem: {bundle})",
            trip=trip, report=report, bundle=bundle)

    # -- execution ---------------------------------------------------------
    def __call__(self, *batch, batch_size=None):
        # the outermost per-step span: in a profiled run the device's
        # work groups under it as step `step_num` (diagnostics/spans.py)
        with _spans.span("train_step", cat=_spans.STEP_CAT,
                         step_num=_spans.current_step()):
            return self._dispatch(batch, batch_size)

    def _dispatch(self, batch, batch_size):
        for a in batch:
            if not isinstance(a, NDArray):
                raise TypeError(
                    f"TrainStep expects NDArray batch elements, got "
                    f"{type(a).__name__}")
        if batch_size is None:
            batch_size = batch[0].shape[self._batch_axis]
        from .. import env as _env

        if not _env.get("MXTPU_WHOLE_STEP"):
            return self._phased(batch, batch_size)
        if not self._built:
            # complete deferred init BEFORE the (cached) eligibility
            # check — it inspects dtypes and device placement
            self._net._ensure_initialized(batch[:self._n_data])
            # deferred-shape params just materialized: the trainer's
            # ShardingPlan (if any) can now place them (no-op otherwise)
            self._trainer._maybe_apply_plan()
        if not self._eligible():
            return self._phased(batch, batch_size)
        if not self._built:
            with _spans.span("train_step.build", cat="compile"):
                self._build()
        return self._whole(batch, batch_size)

    def _phased(self, batch, batch_size):
        """The legacy three-phase sequence (record/forward+loss,
        backward, Trainer.step) — the fallback contract AND the
        reference semantics the whole-step path is proven against."""
        self._last_path = "phased"
        if self._plan is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            # MXTPU_WHOLE_STEP=0 reaches here before __call__'s deferred
            # init ran: materialize params and let the plan place them
            # BEFORE the batch is committed to the mesh below (both are
            # idempotent no-ops otherwise)
            self._net._ensure_initialized(batch[:self._n_data])
            self._trainer._maybe_apply_plan()

            # tensor-sharded plans run here (GSPMD carries the tp axes),
            # but the batch arrives committed to one device while the
            # plan placed params across the mesh — split it along the
            # data axis (replicate when the batch doesn't divide).
            mesh = self._plan.mesh
            dp = self._plan.axis_sizes()[self._plan.batch_axis]
            ax = self._batch_axis

            def _place(a):
                divisible = (len(a.shape) > ax and a.shape[ax] % dp == 0)
                spec = P(*([None] * ax), self._plan.batch_axis) \
                    if divisible else P()
                return NDArray(
                    jax.device_put(a._data, NamedSharding(mesh, spec)))

            batch = tuple(_place(a) for a in batch)
        with ag.record():
            out = self._net(*batch[:self._n_data])
            loss = self._loss(out, *batch[self._n_data:]) \
                if self._loss is not None else out
        loss.backward()
        self._trainer.step(batch_size)
        _telemetry.record_step_dispatch("phased")
        return loss

    def _whole(self, batch, batch_size):
        # children of `train_step`, contiguous, one per part of the call
        # (docs/diagnostics.md has the table; chipbench reads them)
        self._last_path = "whole_step"
        tr = self._trainer
        opt = tr._optimizer
        with _spans.span("train_step.prologue"):
            # the legacy Trainer.step prologue: grads scale by scale/batch
            opt.rescale_grad = tr._scale / batch_size
            # resolve counts/lr/wd in trainer order — the exact sequence
            # update_fused drives, so schedules and Adam's t match bitwise
            # — into one host array per family
            lrs, wds, ts = opt._packed_schedule(self._train_index)
            hyper = opt._packed_hyper(self._hyper_keys)
        with _spans.span("train_step.operands"):
            donate, nmode, tws, frozen, states, key, inputs = \
                self._operands(batch)
            fn = self._jitted(donate)
            before = _cache_size(fn)
        t0 = time.perf_counter()
        with _spans.span("whole_step", cat="fwd"), \
                _watchdog.guard("whole_step"):
            loss_data, new_ws, new_states, aux = fn(
                tws, frozen, states, key, lrs, wds, ts, hyper, *inputs)
        after = _cache_size(fn)
        if after is not None and after != before:
            with _spans.span("train_step.compile_capture", cat="compile"):
                compile_seconds = time.perf_counter() - t0
                _telemetry.record_compile("whole_step", self._variant,
                                          compile_seconds)
                # static for a built program, and 4 when it is sound (the
                # four host arrays above): more means Python scalars
                # leaked back into the call, each one a host-to-device
                # transfer per step
                _telemetry.record_step_scalar_operands(
                    (tws, frozen, states, key, lrs, wds, ts, hyper, inputs))
                # AOT cost/memory analysis of the one-dispatch program for
                # the compile registry (tools/diagnose.py whole-step
                # report); lower against specs — the live buffers were
                # just donated
                self._introspecting = True
                try:
                    _introspect.capture_compile(
                        "whole_step", self._variant, fn,
                        (*_specs((tws, frozen, states, key)), lrs, wds,
                         ts, hyper, *_specs(inputs)),
                        compile_seconds=compile_seconds)
                finally:
                    self._introspecting = False
            # a step compiled: keep what the ring says of the time up to
            # here, for a run that outlives the ring
            _startup.take()
        with _spans.span("train_step.writeback"):
            if nmode != "off":
                self._numerics_boundary(
                    loss_data,
                    (tws, frozen, states, key, lrs, wds, ts, hyper,
                     *inputs))
            # write results back into the live containers (the donated
            # buffers are dead; these are the fresh in-place outputs)
            for i, n, p in self._train_items:
                w = p.data()
                w._data = new_ws[n]
                w._version += 1
                _write_state(tr._states[i], new_states[n])
                # grads were consumed in-program: mark the (untouched)
                # grad buffers stale exactly like the legacy update
                # bookkeeping
                tr._grad_versions[i] = p.grad()._version
            for p, v in zip(self._sink_params, aux):
                target = p.data() if isinstance(p, Parameter) else p
                target._data = v
                target._version += 1
                if getattr(p, "moe_rungs", None):
                    # an expert layer's counters: the array stays on the
                    # device until telemetry.flush_moe_load() is asked
                    _telemetry.stage_moe_load(
                        self._name_of[id(p)].rpartition(".")[0], v,
                        p.moe_rungs)
                elif getattr(p, "stages_exit_mass", False):
                    # likewise, until telemetry.flush_exit_mass()
                    _telemetry.stage_exit_mass(v)
        with _spans.span("train_step.bookkeeping"):
            # what the program's own instrumentation costs per step
            _telemetry.record_step_dispatch(
                "whole_step", _donated_bytes(tws, states) if donate else 0)
            tr._record_step_complete(batch_size)
        return NDArray(loss_data)

    def _operands(self, batch):
        """Gather the whole-step call's array operands from the live
        containers, place them on the mesh, and decide donation.  Returns
        (donate, numerics mode, tws, frozen, states, key, inputs)."""
        tr = self._trainer
        tws, states = {}, {}
        for i, n, p in self._train_items:
            tws[n] = p.data()._data
            states[n] = jax.tree_util.tree_map(
                _unwrap, tr._states[i],
                is_leaf=lambda x: isinstance(x, NDArray))
        frozen = {n: p.data()._data for n, p in self._block_params
                  if n not in tws}
        key = _random.next_key()
        inputs = [a._data for a in batch]
        if self._mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            rep = NamedSharding(self._mesh, P())
            shd = NamedSharding(self._mesh, P(self._axis))
            if self._tensor_plan:
                # GSPMD whole-step: every operand enters under its PLAN
                # sharding (params on their specs, state on the ZeRO
                # layout, batch over the data axis). plan.apply/
                # place_state_like already put them there, so these are
                # no-op puts after step one — they exist to commit
                # stragglers (a fresh frozen buffer, the RNG key).
                plan = self._plan
                tws = {n: jax.device_put(v, self._w_shard[n])
                       for n, v in tws.items()}
                wshape = {n: p.shape for _i, n, p in self._train_items}
                states = {
                    n: jax.tree_util.tree_map(
                        lambda v, _n=n: jax.device_put(
                            v, self._s_shard[_n])
                        if getattr(v, "shape", None) == wshape[_n]
                        else jax.device_put(v, rep), st)
                    for n, st in states.items()}
                frozen = {
                    n: jax.device_put(v, NamedSharding(
                        self._mesh, plan.spec_for(n, v.shape)))
                    for n, v in frozen.items()}
                key = jax.device_put(key, rep)
                inputs = [jax.device_put(x, shd) for x in inputs]
            else:
                # place operands for the shard_map program — params,
                # state and key replicated, batch split along the data
                # axis; jit refuses arrays committed to a single device
                # otherwise. Replicated-to-replicated puts are no-ops
                # after step one (the program's outputs come back
                # replicated).
                def _rep(v):
                    return jax.device_put(v, rep)

                tws = jax.tree_util.tree_map(_rep, tws)
                states = jax.tree_util.tree_map(_rep, states)
                frozen = jax.tree_util.tree_map(_rep, frozen)
                key = _rep(key)
                inputs = [jax.device_put(x, shd) for x in inputs]
        donate = _donate_enabled() and _donation_safe(
            (tws, states), (frozen, inputs, key))
        nmode = _numerics_mode()
        if donate and nmode != "off":
            # any active mode raises from _numerics_boundary BEFORE the
            # writeback loop, so the live param/state containers must
            # still hold valid (pre-step) buffers for a caller that
            # catches NonFiniteError and resumes; step mode additionally
            # bisects by re-running the recorded program on THESE
            # operands — they must survive the dispatch
            donate = False
        return donate, nmode, tws, frozen, states, key, inputs
