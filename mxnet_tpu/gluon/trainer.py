"""Gluon Trainer (reference: python/mxnet/gluon/trainer.py:79).

Applies an Optimizer to a set of Parameters, with gradient aggregation
through a KVStore: per-device gradients are summed (pushpull) and every
device's weight copy updated — the reference's `_allreduce_grads` +
`_update` path (trainer.py:402,451). With kvstore='tpu_dist' the aggregation
is an XLA collective; update_on_kvstore=True runs the optimizer inside the
store (the dist server analog).
"""
from __future__ import annotations

import pickle
import time

from .. import optimizer as opt_mod
from ..diagnostics import spans as _spans
from ..telemetry import instruments as _telemetry
from ..kvstore import KVStoreBase, create as kv_create
from ..ndarray.ndarray import NDArray
from .parameter import Parameter

__all__ = ["Trainer"]


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore=None,
                 compression_params=None, update_on_kvstore=None,
                 batch_axis=0, mesh=None, sharding_plan=None):  # noqa: ARG002
        if isinstance(params, dict):
            param_list = [params[k] for k in sorted(params)]
            self._param_names = sorted(params)
        elif isinstance(params, (list, tuple)):
            param_list = list(params)
            self._param_names = [p.name for p in param_list]
        else:
            raise ValueError("params must be dict/list of Parameters")
        for p in param_list:
            if not isinstance(p, Parameter):
                raise ValueError(f"expected Parameter, got {type(p)}")
        self._params = param_list
        self._scale = 1.0
        optimizer_params = optimizer_params or {}
        self._optimizer = opt_mod.create(optimizer, **optimizer_params) \
            if not isinstance(optimizer, opt_mod.Optimizer) else optimizer
        self._optimizer.param_dict = dict(enumerate(self._params))
        self._states = [None] * len(self._params)
        self._states_created = [False] * len(self._params)
        if isinstance(kvstore, KVStoreBase):
            self._kvstore = kvstore
        elif isinstance(kvstore, str) and kvstore not in (None, "None"):
            self._kvstore = kv_create(kvstore)
        else:
            self._kvstore = None
        if compression_params is not None:
            if self._kvstore is None:
                raise ValueError(
                    "compression_params requires a kvstore")
            self._kvstore.set_gradient_compression(compression_params)
        self._update_on_kvstore = bool(update_on_kvstore) and \
            self._kvstore is not None
        if self._update_on_kvstore:
            self._kvstore.set_optimizer(self._optimizer)
        self._kv_initialized = False
        # hybrid parallelism (mxnet_tpu/sharding; docs/sharding.md):
        # mesh= is the axes shorthand (Trainer(..., mesh=(('dp', -1),))),
        # sharding_plan= the full object; resolve_plan folds in
        # MXTPU_MESH/MXTPU_SHARDING, returning None when the subsystem is
        # off or nothing names a mesh — that None keeps every path below
        # bitwise-identical to the unsharded trainer.
        from ..sharding import resolve_plan as _resolve_plan

        self._sharding_plan = _resolve_plan(
            sharding_plan if sharding_plan is not None else mesh)
        self._plan_applied = False
        if self._sharding_plan is not None and self._kvstore is not None:
            setter = getattr(self._kvstore, "set_sharding_plan", None)
            if setter is not None:
                setter(self._sharding_plan)
        self._maybe_apply_plan()
        self._last_step_end = None  # telemetry: previous step() finish
        # param index -> grad buffer version seen at its last update;
        # a matching version means the grad is STALE (nothing backprop'd
        # into it since) — see update()/allreduce_grads()
        self._grad_versions = {}

    @property
    def optimizer(self):
        return self._optimizer

    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    @property
    def sharding_plan(self):
        """The resolved ShardingPlan, or None (unsharded)."""
        return self._sharding_plan

    def set_sharding_plan(self, plan):
        """Swap this trainer onto a new ShardingPlan (or None ->
        replicated) — the elastic re-entry hook (mxnet_tpu/elastic;
        docs/elasticity.md). Re-places params + grads under the new
        plan immediately when params are live, and re-places created
        optimizer state per the new plan's ZeRO state specs, so state
        saved 1/N along one fsdp axis re-extends along the new one.
        Callers owning a TrainStep must also call its rebuild() — the
        compiled whole-step program bakes the old mesh in."""
        self._sharding_plan = plan
        self._plan_applied = False
        if self._kvstore is not None:
            setter = getattr(self._kvstore, "set_sharding_plan", None)
            if setter is not None:
                setter(plan)
        if plan is None:
            # dropping to replicated: pull live params/grads/state back
            # onto the default device — an old mesh placement left in
            # place poisons the next compiled program with mixed-device
            # operands
            import jax

            if not any(p._data_map is None for p in self._params):
                dev = jax.devices()[0]
                for i, p in enumerate(self._params):
                    for arr in p._data_map.values():
                        arr._data = jax.device_put(arr._data, dev)
                        arr._version += 1
                        if arr._grad is not None:
                            arr._grad._data = jax.device_put(
                                arr._grad._data, dev)
                            arr._grad._version += 1
                    if self._states_created[i]:
                        opt_mod.place_state_like(self._states[i],
                                                 p.data())
            return
        self._maybe_apply_plan()
        if self._plan_applied:
            for i, p in enumerate(self._params):
                if self._states_created[i]:
                    opt_mod.place_state_like(
                        self._states[i], p.data(), plan=plan,
                        name=self._param_names[i])

    def _maybe_apply_plan(self):
        """Place every param (+grads) per the plan, once all params are
        initialized.  Deferred-shape models initialize at first forward,
        so this is re-checked lazily from __init__, step()/update(), and
        TrainStep — it no-ops after the first successful application and
        instantly when there is no plan."""
        plan = self._sharding_plan
        if plan is None or self._plan_applied:
            return
        if any(p._data_map is None for p in self._params):
            return  # deferred init still pending; try again next call
        plan.apply(dict(zip(self._param_names, self._params)),
                   label="trainer")
        self._plan_applied = True

    def _ensure_states(self, items):
        """Create the optimizer state of every (index, weight) of `items`
        that has none yet: all of them in one program."""
        items = [(i, w) for i, w in items if not self._states_created[i]]
        if not items:
            return
        with _spans.span("trainer.create_states", cat="compile"):
            states = self._optimizer.create_states_multi_precision(
                *zip(*items))
        for (i, weight), state in zip(items, states):
            self._states[i] = state
            self._states_created[i] = True
            if self._plan_applied:
                # optimizer state (momentum, fp32 master copies, fused
                # bucket slices) mirrors its weight's shape — give it
                # the weight's placement so updates stay local to each
                # shard instead of pulling state cross-device; under a
                # ZeRO plan (fsdp axis + MXTPU_ZERO) it lands on the
                # sharded-bucket layout instead, 1/N per rank
                opt_mod.place_state_like(
                    state, weight, plan=self._sharding_plan,
                    name=self._param_names[i])

    def allreduce_grads(self, ignore_stale_grad=False):
        """Aggregate gradients across device copies via the kvstore
        (reference: trainer.py:402 _allreduce_grads).

        With the fused path on (MXTPU_FUSED_UPDATE, default) all params
        go to the store in ONE list-form pushpull, which tpu_dist turns
        into a bucketed flat allreduce — one reduce dispatch per ~25 MB
        dtype-homogeneous buffer instead of one per param. Otherwise
        calls are issued per param in descending priority (priority=-i,
        so layer 0 first — its weights gate the next forward), the P3
        dispatch-order contract (src/kvstore/p3store_dist.h).

        `ignore_stale_grad` skips params whose grad buffer is STALE
        (untouched since their last update): reducing one would both sum
        garbage into live gradients and bump the buffer's version, making
        update() mistake it for fresh.
        """
        kv = self._kvstore
        if kv is None:
            return
        distributed = getattr(kv, "num_workers", 1) > 1 or \
            kv.is_capable("pushpull")
        from .. import env as _env

        fused = _env.get("MXTPU_FUSED_UPDATE")
        keys, vals = [], []
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            grads = p.list_grad()
            if len(grads) == 1 and not distributed:
                continue  # single copy, local store: nothing to reduce
            if ignore_stale_grad and \
                    self._grad_versions.get(i) == grads[0]._version:
                continue
            if fused:
                keys.append(i)
                vals.append(grads)
            else:
                kv.pushpull(i, grads, out=grads, priority=-i)
        if fused and keys:
            if len(keys) == 1:
                kv.pushpull(keys[0], vals[0], out=vals[0], priority=0)
            else:
                kv.pushpull(keys, vals, out=vals, priority=0)

    def step(self, batch_size, ignore_stale_grad=False):
        """allreduce + optimizer update, scaling grads by 1/batch_size
        (reference: trainer.py:341)."""
        self._optimizer.rescale_grad = self._scale / batch_size
        self._maybe_apply_plan()
        with _spans.span("allreduce_grads", cat="collective"):
            self.allreduce_grads(ignore_stale_grad)
        with _spans.span("optimizer_update", cat="optimizer"):
            self.update(batch_size, ignore_stale_grad, _skip_rescale=True)
        self._record_step_complete(batch_size)

    def _record_step_complete(self, batch_size):
        """Per-iteration bookkeeping shared by step() and the whole-step
        compiled path (gluon.TrainStep): close the span bucket, time the
        step interval."""
        # close this iteration's step bucket: fwd/bwd spans recorded since
        # the previous step() and the update phases all share one index
        _spans.mark_step()
        # step-time = interval between consecutive step() completions, so
        # the histogram sees the FULL iteration (data + fwd + bwd + update
        # dispatch); the first step is counted but not timed. The MFU
        # gauge follows when telemetry.set_flop_budget() declared a
        # per-step FLOP cost (docs/telemetry.md).
        now = time.perf_counter()
        last = self._last_step_end
        self._last_step_end = now
        _telemetry.observe_step(
            None if last is None else now - last, examples=batch_size)
        try:
            # the flight recorder's per-step heartbeat: carries enough to
            # read training health off a postmortem (loss arrives via
            # flight.record_loss when a loop host-syncs it)
            from ..observability import flight as _flight

            _flight.record(
                "step", examples=batch_size,
                lr=getattr(self._optimizer, "learning_rate", None),
                dt=None if last is None else now - last)
        except Exception:
            pass

    def update(self, batch_size, ignore_stale_grad=False,
               _skip_rescale=False):
        if not _skip_rescale:
            self._optimizer.rescale_grad = self._scale / batch_size
            self._maybe_apply_plan()
        from .. import env as _env

        # fused multi-tensor path (default): single-device dense params
        # are collected into ONE list-form update_multi_precision call —
        # the optimizer buckets them by (dtype, multi-precision) and runs
        # one donated jit dispatch per bucket. Sparse grads and params
        # replicated across devices stay on the legacy per-param loop.
        fuse = _env.get("MXTPU_FUSED_UPDATE") and \
            self._optimizer._supports_fused()
        todo = []       # (index, parameter, primary weight, its gradient)
        for i, p in enumerate(self._params):
            if p.grad_req == "null":
                continue
            p._check_initialized()
            dev = p.list_ctx()[0]   # update primary; replicate below
            g = p.grad(dev)
            # stale = grad buffer untouched since the last update
            # (reference: Parameter._fresh_grad per-step flag)
            fresh = self._grad_versions.get(i) != g._version
            if not ignore_stale_grad or fresh:
                todo.append((i, p, p.data(dev), g))
        self._ensure_states([(i, w) for i, _p, w, _g in todo])
        f_idx, f_w, f_g, f_s = [], [], [], []
        for i, p, w, g in todo:
            if getattr(p, "grad_stype", "default") == "row_sparse":
                # hand the optimizer only the touched rows
                # (lazy_update semantics; Parameter docs)
                self._optimizer.update_multi_precision(
                    i, w, p._as_row_sparse_grad(g), self._states[i])
            elif fuse and len(p.list_ctx()) == 1:
                f_idx.append(i)
                f_w.append(w)
                f_g.append(g)
                f_s.append(self._states[i])
            else:
                self._optimizer.update_multi_precision(
                    i, w, g, self._states[i])
            self._grad_versions[i] = g._version
        for p in self._params:
            if p.grad_req != "null" and len(p.list_ctx()) > 1:
                primary = p.data(p.list_ctx()[0])
                for dev in p.list_ctx()[1:]:
                    primary.copyto(p.data(dev))
        if f_idx:
            self._optimizer.update_fused(f_idx, f_w, f_g, f_s,
                                         multi_precision=True)

    def zero_grad(self):
        for p in self._params:
            if p.grad_req != "null" and p._data_map is not None:
                p.zero_grad()

    # -- checkpoint --------------------------------------------------------
    # (For complete atomic checkpoints — params + states + RNG + resume —
    # use mx.checkpoint.CheckpointManager; these two round-trip ONLY the
    # optimizer side, the reference save_states/load_states contract.)
    def _stale_indices(self):
        """Param indices whose grad buffer is currently STALE (untouched
        since its last update) — the portable form of _grad_versions,
        whose raw buffer versions are process-local."""
        stale = []
        for i, p in enumerate(self._params):
            if p.grad_req == "null" or p._data_map is None:
                continue
            grads = p.list_grad()
            if grads and self._grad_versions.get(i) == grads[0]._version:
                stale.append(i)
        return stale

    def save_states(self, fname):
        """Serialize optimizer states (reference: trainer.py:489).

        Format 2 additionally round-trips the fused/legacy-shared state
        bookkeeping (per-param update counts `t`), stale-grad tracking,
        loss scale, and per-param (name, dtype) so load_states can
        reject a payload from a different model instead of mis-zipping.
        """
        def to_np(s):
            if s is None:
                return None
            if isinstance(s, NDArray):
                return s.asnumpy()
            return [to_np(x) for x in s]

        payload = {
            "format": 2,
            "states": [to_np(s) for s in self._states],
            "created": list(self._states_created),
            "num_update": self._optimizer.num_update,  # format-1 readers
            "optimizer": self._optimizer.bookkeeping_state(),
            "param_meta": [
                (p.name, str(p.dtype) if p.dtype is not None else None)
                for p in self._params],
            "stale": self._stale_indices(),
            "scale": self._scale,
        }
        with open(fname, "wb") as f:
            pickle.dump(payload, f)

    def load_states(self, fname):
        """Inverse of save_states. Raises ValueError (clear message, no
        state touched) when the payload's param count or dtypes don't
        match this trainer. Format-1 payloads still load."""
        import jax.numpy as jnp

        with open(fname, "rb") as f:
            payload = pickle.load(f)

        states = payload["states"]
        if len(states) != len(self._params):
            raise ValueError(
                f"optimizer-state payload {fname!r} holds "
                f"{len(states)} parameter states but this trainer has "
                f"{len(self._params)} parameters — wrong model or "
                f"stale checkpoint")
        for i, (name, dt) in enumerate(payload.get("param_meta") or []):
            p = self._params[i]
            have = str(p.dtype) if p.dtype is not None else None
            if dt is not None and have is not None and dt != have:
                raise ValueError(
                    f"optimizer-state payload {fname!r}: param {i} "
                    f"({name!r}) was saved with dtype {dt}, trainer "
                    f"param {p.name!r} declares {have}")

        def from_np(s):
            if s is None:
                return None
            if isinstance(s, list):
                return tuple(from_np(x) for x in s)
            return NDArray(jnp.asarray(s))

        self._states = [from_np(s) for s in states]
        self._states_created = list(payload["created"])
        opt_state = payload.get("optimizer")
        if opt_state is not None:
            self._optimizer.load_bookkeeping_state(opt_state)
        else:
            self._optimizer.num_update = payload["num_update"]
        if "scale" in payload:
            self._scale = float(payload["scale"])
        if "stale" in payload:
            # re-mark stale grads against THIS process's buffer versions
            self._grad_versions = {}
            for i in payload["stale"]:
                p = self._params[i]
                if p.grad_req != "null" and p._data_map is not None:
                    grads = p.list_grad()
                    if grads:
                        self._grad_versions[i] = grads[0]._version
