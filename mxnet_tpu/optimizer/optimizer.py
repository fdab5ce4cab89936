"""Optimizer base + algorithm zoo.

API parity with the reference Optimizer (python/mxnet/optimizer/optimizer.py):
create_state(index, weight) / update(index, weight, grad, state),
lr_scheduler + lr_mult/wd_mult, rescale_grad, clip_gradient,
update_multi_precision (fp32 master weights for bf16/fp16 params).

Each algorithm implements `_rule(w, g, state, lr, wd, hyper) -> (new_w,
new_state)` as a pure jax function; `update()` runs it through a per-class
jit cache and swaps the weight handle in place (engine version bump).

List inputs take the FUSED multi-tensor path (docs/performance.md): params
are bucketed by (weight dtype, multi-precision) and each bucket runs ONE
donated jit dispatch doing rescale → global-norm clip → per-element clip →
`_rule` for every member — O(buckets) dispatches instead of O(params), with
weight/state buffers donated so XLA updates them in place. Per-param lr/wd/
update-counts enter as one host array per family (`_weak_elems` reads them
out inside the program), so schedule changes never retrace.
MXTPU_FUSED_UPDATE=0 restores the per-param loop.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import registry
from ..diagnostics import spans as _spans
from ..diagnostics import watchdog as _watchdog
from ..ndarray.ndarray import NDArray, _wrap_out, device_groups
from ..telemetry import instruments as _telemetry

_REG = registry("optimizer")

__all__ = ["Optimizer", "register", "create", "place_state_like"]


def register(klass):
    _REG.register(klass)
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _REG.create(name, **kwargs)


def _unwrap(x):
    return x._data if isinstance(x, NDArray) else x


def _spread(x):
    """Does this array live on more than one device?"""
    return isinstance(x, jax.Array) and len(x.sharding.device_set) > 1


def _cache_size(fn):
    """Trace-cache entry count of a jitted fn (None when the jax version
    doesn't expose it) — comparing before/after a dispatch detects
    retraces for the compile registry."""
    get = getattr(fn, "_cache_size", None)
    try:
        return get() if get is not None else None
    except Exception:
        return None


def _donate_enabled():
    from .. import env as _env

    return _env.get("MXTPU_DONATE_UPDATE")


def _leaf_ids(*trees):
    out = []
    for t in trees:
        out.extend(id(x) for x in jax.tree_util.tree_leaves(t))
    return out


def _donation_safe(donated, protected=()):
    """True when every would-be-donated buffer is unique and none aliases
    a non-donated argument. Donating a buffer that appears twice in the
    call (weight tying, a test passing the grad as its own weight) makes
    XLA read a dead input — INVALID_ARGUMENT at dispatch — so such calls
    fall back to the copying variant."""
    ids = _leaf_ids(*donated)
    seen = set(ids)
    if len(ids) != len(seen):
        return False
    return not any(pid in seen for pid in _leaf_ids(*protected))


def _specs(tree):
    """Shape/dtype skeleton of an argument tree — what capture_compile
    lowers against AFTER the live buffers were donated into the step.  A
    committed array's spec keeps its sharding: where an operand lives is
    part of what jit lowered for, and a spec that leaves it out lowers
    and compiles the whole program a second time."""
    def spec(x):
        if not hasattr(x, "shape"):
            return x
        where = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=where)

    return jax.tree_util.tree_map(spec, tree)


def _donated_bytes(*trees):
    return sum(_telemetry.nbytes_of(x)
               for t in trees for x in jax.tree_util.tree_leaves(t))


class Optimizer:
    """Base optimizer (reference: optimizer.py:Optimizer)."""

    _jit_cache = {}

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, clip_global_norm=None,
                 learning_rate=None, lr_scheduler=None,
                 multi_precision=False, param_dict=None, aggregate_num=None,
                 use_fused_step=True, lazy_update=True,
                 **kwargs):  # noqa: ARG002
        # lazy_update (reference: optimizer/sgd.py:36-95): with a
        # row_sparse gradient, update ONLY the rows present in the grad
        # (weight decay / state decay on untouched rows is deferred).
        # False densifies the grad and applies the rule to every row.
        self.lazy_update = lazy_update
        self.rescale_grad = rescale_grad
        self.lr = learning_rate if learning_rate is not None else 0.01
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None and learning_rate is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        # clip_global_norm: scale the WHOLE gradient set so its joint L2
        # norm stays under this bound (fused path only; per-bucket sqnorm
        # pre-pass, host-combined). None = off.
        self.clip_global_norm = clip_global_norm
        self.multi_precision = multi_precision
        self.num_update = 0
        self._index_update_count = {}
        self.idx2name = param_idx2name or {}
        self.param_dict = param_dict or {}
        self.lr_mult = {}
        self.wd_mult = {}

    # -- hyperparameter plumbing (parity) --------------------------------
    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise ValueError("lr_scheduler is set; cannot set learning rate")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = dict(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = 0
        self._index_update_count[index] += 1
        self.num_update = max(self.num_update,
                              self._index_update_count[index])

    # -- checkpoint bookkeeping ------------------------------------------
    def bookkeeping_state(self):
        """JSON-able schedule state: `num_update` drives lr_scheduler and
        the per-param counts are each param's `t` (Adam bias correction).
        Omitting these from a checkpoint silently restarts schedules —
        resume would NOT be bitwise-identical."""
        return {
            "num_update": int(self.num_update),
            "index_update_count": {
                int(k): int(v) for k, v in self._index_update_count.items()
            },
        }

    def load_bookkeeping_state(self, state):
        """Inverse of bookkeeping_state (keys arrive as str after a JSON
        round-trip)."""
        self.num_update = int(state.get("num_update", 0))
        self._index_update_count = {
            int(k): int(v)
            for k, v in (state.get("index_update_count") or {}).items()
        }

    def _get_lr(self, index):
        lr = self.learning_rate
        param = self.param_dict.get(index)
        if param is not None:
            lr *= getattr(param, "lr_mult", 1.0)
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        param = self.param_dict.get(index)
        if param is not None:
            wd *= getattr(param, "wd_mult", 1.0)
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- state ------------------------------------------------------------
    def create_state(self, index, weight):  # noqa: ARG002
        return None

    def create_state_multi_precision(self, index, weight):
        return self.create_states_multi_precision([index], [weight])[0]

    def create_states_multi_precision(self, indices, weights):
        """The state of every (index, weight): `create_state`, under a
        float32 master where `multi_precision` asks for one, traced over
        the whole list into ONE program (one per set of devices).  The
        masters' casts and the states' zeros of a model are then one
        compile, not one per distinct shape and type.  `create_state` is
        traced: one that needs the weight's values on the host (a user's
        own optimizer calling `asnumpy`) runs eagerly instead."""
        def one(index, data, where):
            weight = NDArray(data)
            if self.multi_precision and \
                    weight.dtype.name in ("float16", "bfloat16"):
                master = NDArray(data.astype(jnp.float32))
                state = (master, self.create_state(index, master))
            else:
                state = self.create_state(index, weight)

            def leaf(x):
                x = _unwrap(x)
                # zeros read nothing of a weight that is spread over
                # devices, and the compiler would make them whole on
                # every one: a leaf of the weight's shape is laid out as
                # the weight is, which is what the eager `zeros_like` did
                if where is not None and getattr(x, "shape", None) == data.shape:
                    x = jax.lax.with_sharding_constraint(x, where)
                return x

            return jax.tree_util.tree_map(
                leaf, state, is_leaf=lambda x: isinstance(x, NDArray))

        datas = [w._data for w in weights]
        wheres = [d.sharding if _spread(d) else None for d in datas]
        states = [None] * len(datas)
        for ks in device_groups(datas):
            def create_states(group, ks=ks):
                return [one(indices[k], d, wheres[k])
                        for k, d in zip(ks, group)]

            group = [datas[k] for k in ks]
            try:
                # keep_unused: zeros read nothing of the weight, and jit
                # would drop it from the call, and with it where the
                # state lives
                made = jax.jit(create_states, keep_unused=True)(group)
            except jax.errors.JAXTypeError:     # host code under the tracer
                made = create_states(group)
            for k, st in zip(ks, made):
                states[k] = jax.tree_util.tree_map(NDArray, st)
        return states

    # -- hyper vector passed into the jitted rule -------------------------
    def _hyper(self):
        """Dynamic (non-recompiling) hyperparameters as a dict of scalars."""
        return {}

    def _packed_schedule(self, indices):
        """Advance the update counts of `indices` and resolve their lr /
        wd, one parameter after the other (so num_update-driven schedules
        and Adam's t see exactly the per-parameter loop's sequence), into
        the three per-parameter operands of a fused program: float32[n],
        float32[n], int32[n]. A Python scalar among a jitted call's
        operands is a host-to-device transfer of its own on every call;
        a vector is one, whatever n is."""
        n = len(indices)
        lrs = _np.empty(n, _np.float32)
        wds = _np.empty(n, _np.float32)
        ts = _np.empty(n, _np.int32)
        for k, i in enumerate(indices):
            self._update_count(i)
            lrs[k] = self._get_lr(i)
            wds[k] = self._get_wd(i)
            ts[k] = self._index_update_count[i]
        return lrs, wds, ts

    def _packed_hyper(self, keys, scale=1.0):
        """The one hyper-parameter operand of a fused program: float32
        [`_hyper()` in the order of `keys`, rescale_grad, global-norm
        scale] — `_unpack_hyper` is its inverse inside the program."""
        hyper = self._hyper()
        return _np.asarray([*(hyper[k] for k in keys), self.rescale_grad,
                            scale], _np.float32)

    # -- the pure rule; subclasses override -------------------------------
    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        raise NotImplementedError

    def _preprocess(self, g, w, wd, hyper):  # noqa: ARG002
        g = g * hyper["rescale_grad"]
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g

    def _jitted(self, donate=False):
        cls = type(self)
        key = (cls, self.clip_gradient, donate)
        fn = Optimizer._jit_cache.get(key)
        if fn is None:
            clip = self.clip_gradient

            def step(w, g, state, lr, wd, hyper):
                lr, wd = _weak32(lr), _weak32(wd)
                hyper = {k: _weak32(v) for k, v in hyper.items()}
                g = g * hyper["rescale_grad"]
                if clip is not None:
                    g = jnp.clip(g, -clip, clip)
                return cls._rule(w, g, state, lr, wd, hyper)

            fn = jax.jit(step, donate_argnums=(0, 2) if donate else ())
            Optimizer._jit_cache[key] = fn
        return fn

    def _supports_fused(self):
        """The fused bucketed step runs the class `_rule` under a shared
        rescale/clip prologue — optimizers that override the imperative
        `update`/`update_multi_precision` entry points (SGLD's Langevin
        noise) or never define `_rule` must take the legacy loop."""
        cls = type(self)
        return (cls.update is Optimizer.update
                and cls.update_multi_precision
                is Optimizer.update_multi_precision
                and cls._rule is not Optimizer._rule)

    @staticmethod
    def _fused_param_step(cls, clip, gn, mp, w, st, g, lr, wd, t, scale,
                          hyper):
        """One parameter's ladder inside a fused bucket: rescale →
        global-norm scale → per-element clip → `cls._rule` (→ master
        cast)."""
        h = {k: _weak32(v) for k, v in hyper.items()}
        h["t"] = t
        lr, wd, scale = _weak32(lr), _weak32(wd), _weak32(scale)
        if mp:
            # legacy update_multi_precision order: cast the
            # low-precision grad to f32 FIRST, then rescale/
            # clip on the f32 master
            master, inner = st
            g = g.astype(jnp.float32)
        g = g * h["rescale_grad"]
        if gn:
            g = g * scale
        if clip is not None:
            g = jnp.clip(g, -clip, clip)
        if mp:
            nm, ni = cls._rule(master, g, inner, lr, wd, h)
            return nm.astype(w.dtype), (nm, ni)
        return cls._rule(w, g, st, lr, wd, h)

    @staticmethod
    def _fused_step_body(cls, clip, gn, mp, ws, states, gs, lrs, wds, ts,
                         scale, hyper):
        """Traced body of one fused bucket, unrolled over the bucket at
        trace time. Shared verbatim by `_fused_jitted` and the whole-step
        compiled path (gluon/train_step.py) so both produce bitwise-equal
        numerics — same op order, same dtype promotion."""
        new_ws, new_states = [], []
        for w, st, g, lr, wd, t in zip(ws, states, gs, lrs, wds, ts):
            nw, ns = Optimizer._fused_param_step(
                cls, clip, gn, mp, w, st, g, lr, wd, t, scale, hyper)
            new_ws.append(nw)
            new_states.append(ns)
        return new_ws, new_states

    def _fused_jitted(self, n, mp, donate):
        """One jit for a whole bucket of n same-dtype params: the python
        loop unrolls at trace time into a single XLA program (the
        multi-tensor-apply analog), weights+states donated so outputs
        reuse their HBM. lr/wd/t arrive as three host vectors of length n
        (float32, float32, int32) and the hyper-parameters as one
        (`_packed_hyper`): four transfers a call whatever n is, and
        VALUES never retrace. `_weak_elems` hands the rule weak scalars,
        which preserves the legacy dtype promotion (bf16 math stays
        bf16)."""
        cls = type(self)
        hkeys = tuple(sorted(self._hyper()))
        gn = self.clip_global_norm is not None
        key = (cls, self.clip_gradient, "fused", n, mp, gn, donate, hkeys)
        fn = Optimizer._jit_cache.get(key)
        if fn is None:
            clip = self.clip_gradient

            def step(ws, states, gs, lrs, wds, ts, hvec):
                hyper, scale = _unpack_hyper(hkeys, hvec)
                return Optimizer._fused_step_body(
                    cls, clip, gn, mp, ws, states, gs, _weak_elems(lrs),
                    _weak_elems(wds), _weak_elems(ts), scale, hyper)

            fn = jax.jit(step, donate_argnums=(0, 1) if donate else ())
            Optimizer._jit_cache[key] = fn
        return fn

    @staticmethod
    def _fused_norm_jitted(n):
        """Per-bucket Σg² pre-pass for clip_global_norm (f32 accumulate);
        buckets' partial sums combine on host into the one global scale."""
        key = ("fused_norm", n)
        fn = Optimizer._jit_cache.get(key)
        if fn is None:
            def sqnorm(gs, rescale):
                total = jnp.zeros((), jnp.float32)
                for g in gs:
                    g32 = g.astype(jnp.float32) * rescale
                    total = total + jnp.sum(g32 * g32)
                return total

            fn = jax.jit(sqnorm)
            Optimizer._jit_cache[key] = fn
        return fn

    def _sparse_jitted(self, donate=False):
        """Row-sparse lazy update: gather the touched rows, run the SAME
        rule, scatter the deltas back (reference: the row_sparse kernels
        in src/operator/optimizer_op.cc). Out-of-range indices (the
        fixed-size-unique padding) are clamped on gather and DROPPED on
        scatter by XLA, so padded slots are no-ops; index arrays are
        padded to power-of-two buckets to bound recompiles."""
        cls = type(self)
        key = (cls, self.clip_gradient, "row_sparse", donate)
        fn = Optimizer._jit_cache.get(key)
        if fn is None:
            clip = self.clip_gradient

            def step(w, gvals, idx, state, lr, wd, hyper):
                g = gvals * hyper["rescale_grad"]
                if clip is not None:
                    g = jnp.clip(g, -clip, clip)
                w_rows = w[idx]
                s_rows = jax.tree_util.tree_map(lambda s: s[idx], state)
                nw_rows, ns_rows = cls._rule(w_rows, g, s_rows, lr, wd,
                                             hyper)
                # rows whose grad is exactly zero are no-ops: a stale
                # forward-recorded hint (e.g. a recorded probe forward
                # that was never backpropagated) must not decay rows the
                # backward never touched
                live = jnp.any(g != 0, axis=tuple(range(1, g.ndim)))
                mrow = live.reshape((-1,) + (1,) * (w_rows.ndim - 1))
                new_w = w.at[idx].add(
                    jnp.where(mrow, nw_rows - w_rows, 0).astype(w.dtype))
                new_state = jax.tree_util.tree_map(
                    lambda s, ns: s.at[idx].add(
                        jnp.where(live.reshape(
                            (-1,) + (1,) * (s[idx].ndim - 1)),
                            ns - s[idx], 0).astype(s.dtype)),
                    state, ns_rows)
                return new_w, new_state

            fn = jax.jit(step, donate_argnums=(0, 3) if donate else ())
            Optimizer._jit_cache[key] = fn
        return fn

    # rules whose update couples rows (layer-wise norms) cannot run on a
    # gathered row subset — they densify instead of silently mis-scaling
    _row_local = True

    def _update_row_sparse(self, index, weight, grad, state):
        from ..ndarray.sparse import RowSparseNDArray

        assert isinstance(grad, RowSparseNDArray)
        if not self.lazy_update or not type(self)._row_local:
            self.update(index, weight, grad.todense(), state)
            return
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        hyper = dict(self._hyper())
        hyper["rescale_grad"] = self.rescale_grad
        hyper["t"] = self._index_update_count[index]
        idx = grad.indices
        vals = grad.data.astype(weight._data.dtype)
        k = idx.shape[0]
        bucket = 1 << max(0, int(k - 1).bit_length())
        if bucket > k:   # pad with out-of-range rows (dropped on scatter)
            pad = bucket - k
            idx = jnp.concatenate(
                [idx, jnp.full((pad,), weight.shape[0], idx.dtype)])
            vals = jnp.concatenate(
                [vals, jnp.zeros((pad,) + vals.shape[1:], vals.dtype)])
        state_data = jax.tree_util.tree_map(
            _unwrap, state, is_leaf=lambda x: isinstance(x, NDArray))
        donate = _donate_enabled() and _donation_safe(
            (weight._data, state_data), (vals, idx))
        new_w, new_state = self._sparse_jitted(donate)(
            weight._data, vals, idx, state_data, lr, wd, hyper)
        _telemetry.record_update_dispatch(
            "sparse",
            _donated_bytes(weight._data, state_data) if donate else 0)
        weight._data = new_w
        weight._version += 1
        _write_state(state, new_state)

    # -- public update ----------------------------------------------------
    def update(self, index, weight, grad, state):
        """Single-param update; list inputs take the fused bucketed step
        (one donated dispatch per dtype bucket — docs/performance.md)."""
        if isinstance(index, (list, tuple)):
            self._update_list(index, weight, grad, state,
                              multi_precision=False)
            return
        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(grad, RowSparseNDArray):
            self._update_row_sparse(index, weight, grad, state)
            return
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        hyper = dict(self._hyper())
        hyper["rescale_grad"] = self.rescale_grad
        hyper["t"] = self._index_update_count[index]
        state_data = jax.tree_util.tree_map(
            _unwrap, state, is_leaf=lambda x: isinstance(x, NDArray))
        donate = _donate_enabled() and _donation_safe(
            (weight._data, state_data), (grad._data,))
        new_w, new_state = self._jitted(donate)(
            weight._data, grad._data, state_data, lr, wd, hyper)
        _telemetry.record_update_dispatch(
            "per_param",
            _donated_bytes(weight._data, state_data) if donate else 0)
        weight._data = new_w
        weight._version += 1
        _write_state(state, new_state)

    def _update_list(self, index, weight, grad, state, multi_precision):
        from .. import env as _env

        if _env.get("MXTPU_FUSED_UPDATE") and self._supports_fused():
            self.update_fused(index, weight, grad, state,
                              multi_precision=multi_precision)
            return
        for i, w, g, s in zip(index, weight, grad, state):
            if multi_precision:
                self.update_multi_precision(i, w, g, s)
            else:
                self.update(i, w, g, s)

    def update_fused(self, index, weight, grad, state,
                     multi_precision=False):
        """Fused multi-tensor update: ONE donated jit dispatch per
        (weight dtype, multi-precision) bucket covering the whole list —
        rescale → global-norm clip → per-element clip → `_rule` — with
        per-param lr/wd/t packed into one host vector each (and the
        hyper-parameters into a fourth) so an LR schedule never retraces
        and a bucket costs four transfers, not 3 per parameter. Sparse
        grads peel off to the legacy per-param path;
        numerics match the per-param loop bitwise (same op order, same
        dtype promotion)."""
        from ..ndarray.sparse import RowSparseNDArray

        dense = []
        for i, w, g, s in zip(index, weight, grad, state):
            if isinstance(g, RowSparseNDArray):
                if multi_precision:
                    self.update_multi_precision(i, w, g, s)
                else:
                    self.update(i, w, g, s)
                continue
            dense.append((i, w, g, s))
        # resolve hyperparams in list order so num_update-driven
        # schedules see exactly the legacy per-param sequence
        lrs, wds, ts = self._packed_schedule([it[0] for it in dense])
        buckets = {}
        for k, (i, w, g, s) in enumerate(dense):
            use_mp = (multi_precision
                      and isinstance(s, tuple) and len(s) == 2
                      and isinstance(s[0], NDArray)
                      and s[0].dtype == _np.float32
                      and w.dtype != _np.float32)
            buckets.setdefault((str(w.dtype), use_mp), []).append(
                (i, w, g, s, k))
        if not buckets:
            return
        scale = 1.0
        if self.clip_global_norm is not None:
            sq = 0.0
            for items in buckets.values():
                nfn = self._fused_norm_jitted(len(items))
                sq += float(nfn([it[2]._data for it in items],
                                self.rescale_grad))
                _telemetry.record_update_dispatch("fused_norm")
            gnorm = sq ** 0.5
            if gnorm > self.clip_global_norm:
                scale = self.clip_global_norm / gnorm
        donate_env = _donate_enabled()
        hvec = self._packed_hyper(sorted(self._hyper()), scale)
        for (dtype_s, use_mp), items in buckets.items():
            ws = [it[1]._data for it in items]
            gs = [it[2]._data for it in items]
            sts = [jax.tree_util.tree_map(
                _unwrap, it[3], is_leaf=lambda x: isinstance(x, NDArray))
                for it in items]
            sel = [it[4] for it in items]
            donate = donate_env and _donation_safe((ws, sts), (gs,))
            fn = self._fused_jitted(len(items), use_mp, donate)
            before = _cache_size(fn)
            with _spans.span("fused_update", cat="optimizer"), \
                    _watchdog.guard("fused_update"):
                new_ws, new_sts = fn(ws, sts, gs, lrs[sel], wds[sel],
                                     ts[sel], hvec)
            _telemetry.record_update_dispatch(
                "fused", _donated_bytes(ws, sts) if donate else 0)
            _telemetry.record_fused_bucket("update", len(items))
            after = _cache_size(fn)
            if after is not None and after != before:
                variant = (f"{type(self).__name__.lower()}-n{len(items)}"
                           f"-{dtype_s}-mp{int(use_mp)}")
                _telemetry.record_trace("fused_update", variant)
                from ..diagnostics import introspect as _introspect

                _introspect.capture_compile(
                    "fused_update", variant, fn,
                    (*_specs((ws, sts, gs)), lrs[sel], wds[sel], ts[sel],
                     hvec))
            for it, nw, ns in zip(items, new_ws, new_sts):
                w, s = it[1], it[3]
                w._data = nw
                w._version += 1
                _write_state(s, ns)

    def update_multi_precision(self, index, weight, grad, state):
        if isinstance(index, (list, tuple)):
            self._update_list(index, weight, grad, state,
                              multi_precision=True)
            return
        use_mp = (
            isinstance(state, tuple)
            and len(state) == 2
            and isinstance(state[0], NDArray)
            and state[0].dtype == _np.float32
            and weight.dtype != _np.float32
        )
        if not use_mp:
            self.update(index, weight, grad, state)
            return
        master, inner = state
        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(grad, RowSparseNDArray):
            grad32 = RowSparseNDArray(grad.data.astype(jnp.float32),
                                      grad.indices, grad.shape)
        else:
            grad32 = _wrap_out(grad._data.astype(jnp.float32))
        self.update(index, master, grad32, inner)
        weight._data = master._data.astype(weight._data.dtype)
        weight._version += 1

    def __repr__(self):
        return f"{type(self).__name__}(learning_rate={self.learning_rate})"


def _write_state(state, new_state):
    """Write new raw state arrays back into NDArray state containers."""
    if state is None:
        return
    if isinstance(state, NDArray):
        state._data = new_state
        state._version += 1
        return
    for s, ns in zip(state, new_state):
        _write_state(s, ns)


def _weak32(x):
    """A traced Python float (weak float64 under the package's x64
    contract) as a weak float32: scalar-only hyperparameter math in a rule
    (``1 - beta``, ``lr * a / b``) then stays 32-bit instead of compiling
    to emulated f64 on a TPU, and the scalar still takes the dtype of
    whatever tensor it scales.  Anything else passes through."""
    if (isinstance(x, jax.Array) and x.dtype == jnp.float64
            and getattr(x, "weak_type", False)):
        from jax._src.lax.lax import _convert_element_type

        return _convert_element_type(x, _np.dtype("float32"),
                                     weak_type=True)
    return x


def _weak_elems(vec):
    """Every element of a packed operand vector (lr / wd float32[n],
    update counts int32[n], `_packed_hyper`) as a WEAK scalar of the
    vector's dtype, read by static index inside the program.

    An element of a float32 vector is strongly typed: ``lr * g`` on a bf16
    gradient would promote to float32, where the Python scalar it replaces
    took the gradient's dtype.  Weak typing keeps that promotion, and the
    host's float64 -> float32 rounding (NumPy, to nearest even) is the one
    `_weak32` did on the device, so the update is bitwise what the scalar
    operands gave."""
    from jax._src.lax.lax import _convert_element_type

    return [_convert_element_type(
        jax.lax.index_in_dim(vec, i, keepdims=False), vec.dtype,
        weak_type=True) for i in range(vec.shape[0])]


def _unpack_hyper(keys, hvec):
    """Inverse of `Optimizer._packed_hyper` inside a program: the rule's
    hyper dict (with rescale_grad) and the global-norm scale."""
    *vals, rescale, scale = _weak_elems(hvec)
    hyper = dict(zip(keys, vals))
    hyper["rescale_grad"] = rescale
    return hyper, scale


def _one_minus_pow(beta, t):
    """``1 - beta ** t``, the Adam-family bias correction, as a WEAK
    float32 scalar.

    `beta` and `t` reach a rule as traced Python scalars, which the
    package's x64 contract makes float64 / int64; ``beta ** t`` on them is
    64-bit transcendental math, which a TPU has to emulate — per
    parameter, it made BERT-base's whole step take over 20 minutes to
    compile.  Float32 ``-expm1(x)``, ``x = t * log(beta)``, is the same
    number to ~1e-5 relative — float32's rounding of ``beta`` itself, at
    beta = 0.999; the naive float32 ``1 - beta ** t`` adds its own
    cancellation (~1e-4 at small t) on top; it is spelled
    ``-tanh(x / 2) * (exp(x) + 1)``, the same function, and stays so:
    another spelling rounds differently, and every stored step and
    parity test holds these bits.  Weak typing lets the result scale a bf16, f32 or f64 tensor without promoting it, exactly
    like the Python scalar it replaces."""
    from jax._src.lax.lax import _convert_element_type

    x = jnp.asarray(t, jnp.float32) * jnp.log(jnp.asarray(beta, jnp.float32))
    out = -jnp.tanh(0.5 * x) * (jnp.exp(x) + 1.0)
    return _convert_element_type(out, _np.dtype("float32"), weak_type=True)


def _zeros_like(weight, dtype=None):
    return _wrap_out(jnp.zeros_like(weight._data, dtype=dtype))


def place_state_like(state, weight, plan=None, name=None):
    """Give optimizer state its weight's device placement — or, under a
    ZeRO plan, the sharded-bucket layout.

    State leaves (momentum, variance, fp32 master copies) mirror the
    weight's shape, so under a ShardingPlan they take the weight's
    NamedSharding verbatim — each shard's update then reads/writes only
    local state. With ``plan``/``name`` given and the plan's ZeRO axis
    live (MXTPU_ZERO + an fsdp mesh axis), same-shape leaves instead
    take ``plan.state_spec_for(name, shape)`` — the param spec extended
    along fsdp, so each rank holds 1/N of optimizer memory and the
    whole-step program's in-trace pins find state already in place.
    Leaves whose shape differs (scalar counters) and unplaced weights
    (no sharding attribute, or single-device default) are left alone;
    the trainer calls this right after state creation, so there is
    never live donated aliasing to worry about."""
    sharding = getattr(getattr(weight, "_data", None), "sharding", None)
    if plan is not None and name is not None and \
            weight.shape is not None and plan.zero_axis() is not None:
        from jax.sharding import NamedSharding

        sharding = NamedSharding(
            plan.mesh, plan.state_spec_for(name, weight.shape))
    if sharding is None:
        return state

    def _place(s):
        if s is None:
            return
        if isinstance(s, NDArray):
            if s.shape == weight.shape:
                s._data = jax.device_put(s._data, sharding)
                s._version += 1
            return
        for leaf in s:
            _place(leaf)

    _place(state)
    return state


# ---------------------------------------------------------------------------
# algorithms
# ---------------------------------------------------------------------------


@register
class SGD(Optimizer):
    """SGD with momentum (reference: optimizer/sgd.py; op sgd_mom_update)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lazy_update=True,
                 **kwargs):
        super().__init__(learning_rate=learning_rate,
                         lazy_update=lazy_update, **kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _zeros_like(weight)

    def _hyper(self):
        return {"momentum": self.momentum}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        g = g + wd * w
        if state is None:
            return w - lr * g, None
        mom = hyper["momentum"] * state - lr * g
        return w + mom, mom


@register
class NAG(SGD):
    """Nesterov accelerated SGD (reference: optimizer/nag.py)."""

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        g = g + wd * w
        if state is None:
            return w - lr * g, None
        mom = hyper["momentum"] * state - lr * g
        return w + hyper["momentum"] * mom - lr * g, mom


@register
class Signum(Optimizer):
    """Sign-momentum SGD (reference: optimizer/sgd.py Signum)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return _zeros_like(weight)

    def _hyper(self):
        return {"momentum": self.momentum, "wd_lh": self.wd_lh}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        g = g + wd * w
        if state is None:
            return w - lr * jnp.sign(g), None
        mom = hyper["momentum"] * state - (1 - hyper["momentum"]) * g
        new_w = w + lr * jnp.sign(mom) - lr * hyper["wd_lh"] * w
        return new_w, mom


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics (reference: optimizer/sgld.py)."""

    def __init__(self, learning_rate=0.01, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)

    def update(self, index, weight, grad, state):
        from .. import _random
        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(grad, RowSparseNDArray):
            grad = grad.todense()   # Langevin noise hits every row anyway
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        g = grad._data * self.rescale_grad
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        g = g + wd * weight._data
        noise = jax.random.normal(_random.next_key(), weight.shape,
                                  jnp.float32) * jnp.sqrt(lr)
        weight._data = (weight._data - lr / 2 * g
                        + noise.astype(weight._data.dtype))
        weight._version += 1


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (reference: optimizer/dcasgd.py)."""

    def __init__(self, learning_rate=0.01, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = None if self.momentum == 0.0 else _zeros_like(weight)
        return (mom, _wrap_out(jnp.copy(weight._data)))

    def _hyper(self):
        return {"momentum": self.momentum, "lamda": self.lamda}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        mom, prev_w = state
        comp = g + wd * w + hyper["lamda"] * g * g * (w - prev_w)
        if mom is None:
            new_mom = None
            upd = -lr * comp
        else:
            new_mom = hyper["momentum"] * mom - lr * comp
            upd = new_mom
        return w + upd, (new_mom, w + upd)


@register
class Adam(Optimizer):
    """Adam (reference: optimizer/adam.py; op adam_update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def _hyper(self):
        return {"beta1": self.beta1, "beta2": self.beta2, "eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        g = g + wd * w
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        lr_t = lr * jnp.sqrt(_one_minus_pow(b2, t)) / _one_minus_pow(b1, t)
        return w - lr_t * m / (jnp.sqrt(v) + hyper["eps"]), (m, v)


@register
class AdamW(Adam):
    """Decoupled weight decay Adam (reference: contrib adamw.py)."""

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / _one_minus_pow(b1, t)
        vhat = v / _one_minus_pow(b2, t)
        return w - lr * (mhat / (jnp.sqrt(vhat) + hyper["eps"]) + wd * w), (m, v)


@register
class Nadam(Adam):
    """Nesterov Adam (reference: optimizer/nadam.py)."""

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        g = g + wd * w
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / (1 - b1 ** (t + 1))
        vhat = v / _one_minus_pow(b2, t)
        m_bar = b1 * mhat + (1 - b1) * g / _one_minus_pow(b1, t)
        return w - lr * m_bar / (jnp.sqrt(vhat) + hyper["eps"]), (m, v)


@register
class AdaBelief(Adam):
    """AdaBelief (reference: optimizer/adabelief.py)."""

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, s = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        g = g + wd * w
        m = b1 * m + (1 - b1) * g
        s = b2 * s + (1 - b2) * jnp.square(g - m) + hyper["eps"]
        lr_t = lr * jnp.sqrt(_one_minus_pow(b2, t)) / _one_minus_pow(b1, t)
        return w - lr_t * m / (jnp.sqrt(s) + hyper["eps"]), (m, s)


@register
class Adamax(Adam):
    """Adamax — Adam with the infinity norm (reference: optimizer/adamax.py)."""

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, u = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        g = g + wd * w
        m = b1 * m + (1 - b1) * g
        u = jnp.maximum(b2 * u, jnp.abs(g))
        return w - (lr / _one_minus_pow(b1, t)) * m / (u + hyper["eps"]), (m, u)


@register
class FTML(Optimizer):
    """Follow the Moving Leader (reference: optimizer/ftml.py)."""

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon

    def create_state(self, index, weight):
        # d_prev, v, z
        return (_zeros_like(weight), _zeros_like(weight),
                _zeros_like(weight))

    def _hyper(self):
        return {"beta1": self.beta1, "beta2": self.beta2, "eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        d_prev, v, z = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        g = g + wd * w
        v = b2 * v + (1 - b2) * g * g
        d = _one_minus_pow(b1, t) / lr * (
            jnp.sqrt(v / _one_minus_pow(b2, t)) + hyper["eps"])
        sigma = d - b1 * d_prev
        z = b1 * z + (1 - b1) * g - sigma * w
        return -z / d, (d, v, z)


@register
class RMSProp(Optimizer):
    """RMSProp, optionally centered (reference: optimizer/rmsprop.py)."""

    def __init__(self, learning_rate=0.001, rho=0.9, momentum=0.9,
                 epsilon=1e-8, centered=False, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.rho, self.momentum, self.epsilon = rho, momentum, epsilon
        self.centered = centered

    def create_state(self, index, weight):
        if self.centered:
            return (_zeros_like(weight), _zeros_like(weight),
                    _zeros_like(weight))
        return (_zeros_like(weight),)

    def _hyper(self):
        return {"rho": self.rho, "momentum": self.momentum,
                "eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        rho, eps = hyper["rho"], hyper["eps"]
        g = g + wd * w
        if len(state) == 1:
            (n,) = state
            n = rho * n + (1 - rho) * g * g
            return w - lr * g / (jnp.sqrt(n) + eps), (n,)
        n, mg, delta = state
        n = rho * n + (1 - rho) * g * g
        mg = rho * mg + (1 - rho) * g
        delta = hyper["momentum"] * delta - lr * g / (
            jnp.sqrt(n - mg * mg + eps))
        return w + delta, (n, mg, delta)


@register
class AdaGrad(Optimizer):
    """AdaGrad (reference: optimizer/adagrad.py)."""

    def __init__(self, learning_rate=0.01, epsilon=1e-7, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return _zeros_like(weight)

    def _hyper(self):
        return {"eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        g = g + wd * w
        hist = state + g * g
        return w - lr * g / (jnp.sqrt(hist) + hyper["eps"]), hist


@register
class AdaDelta(Optimizer):
    """AdaDelta (reference: optimizer/adadelta.py)."""

    def __init__(self, learning_rate=1.0, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.rho, self.epsilon = rho, epsilon

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def _hyper(self):
        return {"rho": self.rho, "eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        acc_g, acc_d = state
        rho, eps = hyper["rho"], hyper["eps"]
        g = g + wd * w
        acc_g = rho * acc_g + (1 - rho) * g * g
        delta = jnp.sqrt(acc_d + eps) / jnp.sqrt(acc_g + eps) * g
        acc_d = rho * acc_d + (1 - rho) * delta * delta
        return w - lr * delta, (acc_g, acc_d)


@register
class Ftrl(Optimizer):
    """FTRL-proximal (reference: optimizer/ftrl.py)."""

    def __init__(self, learning_rate=0.1, lamda1=0.01, beta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1, self.beta = lamda1, beta

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))  # z, n

    def _hyper(self):
        return {"lamda1": self.lamda1, "beta": self.beta}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        z, n = state
        l1, beta = hyper["lamda1"], hyper["beta"]
        sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr
        z = z + g - sigma * w
        n = n + g * g
        new_w = jnp.where(
            jnp.abs(z) > l1,
            -(z - jnp.sign(z) * l1) / ((beta + jnp.sqrt(n)) / lr + wd),
            jnp.zeros_like(w),
        )
        return new_w, (z, n)


@register
class LAMB(Optimizer):
    _row_local = False  # layer-wise trust ratio needs the full tensor
    """Layer-wise adaptive moments for batch training (reference: lamb.py)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-6, lower_bound=None, upper_bound=None,
                 bias_correction=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.lower_bound, self.upper_bound = lower_bound, upper_bound
        self.bias_correction = bias_correction

    def create_state(self, index, weight):
        return (_zeros_like(weight), _zeros_like(weight))

    def _hyper(self):
        return {"beta1": self.beta1, "beta2": self.beta2, "eps": self.epsilon,
                "lower": self.lower_bound or 0.0,
                "upper": self.upper_bound or -1.0,
                "bias_corr": 1.0 if self.bias_correction else 0.0}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        bc = hyper["bias_corr"]
        mhat = jnp.where(bc > 0, m / _one_minus_pow(b1, t), m)
        vhat = jnp.where(bc > 0, v / _one_minus_pow(b2, t), v)
        r = mhat / (jnp.sqrt(vhat) + hyper["eps"]) + wd * w
        w_norm = jnp.linalg.norm(w)
        r_norm = jnp.linalg.norm(r)
        ratio = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        ratio = jnp.maximum(ratio, hyper["lower"])
        ratio = jnp.where(hyper["upper"] > 0,
                          jnp.minimum(ratio, jnp.abs(hyper["upper"])), ratio)
        return w - lr * ratio * r, (m, v)


@register
class LANS(LAMB):
    """LAMB with Nesterov momentum and per-part gradient normalization
    (reference: optimizer/lans.py)."""

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        m, v = state
        b1, b2, t = hyper["beta1"], hyper["beta2"], hyper["t"]
        g = g / jnp.maximum(jnp.linalg.norm(g), 1e-12)  # normalized grad
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat = m / _one_minus_pow(b1, t)
        vhat = v / _one_minus_pow(b2, t)
        denom = jnp.sqrt(vhat) + hyper["eps"]
        r1 = mhat / denom + wd * w            # momentum part
        r2 = g / denom + wd * w               # gradient (Nesterov) part
        w_norm = jnp.linalg.norm(w)

        def trust(r):
            rn = jnp.linalg.norm(r)
            ratio = jnp.where((w_norm > 0) & (rn > 0), w_norm / rn, 1.0)
            ratio = jnp.maximum(ratio, hyper["lower"])
            return jnp.where(hyper["upper"] > 0,
                             jnp.minimum(ratio, jnp.abs(hyper["upper"])),
                             ratio)

        upd = b1 * trust(r1) * r1 + (1 - b1) * trust(r2) * r2
        return w - lr * upd, (m, v)


@register
class LARS(Optimizer):
    _row_local = False  # layer-wise norms need the full tensor
    """Layer-wise adaptive rate scaling (reference: optimizer/lars.py)."""

    def __init__(self, learning_rate=0.1, momentum=0.9, eta=0.001,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum, self.eta, self.epsilon = momentum, eta, epsilon

    def create_state(self, index, weight):
        return _zeros_like(weight)

    def _hyper(self):
        return {"momentum": self.momentum, "eta": self.eta,
                "eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):
        w_norm = jnp.linalg.norm(w)
        g_norm = jnp.linalg.norm(g)
        trust = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            hyper["eta"] * w_norm / (g_norm + wd * w_norm + hyper["eps"]),
            1.0,
        )
        g = g + wd * w
        mom = hyper["momentum"] * state + lr * trust * g
        return w - mom, mom


# registered lowercase aliases for reference parity
_REG.register(SGD, "sgd")
_REG.register(NAG, "nag")
_REG.register(Adam, "adam")
_REG.register(AdamW, "adamw")
_REG.register(Nadam, "nadam")
_REG.register(RMSProp, "rmsprop")
_REG.register(AdaGrad, "adagrad")
_REG.register(AdaDelta, "adadelta")
_REG.register(Ftrl, "ftrl")
_REG.register(LAMB, "lamb")
_REG.register(LARS, "lars")
_REG.register(Signum, "signum")
_REG.register(SGLD, "sgld")
_REG.register(DCASGD, "dcasgd")
_REG.register(AdaBelief, "adabelief")
_REG.register(Adamax, "adamax")
_REG.register(FTML, "ftml")
_REG.register(LANS, "lans")


@register
class GroupAdaGrad(Optimizer):
    """AdaGrad with ONE accumulator per row (reference:
    optimizer/contrib.py:26 GroupAdaGrad): history += mean(g², axis=1,
    keepdims); w -= lr * g / (sqrt(history) + eps). Weight decay is not
    supported, matching the reference."""

    def __init__(self, learning_rate=0.01, epsilon=1e-6, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        if self.wd != 0.0:
            raise ValueError(
                "GroupAdaGrad does not support weight decay (reference "
                "contrib.py:46)")
        self.epsilon = epsilon

    def create_state(self, index, weight):
        if len(weight.shape) < 2:
            raise ValueError(
                "GroupAdaGrad needs >= 2-d weights (row-wise history)")
        return _wrap_out(jnp.zeros(
            (weight.shape[0], 1), weight._data.dtype))

    def _hyper(self):
        return {"eps": self.epsilon}

    @staticmethod
    def _rule(w, g, state, lr, wd, hyper):  # noqa: ARG004 - wd unused
        axes = tuple(range(1, g.ndim))
        hist = state + jnp.mean(g * g, axis=axes, keepdims=True)
        return w - lr * g / (jnp.sqrt(hist) + hyper["eps"]), hist


class Updater:
    """kvstore-side updater (reference: optimizer/updater.py:31): the
    callable a server registers via kv.set_optimizer — keeps one
    optimizer state per key and applies update(key, grad, weight)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        # key -> flat leaf list from load_optimizer_states, grafted into
        # the freshly created state on the key's first update (the nested
        # structure is only known once create_state runs against a weight)
        self.pending_loaded = {}

    def __call__(self, index, grad, weight):
        if not isinstance(index, (list, tuple)):
            index, grad, weight = [index], [grad], [weight]
        index = [i.decode() if isinstance(i, bytes) else i for i in index]
        # the states this call is the first to need, in one program
        new = {i: w for i, w in zip(index, weight) if i not in self.states}
        fresh = dict(zip(new, self.optimizer.create_states_multi_precision(
            list(new), list(new.values())))) if new else {}
        for i, g, w in zip(index, grad, weight):
            if i not in self.states:
                st = fresh[i]
                flat = self.pending_loaded.pop(i, None)
                if flat is None:
                    flat = self.pending_loaded.pop(str(i), None)
                if flat is not None:
                    st = _graft_state(st, list(flat))
                self.states[i] = st
            self.optimizer.update_multi_precision(i, w, g, self.states[i])

    def set_states(self, states):
        import pickle

        payload = pickle.loads(states)
        if isinstance(payload, dict) and "optimizer" in payload:
            self.optimizer = payload["optimizer"]
            payload = payload["states"]
        self.states = payload

    def get_states(self, dump_optimizer=False):
        import pickle

        if dump_optimizer:
            return pickle.dumps({"states": self.states,
                                 "optimizer": self.optimizer})
        return pickle.dumps(self.states)


def _graft_state(state, flat):
    """Rebuild a freshly created optimizer state with loaded leaf values
    (in flatten order), preserving the state's nested structure and leaf
    dtypes. Leaf-count mismatch (checkpoint from a different optimizer)
    fails fast with a diagnosable error."""
    from ..ndarray.ndarray import NDArray

    def count(s):
        if s is None:
            return 0
        if isinstance(s, NDArray):
            return 1
        if isinstance(s, (list, tuple)):
            return sum(count(x) for x in s)
        return 0

    expected = count(state)
    if expected != len(flat):
        raise ValueError(
            f"optimizer state checkpoint has {len(flat)} leaves but the "
            f"current optimizer's state wants {expected} — was it saved "
            f"under a different optimizer? (load_optimizer_states)")

    def walk(s):
        if s is None:
            return None
        if isinstance(s, NDArray):
            import jax.numpy as jnp

            leaf = flat.pop(0)
            val = leaf._data if isinstance(leaf, NDArray) else \
                jnp.asarray(leaf)
            return NDArray(val.astype(s.dtype))
        if isinstance(s, (list, tuple)):
            return type(s)(walk(x) for x in s)
        return s

    return walk(state)


def get_updater(optimizer):
    """Reference optimizer/updater.py:get_updater."""
    return Updater(optimizer)
