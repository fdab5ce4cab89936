"""Capture / apply complete training state as flat numpy arrays + meta.

The snapshot is the serialization-agnostic middle layer: `capture` walks
a Trainer and returns (arrays, meta) where `arrays` is a flat
name->numpy dict (npz-ready, dtype-codec friendly) and `meta` is a
JSON-able dict that records how to reassemble it. `apply` is the exact
inverse. manager.py owns files, atomicity, and retention; this module
owns *what* training state means:

  * Block parameters (primary device copy; `set_data` re-fans-out to
    every device copy on restore, honoring each param's declared dtype),
  * optimizer per-param state trees — legacy and fused paths share
    `Trainer._states` (possibly (master_fp32, inner) multi-precision
    tuples), flattened leaf-by-leaf with a structure spec in meta,
  * optimizer bookkeeping (`num_update`, per-param update counts `t`
    that drive Adam bias correction and LR schedules — dropping these
    would silently restart schedules, breaking bitwise resume),
  * stale-grad tracking: `Trainer._grad_versions` stores process-local
    buffer versions, meaningless in a new process; we persist *which*
    param indices were stale and re-mark them against the restored
    process's grad versions on apply,
  * the global RNG key and loss-scale, and an opaque user-state blob
    (dataloader cursor etc.) that rides along in meta.
"""
from __future__ import annotations

import numpy as np

from .errors import CheckpointError

__all__ = ["capture", "apply"]

# bump when the (arrays, meta) layout changes incompatibly
SNAPSHOT_VERSION = 1


def _state_spec(state, prefix, out):
    """Flatten one optimizer-state tree: leaves (NDArray) land in `out`
    under generated keys; returns a JSON-able spec mirroring the
    structure — None | "key-string" | [child specs]."""
    from ..ndarray.ndarray import NDArray

    if state is None:
        return None
    if isinstance(state, NDArray):
        out[prefix] = state.asnumpy()
        return prefix
    if isinstance(state, (tuple, list)):
        return [_state_spec(s, f"{prefix}.{j}", out)
                for j, s in enumerate(state)]
    raise CheckpointError(
        f"unserializable optimizer state at {prefix}: {type(state)}")


def _state_from_spec(spec, arrays):
    import jax.numpy as jnp

    from ..ndarray.ndarray import NDArray

    if spec is None:
        return None
    if isinstance(spec, str):
        if spec not in arrays:
            raise CheckpointError(f"missing optimizer state array {spec!r}")
        return NDArray(jnp.asarray(arrays[spec]))
    return tuple(_state_from_spec(s, arrays) for s in spec)


def _stale_indices(trainer):
    """Param indices whose grad buffer is STALE (untouched since their
    last update) — Trainer.update's `_grad_versions.get(i) == g._version`
    test, persisted as indices since raw versions don't survive a
    process boundary."""
    stale = []
    for i, p in enumerate(trainer._params):
        if p.grad_req == "null" or p._data_map is None:
            continue
        grads = p.list_grad()
        if grads and trainer._grad_versions.get(i) == grads[0]._version:
            stale.append(i)
    return stale


def capture(trainer, user_state=None):
    """Snapshot `trainer`'s complete training state.

    Returns (arrays, meta). Arrays are host numpy copies taken NOW —
    after this returns, training may mutate params freely while the
    manager writes the copies out asynchronously.
    """
    arrays = {}
    param_names, param_dtypes, param_shapes = [], [], []
    for i, p in enumerate(trainer._params):
        p._check_initialized()
        arrays[f"param/{i}"] = p.data().asnumpy()
        param_names.append(p.name)
        param_dtypes.append(str(np.dtype(p.dtype)) if p.dtype else None)
        param_shapes.append(list(arrays[f"param/{i}"].shape))
    state_specs = [_state_spec(s, f"opt/{i}", arrays)
                   for i, s in enumerate(trainer._states)]
    meta = {
        "snapshot_version": SNAPSHOT_VERSION,
        "num_params": len(trainer._params),
        "param_names": param_names,
        "param_dtypes": param_dtypes,
        "param_shapes": param_shapes,
        "state_specs": state_specs,
        "states_created": list(trainer._states_created),
        "optimizer": trainer._optimizer.bookkeeping_state(),
        "stale_indices": _stale_indices(trainer),
        # the ShardingPlan this run trained under (docs/sharding.md):
        # arrays above are host numpy — asnumpy() gathers every shard —
        # so the payload itself is placement-free; the record is for
        # provenance (verify_checkpoint) and tooling.  apply() re-places
        # onto the RESTORING trainer's plan, so replicated↔dp↔dp×tp
        # moves are just save + restore.
        "sharding_plan": (trainer.sharding_plan.to_manifest()
                          if getattr(trainer, "sharding_plan", None)
                          is not None else None),
        "scale": trainer._scale,
        "user_state": user_state,
    }
    from .. import _random

    if _random._rng.key is not None:
        arrays["rng/key"] = np.asarray(_random._rng.key)
        meta["rng_key_dtype"] = str(np.asarray(_random._rng.key).dtype)
    return arrays, meta


def apply(trainer, arrays, meta):
    """Load a snapshot into `trainer` (inverse of `capture`).

    Validates param count / name / dtype against the payload and raises
    CheckpointError on mismatch BEFORE touching any state, so a failed
    restore never leaves the trainer half-loaded.
    """
    import jax.numpy as jnp

    n = meta.get("num_params")
    if n != len(trainer._params):
        raise CheckpointError(
            f"checkpoint holds {n} params but trainer has "
            f"{len(trainer._params)} — wrong model or wrong checkpoint")
    names = meta.get("param_names") or []
    dtypes = meta.get("param_dtypes") or []
    for i, p in enumerate(trainer._params):
        if i < len(names) and names[i] != p.name:
            raise CheckpointError(
                f"param {i} name mismatch: checkpoint has {names[i]!r}, "
                f"trainer has {p.name!r}")
        want = dtypes[i] if i < len(dtypes) else None
        have = str(np.dtype(p.dtype)) if p.dtype else None
        if want is not None and have is not None and want != have:
            raise CheckpointError(
                f"param {i} ({p.name}) dtype mismatch: checkpoint has "
                f"{want}, trainer declares {have}")
        if f"param/{i}" not in arrays:
            raise CheckpointError(f"missing array param/{i} ({p.name})")

    for i, p in enumerate(trainer._params):
        p.set_data(arrays[f"param/{i}"])  # fans out to every device copy
    specs = meta.get("state_specs") or [None] * len(trainer._params)
    trainer._states = [_state_from_spec(s, arrays) for s in specs]
    trainer._states_created = list(
        meta.get("states_created") or [s is not None for s in specs])
    opt_meta = meta.get("optimizer")
    if opt_meta:
        trainer._optimizer.load_bookkeeping_state(opt_meta)
    trainer._scale = float(meta.get("scale", 1.0))
    # re-mark stale grads against THIS process's buffer versions
    trainer._grad_versions = {}
    for i in meta.get("stale_indices") or []:
        p = trainer._params[i]
        if p.grad_req != "null" and p._data_map is not None:
            grads = p.list_grad()
            if grads:
                trainer._grad_versions[i] = grads[0]._version
    # re-place restored arrays onto the RESTORING trainer's plan (which
    # may differ from the save-time plan recorded in meta): set_data /
    # the state rebuild above landed everything at default placement,
    # so a dp=4 checkpoint loads into a replicated run — and vice versa
    # — by re-running plan application here
    plan = getattr(trainer, "_sharding_plan", None)
    if plan is not None:
        trainer._plan_applied = False
        trainer._maybe_apply_plan()
        if trainer._plan_applied:
            from ..optimizer.optimizer import place_state_like

            for i, p in enumerate(trainer._params):
                if trainer._states_created[i]:
                    place_state_like(trainer._states[i], p.data(),
                                     plan=plan,
                                     name=trainer._param_names[i])
    if "rng/key" in arrays:
        from .. import _random

        key = jnp.asarray(arrays["rng/key"])
        want = meta.get("rng_key_dtype")
        if want:
            key = key.astype(want)
        _random._rng.key = key
