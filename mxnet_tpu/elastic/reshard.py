"""Mesh-migrating checkpoint restore (docs/elasticity.md).

Checkpoints are placement-free by construction: ``snapshot.capture``
host-gathers every shard (``asnumpy()`` on a NamedSharding array reads
the full logical value), so the payload of a dp=4 run and an
fsdp=2·tp=2 run of the same model is byte-identical. Resharding is
therefore not an array-rewrite problem — it is a *contract* problem:

  * :func:`plan_compatibility` judges a saved plan manifest against a
    target plan: ``exact`` (same resolved axes), ``replace`` (same
    world size, different placement — restore re-places silently, the
    PR-12 contract) or ``reshard`` (different world size — a topology
    migration that :class:`PlanMismatch` gates behind
    ``allow_reshard=True``);
  * :func:`resharded_restore` is the opt-in front door: it calls
    ``CheckpointManager.restore(..., allow_reshard=True)`` and returns
    the compatibility report alongside the RestoreResult;
  * :func:`reshard_checkpoint` rewrites a committed checkpoint OFFLINE
    for a target mesh: same arrays, the manifest's recorded plan
    replaced by the target plan and the payload re-split across the
    target world's shard files — the output restores onto the new
    topology as an ``exact`` match, with the full tmp+fsync+rename
    commit protocol so a crash mid-rewrite never leaves a half
    checkpoint;
  * :func:`verify_parity` proves a restore bitwise against the
    checkpoint's own host-gathered truth (params AND optimizer state),
    the acceptance oracle tests/test_elastic.py runs on the
    8-virtual-device CPU mesh.

ZeRO re-extension needs no special code here: ``snapshot.apply``
re-places restored optimizer state via ``place_state_like`` under the
RESTORING plan's ``state_spec_for``, so state saved 1/4-per-rank under
fsdp=4 lands 1/2-per-rank under fsdp=2 (or replicated) from the same
logical arrays.
"""
from __future__ import annotations

import json
import os
import shutil
import time

import numpy as np

from ..checkpoint.errors import CheckpointError, PlanMismatch

__all__ = ["PlanMismatch", "plan_compatibility", "plan_world_size",
           "resharded_restore", "reshard_checkpoint", "verify_parity"]


def _as_manifest(plan):
    """A plan argument -> manifest dict (or None): accepts a manifest
    dict already, a ShardingPlan, or an axes spelling."""
    if plan is None or isinstance(plan, dict):
        return plan
    from ..sharding.plan import ShardingPlan

    if not isinstance(plan, ShardingPlan):
        plan = ShardingPlan(plan)
    return plan.to_manifest()


def plan_world_size(plan_manifest):
    """Device count a plan manifest's mesh spans (the product of its
    axis sizes); 1 for None (unsharded = one logical device view).
    -1 (uninferred) sizes resolve against this host's device count."""
    if plan_manifest is None:
        return 1
    total, infer = 1, 0
    for _name, size in plan_manifest.get("axes") or ():
        if int(size) == -1:
            infer += 1
        else:
            total *= int(size)
    if infer:
        import jax

        n = len(jax.devices())
        total = n if total == 0 else max(n // total, 1) ** infer * total
    return total


def plan_compatibility(saved, target):
    """Judge a saved plan against a target plan. Both may be manifests,
    ShardingPlans, axes spellings, or None. Returns a JSON-able report:

      verdict   'exact'    same resolved axes (a plain resume),
                'replace'  same world size, different placement —
                           restore() re-places silently,
                'reshard'  different world size — restore() raises
                           PlanMismatch unless allow_reshard=True;
      saved_world / target_world / saved_axes / target_axes / notes.
    """
    saved = _as_manifest(saved)
    target = _as_manifest(target)
    sw, tw = plan_world_size(saved), plan_world_size(target)
    s_axes = [list(a) for a in (saved or {}).get("axes") or []]
    t_axes = [list(a) for a in (target or {}).get("axes") or []]
    notes = []
    if s_axes == t_axes:
        verdict = "exact"
    elif sw == tw:
        verdict = "replace"
        notes.append("same world size: restore() re-places arrays "
                     "under the target plan silently")
    else:
        verdict = "reshard"
        notes.append(
            f"world size changes {sw} -> {tw}: restore() raises "
            f"PlanMismatch unless allow_reshard=True "
            f"(elastic.resharded_restore / tools/ckpt.py reshard)")
    if (saved or {}).get("zero_axis") != (target or {}).get("zero_axis"):
        notes.append(
            f"ZeRO axis changes "
            f"{(saved or {}).get('zero_axis')!r} -> "
            f"{(target or {}).get('zero_axis')!r}: optimizer state "
            f"re-extends along the target fsdp axis on restore")
    return {"verdict": verdict, "compatible": verdict != "reshard",
            "saved_world": sw, "target_world": tw,
            "saved_axes": s_axes, "target_axes": t_axes, "notes": notes}


def resharded_restore(manager, step=None, trainer=None):
    """Restore a checkpoint onto a trainer whose plan differs from the
    saved one — the explicit opt-in for world-size migrations.

    Thin, auditable front door over ``manager.restore(...,
    allow_reshard=True)``: the manager itself times the re-placement
    (``reshard_ms``) and stamps the flight recorder. Returns
    ``(RestoreResult, compatibility report)``.
    """
    result = manager.restore(step=step, trainer=trainer,
                             allow_reshard=True)
    tr = trainer or manager._trainer
    saved = (result.manifest.get("meta") or {}).get("sharding_plan")
    target = getattr(tr, "sharding_plan", None)
    return result, plan_compatibility(saved, target)


def reshard_checkpoint(src, dst, target_plan=None, *, step=None,
                       target_world=1, mode="replicated", verify=True):
    """Rewrite a committed checkpoint for a target mesh, offline.

    Reads the checkpoint at ``src`` (latest committed step unless
    ``step``), then writes a NEW committed checkpoint under ``dst``
    whose manifest records ``target_plan`` (a ShardingPlan, axes
    spelling, manifest dict, or None for replicated) as the run's plan
    and whose payload is split across ``target_world`` shard files in
    ``mode`` ('replicated': one arrays.npz; 'sharded': round-robin
    shard-NNNNN.npz, the exact split a ``target_world``-rank sharded
    save would produce). Arrays are copied verbatim — the logical state
    is placement-free — so the output restores onto the target topology
    as an ``exact`` plan match. The write runs the same
    tmp+fsync+rename commit protocol as a live save. Returns a report
    dict ({'step', 'dst', 'arrays', 'nbytes', 'compatibility'}).
    """
    from ..checkpoint import manager as _mgr
    from ..telemetry import instruments as _telemetry

    t0 = time.perf_counter()
    src = os.path.abspath(str(src))
    dst = os.path.abspath(str(dst))
    steps = []
    for n in os.listdir(src):
        s = _mgr._step_of(n)
        if s is not None and os.path.isfile(
                os.path.join(src, n, _mgr.MANIFEST_NAME)):
            steps.append(s)
    if step is None:
        if not steps:
            from ..checkpoint.errors import CheckpointNotFound

            raise CheckpointNotFound(f"no committed checkpoint in {src}")
        step = max(steps)
    step = int(step)
    d = os.path.join(src, _mgr._STEP_FMT.format(step))
    arrays, manifest = _mgr._read_checkpoint(d, verify=verify)

    target = _as_manifest(target_plan)
    compat = plan_compatibility(
        (manifest.get("meta") or {}).get("sharding_plan"), target)
    target_world = int(target_world)
    mode = str(mode).lower()
    if mode not in ("replicated", "sharded"):
        raise CheckpointError(
            f"mode must be 'replicated' or 'sharded', got {mode!r}")
    names = sorted(arrays)
    if mode == "sharded" and target_world > 1:
        files = {n: f"shard-{i % target_world:05d}.npz"
                 for i, n in enumerate(names)}
    else:
        files = {n: "arrays.npz" for n in names}

    out = dict(manifest)
    out["world_size"] = target_world
    out["mode"] = mode
    out["reason"] = "reshard"
    out["time"] = time.time()
    out["meta"] = dict(manifest.get("meta") or {})
    out["meta"]["sharding_plan"] = target
    out["arrays"] = {
        n: {"file": files[n], "shape": list(arrays[n].shape),
            "dtype": str(arrays[n].dtype), "crc32": _mgr._crc(arrays[n]),
            "nbytes": int(arrays[n].nbytes)}
        for n in names}

    from .._dtype_codec import encode_payload

    os.makedirs(dst, exist_ok=True)
    final = os.path.join(dst, _mgr._STEP_FMT.format(step))
    tmp = os.path.join(dst, _mgr._TMP_FMT.format(step))
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    for fname in sorted(set(files.values())):
        payload = encode_payload(
            {n: np.asarray(arrays[n]) for n in names
             if files[n] == fname})
        with open(os.path.join(tmp, fname), "wb") as f:
            np.savez(f, **payload)
            f.flush()
            os.fsync(f.fileno())
    _mgr._write_json(os.path.join(tmp, _mgr.MANIFEST_NAME), out)
    _mgr._fsync_dir(tmp)
    if os.path.isdir(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    _mgr._fsync_dir(dst)
    nbytes = sum(e["nbytes"] for e in out["arrays"].values())
    _telemetry.record_reshard(
        (time.perf_counter() - t0) * 1e3,
        saved_world=compat["saved_world"],
        target_world=compat["target_world"], site="offline")
    return {"step": step, "dst": final, "arrays": len(names),
            "nbytes": nbytes, "compatibility": compat}


def verify_parity(trainer, arrays, atol=0.0):
    """Bitwise-compare a trainer's live params + optimizer state against
    a checkpoint's host-gathered arrays (the ``param/{i}`` / ``opt/...``
    namespace ``snapshot.capture`` writes). Returns the number of arrays
    compared; raises CheckpointError naming the first divergent one.
    ``atol=0.0`` (default) is exact — the fp32 acceptance bar."""
    import jax

    def _cmp(name, live):
        want = np.asarray(arrays[name])
        got = np.asarray(live)
        if got.shape != want.shape or got.dtype != want.dtype:
            raise CheckpointError(
                f"parity: {name} is {got.dtype}{got.shape}, checkpoint "
                f"holds {want.dtype}{want.shape}")
        if atol == 0.0:
            ok = np.array_equal(got, want)
        else:
            ok = np.allclose(got, want, atol=atol, rtol=0.0)
        if not ok:
            delta = float(np.max(np.abs(
                got.astype("float64") - want.astype("float64"))))
            raise CheckpointError(
                f"parity: {name} diverges (max |delta| = {delta:g})")

    compared = 0
    for i, p in enumerate(trainer._params):
        _cmp(f"param/{i}", p.data().asnumpy())
        compared += 1
    for i, st in enumerate(trainer._states):
        if st is None:
            continue
        leaves = jax.tree_util.tree_leaves(
            st, is_leaf=lambda x: hasattr(x, "asnumpy"))
        spec_keys = sorted(k for k in arrays if k == f"opt/{i}"
                           or k.startswith(f"opt/{i}."))
        if len(leaves) != len(spec_keys):
            raise CheckpointError(
                f"parity: param {i} has {len(leaves)} state leaves, "
                f"checkpoint holds {len(spec_keys)}")
        for key, leaf in zip(spec_keys, leaves):
            _cmp(key, leaf.asnumpy())
            compared += 1
    return compared
