"""On-device program measurement harness (the profiler half of the
measurement plane; observability/costdb.py is the persistence half).

Every compiled program already passes through
``diagnostics.introspect.capture_compile`` — CachedOp variants, the
whole-step program, fused-optimizer updates. This module hooks that
seam: under ``MXTPU_MEASURE=on_compile`` each registration runs a
warmed, synchronized wall-clock microbenchmark of the jitted callable
on the live device and records ``{fingerprint, platform, wall_ms
p50/p95, peak_bytes if available, arg shapes/dtypes, analytic
predictions, telemetry snapshot}`` into the CostDB.
``MXTPU_MEASURE=cli`` instead stashes the callables for a deferred
:func:`sweep` (what ``tools/costdb.py measure`` drives), and the
default ``off`` returns before touching jax — default runs stay
bitwise-identical with zero extra jit traces and zero extra device
dispatches.

Mechanics worth knowing:

  * registration converts large array leaves (> ``SMALL_LEAF_BYTES``)
    to ``ShapeDtypeStruct`` so the pending cache never pins real
    weights; measurement materializes fresh zero buffers per timed run
    because donated programs (``donate_argnums``) invalidate their
    inputs — re-passing run 1's buffers would crash run 2;
  * the fingerprint is the PR-7 dedup ``structural_key`` (sha1-packed,
    address tokens scrubbed so it is stable across processes), falling
    back to a digest of the printed jaxpr when the program is
    unhashable;
  * the analytic predictions come from ``passes/memory.py``
    (``estimate_region_bytes`` / ``estimate_peak_bytes``) over a
    re-trace wrapped in ``suppress_trace_bumps`` so measurement never
    perturbs the zero-retrace telemetry proofs.
"""
from __future__ import annotations

import hashlib
import os
import re
import threading
import time

__all__ = [
    "mode", "enabled", "maybe_register", "pending", "sweep",
    "measure_callable", "fingerprint_of",
    "reset", "SMALL_LEAF_BYTES",
]

# args-cache leaves bigger than this become ShapeDtypeStructs at
# registration (don't pin weights); small leaves (PRNG keys, scalars)
# stay concrete so extended dtypes need no zero-materialization
SMALL_LEAF_BYTES = 4096

_MODES = {
    "off": "off", "": "off", "0": "off", "false": "off", "no": "off",
    "on_compile": "on_compile", "on-compile": "on_compile",
    "compile": "on_compile", "on": "on_compile", "1": "on_compile",
    "true": "on_compile",
    "cli": "cli", "defer": "cli", "deferred": "cli",
}

_tls = threading.local()
_lock = threading.Lock()
_pending = {}      # (block, variant) -> {"fn", "args", "kwargs"}


def _env_get(name, default):
    try:
        from .. import env as _env

        if name in _env.all_vars():
            return _env.get(name)
    except Exception:
        pass
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        return type(default)(raw)
    except (TypeError, ValueError):
        return default


def mode():
    """``off`` | ``on_compile`` | ``cli`` (unknown values read as
    off — an observability knob must fail closed, not crash or
    measure)."""
    raw = str(_env_get("MXTPU_MEASURE", "off") or "off").strip().lower()
    return _MODES.get(raw, "off")


def enabled():
    return mode() != "off"


# ---------------------------------------------------------------------------
# registration (the capture_compile hook)
# ---------------------------------------------------------------------------


def _to_spec(tree):
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype") \
                and not isinstance(x, jax.ShapeDtypeStruct):
            try:
                if jax.dtypes.issubdtype(x.dtype, jax.dtypes.extended):
                    return x  # typed PRNG keys etc: keep concrete
            except Exception:
                pass
            if int(getattr(x, "nbytes", 0) or 0) > SMALL_LEAF_BYTES:
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map(leaf, tree)


def _materialize(tree):
    """Fresh device buffers for every array leaf: zeros for specs, a
    copy for concrete leaves. The stored tree itself is NEVER passed to
    the program — donated buffers are invalidated by the run."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return jnp.zeros(x.shape, x.dtype)
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            try:
                return jnp.array(x)  # copies: fresh, donate-safe buffer
            except Exception:
                return x
        return x
    return jax.tree_util.tree_map(leaf, tree)


def maybe_register(block, variant, jitted, args, kwargs=None):
    """The ``capture_compile`` hook. Never raises; the first check is a
    plain env read so ``MXTPU_MEASURE`` unset/off costs one dict lookup
    and touches no jax state."""
    if mode() == "off":
        return None
    if getattr(_tls, "busy", False):
        return None  # measurement re-entered capture_compile
    try:
        spec_args = _to_spec(tuple(args))
        spec_kwargs = _to_spec(dict(kwargs or {}))
        if mode() == "cli":
            with _lock:
                _pending[(str(block), str(variant))] = {
                    "fn": jitted, "args": spec_args,
                    "kwargs": spec_kwargs,
                }
            return None
        return measure_callable(jitted, spec_args, block=block,
                                variant=variant, kwargs=spec_kwargs)
    except Exception:
        return None


def pending():
    """Programs stashed under ``MXTPU_MEASURE=cli`` awaiting
    :func:`sweep`, as ``["block/variant", ...]``."""
    with _lock:
        return sorted(f"{b}/{v}" for b, v in _pending)


def sweep():
    """Measure every stashed program (cli mode); returns the list of
    CostDB entries. Failures skip that program, never abort the
    sweep."""
    with _lock:
        work = list(_pending.items())
        _pending.clear()
    out = []
    for (block, variant), rec in work:
        try:
            entry = measure_callable(
                rec["fn"], rec["args"], block=block, variant=variant,
                kwargs=rec["kwargs"])
        except Exception:
            entry = None
        if entry is not None:
            out.append(entry)
    return out


# ---------------------------------------------------------------------------
# the harness
# ---------------------------------------------------------------------------


def fingerprint_of(closed):
    """Stable program identity: sha1 of the PR-7 dedup structural key
    (identity-hash address tokens scrubbed so the digest survives
    process boundaries), else of the printed jaxpr."""
    text = None
    try:
        from ..passes import dedup as _dedup

        key = _dedup.structural_key(closed)
        if key is not None:
            text = repr(key)
    except Exception:
        pass
    if text is None:
        text = str(getattr(closed, "jaxpr", closed))
    text = re.sub(r"0x[0-9a-fA-F]+", "0x", text)
    return hashlib.sha1(text.encode()).hexdigest()[:16]


def _leaf_summary(tree, cap=32):
    import jax

    names = []
    for x in jax.tree_util.tree_leaves(tree):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            shape = ",".join(str(d) for d in x.shape)
            names.append(f"{x.dtype}[{shape}]")
        else:
            names.append(type(x).__name__)
    more = len(names) - cap
    return names[:cap] + ([f"...+{more}"] if more > 0 else [])


def _telemetry_snapshot():
    keep = ("jit_trace_total",)
    try:
        from ..telemetry import exporters as _exp

        dumped = _exp.dump()
        return {k: dumped[k] for k in keep if k in dumped}
    except Exception:
        return {}


def _peak_device_bytes():
    try:
        import jax

        peaks = []
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if stats and stats.get("peak_bytes_in_use"):
                peaks.append(int(stats["peak_bytes_in_use"]))
        return max(peaks) if peaks else None
    except Exception:
        return None


def _percentile(sorted_ms, q):
    if not sorted_ms:
        return None
    i = min(len(sorted_ms) - 1,
            max(0, int(round(q * (len(sorted_ms) - 1)))))
    return sorted_ms[i]


def measure_callable(fn, args, block="?", variant="?", kwargs=None):
    """Run the warmed, synchronized microbenchmark of ``fn(*args,
    **kwargs)`` and record the CostDB entry. Returns the entry dict, or
    None when the program can't be materialized on this backend."""
    import jax

    kwargs = dict(kwargs or {})
    runs = max(1, int(_env_get("MXTPU_MEASURE_RUNS", 5)))
    warmup = max(0, int(_env_get("MXTPU_MEASURE_WARMUP", 1)))
    _tls.busy = True
    try:
        try:
            mat_args = _materialize(args)
            mat_kwargs = _materialize(kwargs)
        except Exception:
            return None

        # identity + analytic predictions from one suppressed re-trace
        # (trace caches make this cheap when the program is warm; the
        # suppression keeps zero-retrace telemetry proofs honest).
        fingerprint = None
        predicted_bytes = predicted_peak = None
        try:
            from ..passes import _state as _pstate

            with _pstate.suppress_trace_bumps():
                closed = jax.make_jaxpr(
                    lambda *a: fn(*a, **mat_kwargs))(*mat_args)
            fingerprint = fingerprint_of(closed)
            from ..passes import memory as _memory

            regions = _memory.estimate_region_bytes(closed)
            predicted_bytes = sum(
                int(r.get("external_bytes", 0) or 0) for r in regions)
            predicted_peak = int(_memory.estimate_peak_bytes(closed))
        except Exception:
            pass
        if fingerprint is None:
            fingerprint = hashlib.sha1(
                f"{block}/{variant}".encode()).hexdigest()[:16]
        if not predicted_bytes:
            # degenerate programs: price the visible I/O so the
            # drift join has a nonzero denominator
            predicted_bytes = sum(
                int(getattr(x, "nbytes", 0) or 0)
                for x in jax.tree_util.tree_leaves((mat_args,
                                                    mat_kwargs)))

        for _ in range(warmup):
            out = fn(*_materialize(args), **_materialize(kwargs))
            jax.block_until_ready(out)
        times_ms = []
        for _ in range(runs):
            a = _materialize(args)
            k = _materialize(kwargs)
            jax.block_until_ready((a, k))  # zeros before the clock
            t0 = time.perf_counter()
            out = fn(*a, **k)
            jax.block_until_ready(out)
            times_ms.append((time.perf_counter() - t0) * 1000.0)
        times_ms.sort()

        platform = jax.default_backend()
        entry = {
            "fingerprint": fingerprint,
            "platform": str(platform),
            "block": str(block),
            "variant": str(variant),
            "wall_ms_p50": _percentile(times_ms, 0.50),
            "wall_ms_p95": _percentile(times_ms, 0.95),
            "runs": runs,
            "warmup": warmup,
            "peak_bytes": _peak_device_bytes(),
            "predicted_bytes": predicted_bytes,
            "predicted_peak_bytes": predicted_peak,
            "args": _leaf_summary((args, kwargs)),
            "telemetry": _telemetry_snapshot(),
            "time": time.time(),
        }
        from . import costdb as _costdb

        entry = _costdb.db().put(entry)
        try:
            from ..telemetry import instruments as _instr

            _instr.record_cost_measure(block, variant,
                                       wall_ms=entry["wall_ms_p50"])
        except Exception:
            pass
        _costdb.audit()
        return entry
    finally:
        _tls.busy = False


def reset():
    """Test hygiene: drop pending programs."""
    with _lock:
        _pending.clear()
    _tls.busy = False
