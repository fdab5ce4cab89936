"""Persistent CostDB: measured program costs + predicted-vs-measured
drift auditing.

The other half of the measurement plane (observability/measure.py runs
the microbenchmarks; this module keeps the results). The remat auto
policy decides from the analytic byte model in ``passes/memory.py``,
and nothing else checks whether its predictions match reality. The
CostDB records both sides:

  * every measured program lands here keyed by ``(fingerprint,
    platform)`` — the PR-7 dedup structural fingerprint, so two
    processes (or two runs) measuring structurally identical programs
    share one record;
  * the file is atomic JSON-lines (write-tmp → fsync → ``os.replace``
    through the ``_checkpoint_io`` engine path, the postmortem idiom):
    ``save()`` first merges what other processes committed since our
    load, newest measurement wins, so N ranks on a shared filesystem
    converge instead of clobbering;
  * :func:`drift_report` joins the measurements against the analytic
    predictions. Absolute bandwidth is unknowable portably, so the
    auditor self-calibrates: the median ``predicted_bytes / wall_ms``
    over a platform's entries is that platform's effective bandwidth,
    and each program's drift ratio is its own implied bandwidth over
    the median. A ratio far from 1.0 (beyond
    ``MXTPU_COSTDB_DRIFT_MAX``, either direction) means the byte model
    is lying about THAT program — exactly the case where remat-auto
    chose wrong;
  * :func:`audit` publishes ``cost_model_drift_ratio{site,program}``
    gauges (one per measured program) and drops a ``cost_drift`` flight
    event the first time a program trips.

Surfaced by opsd ``GET /costdb``, ``tools/diagnose.py --passes``,
``tools/costdb.py`` (list/measure/verify/diff), postmortem bundles, and
the fleetctl ``drift`` column. This is the substrate the ROADMAP
autotuner ("persist winners keyed by (program fingerprint, platform)")
plugs into.
"""
from __future__ import annotations

import json
import os
import statistics
import threading
import time

__all__ = [
    "CostDB", "db", "reset", "default_path",
    "drift_report", "drift_max", "audit",
]

DB_FORMAT = 1


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
    if isinstance(default, bool):
        return raw.lower() not in ("", "0", "false", "off")
    try:
        return type(default)(raw)
    except (TypeError, ValueError):
        return default


def default_path():
    """``MXTPU_COSTDB_PATH``, else ``<MXTPU_FLIGHTREC_DIR>/
    mxtpu_costdb.jsonl`` — next to the postmortem bundles."""
    p = str(_env_get("MXTPU_COSTDB_PATH", "") or "")
    if p:
        return p
    d = str(_env_get("MXTPU_FLIGHTREC_DIR", ".") or ".")
    return os.path.join(d, "mxtpu_costdb.jsonl")


def _atomic_write(path, payload):
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(payload)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class CostDB:
    """In-memory measurement cache over one atomic JSON-lines file.

    Entries are dicts from ``measure.measure_callable`` — at minimum
    ``{fingerprint, platform, block, variant, wall_ms_p50, wall_ms_p95,
    predicted_bytes, time}``. The newest ``time`` wins on every merge,
    in memory and on disk alike.
    """

    def __init__(self, path=None, load=True):
        self.path = path or default_path()
        self._entries = {}  # (fingerprint, platform) -> entry dict
        self._lock = threading.Lock()
        if load:
            self.merge_load()

    @staticmethod
    def _key(entry):
        return (str(entry.get("fingerprint", "?")),
                str(entry.get("platform", "?")))

    def put(self, entry):
        """Merge one measurement (newest time wins); autosaves when
        ``MXTPU_COSTDB_AUTOSAVE`` (default on). Returns the entry."""
        entry = dict(entry)
        entry.setdefault("time", time.time())
        entry.setdefault("format", DB_FORMAT)
        with self._lock:
            k = self._key(entry)
            prev = self._entries.get(k)
            if prev is None or prev.get("time", 0) <= entry["time"]:
                self._entries[k] = entry
        if _env_get("MXTPU_COSTDB_AUTOSAVE", True):
            try:
                self.save()
            except Exception:
                pass  # a read-only filesystem must not fail a measurement
        return entry

    def get(self, fingerprint, platform):
        with self._lock:
            return self._entries.get((str(fingerprint), str(platform)))

    def entries(self):
        """Snapshot, oldest measurement first."""
        with self._lock:
            return sorted(self._entries.values(),
                          key=lambda e: e.get("time", 0))

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def merge_load(self):
        """Merge the on-disk file into memory (newest time wins per
        key). Tolerates a missing file and skips torn/garbage lines —
        the JSONL is append-merged by many processes. Returns the
        number of entries merged in."""
        merged = 0
        try:
            with open(self.path) as f:
                lines = f.readlines()
        except OSError:
            return 0
        with self._lock:
            for line in lines:
                line = line.strip()
                if not line:
                    continue
                try:
                    entry = json.loads(line)
                except ValueError:
                    continue
                if not isinstance(entry, dict):
                    continue
                k = self._key(entry)
                prev = self._entries.get(k)
                if prev is None or \
                        prev.get("time", 0) < entry.get("time", 0):
                    self._entries[k] = entry
                    merged += 1
        return merged

    def save(self, sync=True):
        """Commit the merged view atomically: re-merge what other
        processes wrote since our load, then write-tmp → fsync →
        ``os.replace`` through the ``_checkpoint_io`` engine path (the
        postmortem idiom — a kill mid-write leaves the previous
        complete file). Returns the path."""
        self.merge_load()
        with self._lock:
            rows = sorted(self._entries.values(),
                          key=lambda e: e.get("time", 0))
        payload = "".join(
            json.dumps(e, default=str) + "\n" for e in rows)
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        try:
            from .. import _checkpoint_io

            _checkpoint_io.async_run(
                self.path, lambda: _atomic_write(self.path, payload))
            if sync:
                _checkpoint_io.wait_for_path(self.path)
        except Exception:
            _atomic_write(self.path, payload)
        return self.path

    def summary(self):
        entries = self.entries()
        return {
            "path": self.path,
            "entries": len(entries),
            "platforms": sorted({str(e.get("platform"))
                                 for e in entries}),
            "blocks": sorted({f"{e.get('block')}/{e.get('variant')}"
                              for e in entries}),
        }


# ---------------------------------------------------------------------------
# per-process singleton
# ---------------------------------------------------------------------------

_db = [None]
_db_lock = threading.Lock()
_tripped = set()  # (fingerprint, platform) already flight-evented


def db():
    """The per-process CostDB (lazily created, merge-loaded from
    :func:`default_path`)."""
    with _db_lock:
        if _db[0] is None:
            _db[0] = CostDB()
        return _db[0]


def reset():
    """Drop the in-memory DB + drift-event dedup (test hygiene). The
    on-disk file is untouched; the next :func:`db` re-loads it from the
    path resolved THEN, so tests can repoint MXTPU_COSTDB_PATH."""
    with _db_lock:
        _db[0] = None
    _tripped.clear()


# ---------------------------------------------------------------------------
# drift auditing
# ---------------------------------------------------------------------------


def drift_max():
    """The trip threshold: a program whose drift ratio leaves
    ``[1/max, max]`` trips the auditor. Analytic byte models are crude
    — within an order of magnitude of the platform norm is
    "consistent"; beyond it the model is mispredicting that program."""
    try:
        return max(1.0, float(_env_get("MXTPU_COSTDB_DRIFT_MAX", 8.0)))
    except (TypeError, ValueError):
        return 8.0


def drift_report(entries=None, threshold=None):
    """Join measurements against the analytic byte model.

    Per platform: ``calibration`` = median implied bandwidth
    (predicted_bytes / wall_ms_p50) over that platform's entries; each
    program's ``drift_ratio`` is its own implied bandwidth over the
    median, so 1.0 means "the model prices this program like it prices
    everything else here" and a large/small ratio means the model
    over/under-predicts its bytes. Returns::

        {"threshold": float,
         "calibration": {platform: bytes_per_ms},
         "programs": [{program, fingerprint, platform, drift_ratio,
                       tripped, wall_ms_p50, predicted_bytes}, ...],
         "tripped": [the subset with tripped=True]}
    """
    if entries is None:
        entries = db().entries()
    if threshold is None:
        threshold = drift_max()
    usable = [e for e in entries
              if float(e.get("predicted_bytes") or 0) > 0
              and float(e.get("wall_ms_p50") or 0) > 0]
    by_platform = {}
    for e in usable:
        by_platform.setdefault(str(e.get("platform")), []).append(e)
    calibration = {}
    programs = []
    for platform, group in sorted(by_platform.items()):
        bws = [float(e["predicted_bytes"]) / float(e["wall_ms_p50"])
               for e in group]
        calib = statistics.median(bws)
        calibration[platform] = calib
        for e, bw in zip(group, bws):
            ratio = bw / calib if calib > 0 else 1.0
            programs.append({
                "program": f"{e.get('block')}/{e.get('variant')}",
                "fingerprint": e.get("fingerprint"),
                "platform": platform,
                "drift_ratio": round(ratio, 4),
                "tripped": bool(ratio > threshold
                                or ratio < 1.0 / threshold),
                "wall_ms_p50": e.get("wall_ms_p50"),
                "predicted_bytes": e.get("predicted_bytes"),
            })
    programs.sort(key=lambda r: -abs(_log_ratio(r["drift_ratio"])))
    return {
        "threshold": threshold,
        "calibration": calibration,
        "programs": programs,
        "tripped": [r for r in programs if r["tripped"]],
    }


def _log_ratio(r):
    import math

    try:
        return math.log(max(float(r), 1e-12))
    except (TypeError, ValueError):
        return 0.0


def audit(entries=None, threshold=None):
    """Run the drift join and publish it: one
    ``cost_model_drift_ratio{site="program", program}`` gauge per
    measured program, and a ``cost_drift`` flight event the FIRST time
    a (fingerprint, platform) trips — re-audits (opsd polls) don't spam
    the ring. Never raises; returns the :func:`drift_report` dict."""
    try:
        rep = drift_report(entries=entries, threshold=threshold)
    except Exception as e:
        return {"error": repr(e), "programs": [], "tripped": [],
                "calibration": {}, "threshold": None}
    try:
        from ..telemetry import instruments as _instr

        for r in rep["programs"]:
            _instr.set_cost_drift("program", r["program"],
                                  r["drift_ratio"])
    except Exception:
        pass
    for r in rep["tripped"]:
        key = (r["fingerprint"], r["platform"])
        if key in _tripped:
            continue
        _tripped.add(key)
        try:
            from . import flight as _flight

            _flight.record(
                "cost_drift", program=r["program"],
                fingerprint=r["fingerprint"], platform=r["platform"],
                drift_ratio=r["drift_ratio"],
                predicted_bytes=r["predicted_bytes"],
                wall_ms_p50=r["wall_ms_p50"],
                threshold=rep["threshold"])
        except Exception:
            pass
    return rep
