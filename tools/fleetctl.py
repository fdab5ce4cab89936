"""fleetctl — one fleet table from N ranks' live ops servers.

Usage:  python tools/fleetctl.py HOST:PORT [HOST:PORT ...]
                [--watch [SEC]] [--json] [--postmortem-all]
                [--merge OUT_PREFIX] [--token TOK]
                [--straggler-skew N] [--timeout SEC]

Each training/serving rank started with ``MXTPU_OPS_PORT`` exposes the
live ops plane (``mxnet_tpu/observability/opsd.py``; endpoint table in
docs/observability.md). fleetctl polls every given endpoint's
``/identity`` + ``/healthz`` + ``/readyz`` + ``/steps`` (plus
``/traces?n=0`` for the request-phase summary) and renders ONE table —
per-rank step, health, readiness, queue depth, SLO burn rate, and the
pipeline phase where request latency goes — with straggler detection
from step-gauge skew: a rank whose last step trails the fleet
maximum by more than ``--straggler-skew`` (default 2) is flagged, which
is the live version of the postmortem question ``tools/blackbox.py``
answers after the fact.

``--watch`` repolls every SEC seconds (default 2). ``--postmortem-all``
fans ``POST /postmortem`` out to every rank (pass ``--token`` when the
fleet sets MXTPU_OPS_TOKEN) and prints the per-rank bundle paths;
``--merge PREFIX`` additionally feeds the returned paths — they must be
reachable from this host, i.e. a shared filesystem or single-host fleet
— through ``tools/blackbox.py`` into ``PREFIX.trace.json`` +
``PREFIX.report.txt``.

Stdlib only: works from a bastion with no jax or mxnet_tpu installed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request

DEFAULT_SKEW = 2


def _get(base, path, timeout):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return json.load(r)


def _post(base, path, timeout, token=""):
    req = urllib.request.Request(base + path, data=b"", method="POST")
    if token:
        req.add_header("Authorization", f"Bearer {token}")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.load(r)


def poll_rank(endpoint, timeout=3.0):
    """One rank's row: identity + health + readiness + step state.
    Unreachable ranks still get a row (health=down) — a dead rank is
    the most important line in the table."""
    base = f"http://{endpoint}"
    row = {"endpoint": endpoint, "health": "down", "ready": False,
           "rank": None, "job": None, "world": None, "last_step": None,
           "step_ms": None, "examples_per_s": None, "queue": None,
           "mesh": None, "coords": None, "zero_frac": None,
           "generation": None, "error": None}
    try:
        ident = _get(base, "/identity", timeout)
        row.update(rank=ident.get("rank"), job=ident.get("job"),
                   world=ident.get("world"), mesh=ident.get("mesh"),
                   coords=ident.get("coords"),
                   zero_frac=ident.get("zero_frac"),
                   generation=ident.get("generation"))
        hz = _get(base, "/healthz", timeout)
        row["health"] = hz.get("status", "ok")
        steps = _get(base, "/steps", timeout)
        row["last_step"] = steps.get("last_step")
        row["step_ms"] = steps.get("step_time_ms_avg")
        row["examples_per_s"] = steps.get("examples_per_second")
    except (urllib.error.URLError, OSError, ValueError) as e:
        row["error"] = str(getattr(e, "reason", e))
        return row
    # /readyz answers 503 when not ready — that's data, not an error
    try:
        req = urllib.request.Request(base + "/readyz")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                rz = json.load(r)
        except urllib.error.HTTPError as e:
            rz = json.load(e)
        row["ready"] = bool(rz.get("ready"))
        checks = rz.get("checks", {})
        row["stalled"] = checks.get("watchdog", {}).get("stalled_sites",
                                                        [])
        engines = checks.get("serving", {}).get("engines", {})
        if engines:
            row["queue"] = sum(e.get("queue_depth", 0)
                               for e in engines.values())
            row["admission"] = {n: e.get("admission")
                                for n, e in engines.items()}
        slo = checks.get("slo", {})
        row["slo_burning"] = sorted(slo.get("burning") or {})
        burns = [c.get("burn")
                 for m in (slo.get("status") or {}).values()
                 for c in m.values() if c.get("burn") is not None]
        row["slo_burn"] = max(burns) if burns else None
    except (urllib.error.URLError, OSError, ValueError) as e:
        row["error"] = str(getattr(e, "reason", e))
    # per-phase latency breakdown from the request-trace summary (n=0:
    # summaries only). Older servers have no /traces — leave it empty.
    try:
        tr = _get(base, "/traces?n=0", timeout)
        row["phases"] = tr.get("phases") or {}
    except (urllib.error.URLError, OSError, ValueError):
        row["phases"] = {}
    return row


def annotate_stragglers(rows, skew=DEFAULT_SKEW):
    """Flag ranks whose last step trails the fleet max by > skew steps.
    Down ranks are always flagged; a one-rank fleet never is."""
    steps = [r["last_step"] for r in rows
             if r["last_step"] is not None and r["health"] != "down"]
    lead = max(steps) if steps else None
    for r in rows:
        behind = (lead is not None and r["last_step"] is not None
                  and lead - r["last_step"] > skew)
        r["straggler"] = bool(
            len(rows) > 1 and (behind or r["health"] == "down"))
        r["fleet_max_step"] = lead
    return rows


def _mesh_cell(r):
    """A rank's place on the device mesh, e.g. 'dp2,tp0 of dp=4,tp=2'
    — plus the ZeRO optimizer-state fraction it holds when the plan
    fsdp-shards state, e.g. '... zero=1/4' (ShardingPlan stamps
    mesh/coords/zero_frac into the flight identity)."""
    mesh, coords = r.get("mesh"), r.get("coords")
    if not mesh:
        return "-"
    shape = ",".join(f"{a}={n}" for a, n in mesh.items())
    zf = r.get("zero_frac")
    zero = f" zero=1/{round(1 / zf)}" if zf else ""
    if not coords:
        return shape + zero
    at = ",".join(f"{a}{i}" for a, i in coords.items())
    return f"{at} of {shape}{zero}"


def _slo_cell(r):
    """A rank's worst SLO burn rate, '!'-flagged while it is shedding
    readiness (e.g. '1.30x!'); '-' when no objective is configured."""
    burn = r.get("slo_burn")
    if burn is None:
        return "-"
    return f"{burn:.2f}x" + ("!" if r.get("slo_burning") else "")


def _phase_cell(r):
    """Where request latency goes on this rank: the heaviest pipeline
    phase by total time share, e.g. 'device 62%'."""
    phases = r.get("phases") or {}
    totals = {p: s.get("avg_ms", 0.0) * s.get("n", 0)
              for p, s in phases.items()}
    grand = sum(totals.values())
    if grand <= 0:
        return "-"
    top = max(totals, key=totals.get)
    return f"{top} {100.0 * totals[top] / grand:.0f}%"


def fleet_table(rows):
    hdr = ["rank", "endpoint", "health", "ready", "step", "step_ms",
           "ex/s", "queue", "slo", "phase", "mesh", "gen", ""]
    table = [hdr]
    for r in sorted(rows, key=lambda r: (r["rank"] is None, r["rank"])):
        flag = "STRAGGLER" if r.get("straggler") else ""
        if r.get("stalled"):
            flag = (flag + " stalled:" + ",".join(r["stalled"])).strip()
        if r.get("slo_burning"):
            flag = (flag + " slo:" + ",".join(r["slo_burning"])).strip()
        if r.get("error"):
            flag = (flag + f" ({r['error']})").strip()
        table.append([
            "?" if r["rank"] is None else str(r["rank"]),
            r["endpoint"],
            r["health"],
            "yes" if r["ready"] else "NO",
            "-" if r["last_step"] is None else str(r["last_step"]),
            "-" if r["step_ms"] is None else f"{r['step_ms']:.1f}",
            "-" if not r["examples_per_s"] else f"{r['examples_per_s']:.0f}",
            "-" if r["queue"] is None else str(r["queue"]),
            _slo_cell(r),
            _phase_cell(r),
            _mesh_cell(r),
            # elastic world generation (docs/elasticity.md): a restarted
            # fleet shows gen>0 — mixed values mean a rank missed a
            # supervisor restart
            "-" if r.get("generation") is None else str(r["generation"]),
            flag,
        ])
    widths = [max(len(row[i]) for row in table)
              for i in range(len(hdr))]
    lines = []
    for i, row in enumerate(table):
        lines.append("  ".join(c.ljust(w) for c, w in zip(row, widths))
                     .rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    jobs = sorted({r["job"] for r in rows if r["job"]})
    n_strag = sum(1 for r in rows if r.get("straggler"))
    lines.append("")
    lines.append(f"job={','.join(jobs) or '?'}  ranks={len(rows)}  "
                 f"stragglers={n_strag}")
    return "\n".join(lines)


def postmortem_all(endpoints, timeout=10.0, token=""):
    """Fan POST /postmortem out to every rank; returns
    ``{endpoint: path-or-error}``."""
    out = {}
    for ep in endpoints:
        try:
            out[ep] = _post(f"http://{ep}", "/postmortem", timeout,
                            token)["path"]
        except urllib.error.HTTPError as e:
            out[ep] = f"ERROR: HTTP {e.code}"
        except (urllib.error.URLError, OSError, ValueError, KeyError) as e:
            out[ep] = f"ERROR: {getattr(e, 'reason', e)}"
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="poll N ranks' live ops servers into one fleet table")
    ap.add_argument("endpoints", nargs="+", metavar="HOST:PORT")
    ap.add_argument("--watch", nargs="?", const=2.0, type=float,
                    default=None, metavar="SEC",
                    help="repoll every SEC seconds (default 2)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable rows instead of the table")
    ap.add_argument("--postmortem-all", action="store_true",
                    help="trigger a postmortem bundle on every rank and "
                         "print the per-rank paths")
    ap.add_argument("--merge", metavar="PREFIX", default=None,
                    help="with --postmortem-all: merge the bundles via "
                         "tools/blackbox.py into PREFIX.trace.json + "
                         "PREFIX.report.txt (paths must be local)")
    ap.add_argument("--token", default="",
                    help="bearer token for POST endpoints "
                         "(the fleet's MXTPU_OPS_TOKEN)")
    ap.add_argument("--straggler-skew", type=int, default=DEFAULT_SKEW,
                    help="flag ranks more than N steps behind the fleet "
                         f"max (default {DEFAULT_SKEW})")
    ap.add_argument("--timeout", type=float, default=3.0,
                    help="per-request timeout seconds")
    args = ap.parse_args(argv)

    if args.postmortem_all:
        paths = postmortem_all(args.endpoints, timeout=max(args.timeout, 10),
                               token=args.token)
        for ep, p in paths.items():
            print(f"{ep}: {p}")
        bad = [p for p in paths.values() if str(p).startswith("ERROR")]
        if args.merge and not bad:
            import os
            sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
            import blackbox

            trace, text = blackbox.merge(
                sorted(set(paths.values())),
                trace_path=f"{args.merge}.trace.json",
                report_path=f"{args.merge}.report.txt")
            sys.stdout.write(text)
            print(f"merged: {args.merge}.trace.json + "
                  f"{args.merge}.report.txt")
        return 1 if bad else 0

    while True:
        rows = annotate_stragglers(
            [poll_rank(ep, timeout=args.timeout) for ep in args.endpoints],
            skew=args.straggler_skew)
        if args.json:
            print(json.dumps(rows, default=str))
        else:
            if args.watch is not None:
                sys.stdout.write("\x1b[2J\x1b[H")  # clear, home
            print(fleet_table(rows))
        if args.watch is None:
            return 0 if not any(r.get("straggler") for r in rows) else 2
        try:
            time.sleep(args.watch)
        except KeyboardInterrupt:
            return 0


if __name__ == "__main__":
    raise SystemExit(main())
