"""diagnose — run a short instrumented workload and print the full
diagnostics report (docs/diagnostics.md explains every section).

Usage:  python tools/diagnose.py [--steps N] [--batch B] [--hidden H]
                                 [--json] [--watchdog-demo] [--startup]
        python tools/diagnose.py --live HOST:PORT [--json]

Runs N training steps of a small hybridized MLP with every diagnostics
layer armed (spans, compile introspection, device-memory gauge), then
prints `diagnostics.report()`: the per-step phase breakdown
(data/fwd/bwd/collective/optimizer/sync/compile), the XLA compile
registry (flops / bytes accessed / peak-HBM per block variant), live
device memory, and the sync/collective telemetry series.

`--json` emits the same content as one machine-readable JSON object
(step_table + compile_registry + device_memory + telemetry dump).

`--startup` adds the start-up report (`diagnostics.startup_report()`):
seconds from `import mxnet_tpu` to the first finished step by phase, the
programs JAX traced, lowered, loaded or built by name, and what fills
the persistent compile cache by module name.

`--watchdog-demo` arms the watchdog with a short deadline around a
deliberate stall so you can see exactly what a hang dump looks like
before you need one at 3am.

On a real deployment, skip this tool's toy model: call
`mxnet_tpu.diagnostics.report()` from your own training loop — the same
sections fill themselves from whatever ran. Or better, point `--live`
at a rank started with MXTPU_OPS_PORT: the report renders from the
running server's `/metrics` + `/steps` + `/flight` + `/identity`
(observability/opsd.py) with no workload, no jax import, and no
perturbation of the job being diagnosed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train(steps, batch, hidden):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Trainer, TrainStep, nn

    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu"), nn.Dense(hidden // 2))
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05})
    # drive steps through TrainStep: with MXTPU_WHOLE_STEP=1 (default)
    # the whole iteration is ONE donated dispatch and the report's
    # whole-step section fills; MXTPU_WHOLE_STEP=0 shows the phased
    # three-dispatch breakdown instead
    step = TrainStep(net, lambda out: (out * out).sum(axis=-1), trainer)
    x = mx.np.ones((batch, hidden))
    for _ in range(steps):
        step(x, batch_size=batch)
    # one checkpoint save so the report's `checkpoint` phase column is
    # exercised (capture span + async commit through the engine IO path)
    import shutil
    import tempfile

    ckdir = tempfile.mkdtemp(prefix="diagnose-ckpt-")
    try:
        mgr = mx.checkpoint.CheckpointManager(ckdir, trainer, keep_last=1)
        mgr.save(step=steps)
        mgr.flush()
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)
    mx.waitall()
    return net


def _fused_buckets():
    """Fused-update bucket composition from the compile registry: each
    `fused_update` entry's variant encodes `{opt}-n{params}-{dtype}-mp{
    0|1}` (optimizer/optimizer.py update_fused), so the registry doubles
    as a record of how the parameter tree was bucketed."""
    from mxnet_tpu import diagnostics

    out = []
    for (block, variant), e in sorted(diagnostics.compile_registry()
                                      .items()):
        if block != "fused_update":
            continue
        info = {"variant": variant}
        parts = variant.split("-")
        try:
            info.update(optimizer=parts[0], params=int(parts[1][1:]),
                        dtype=parts[2],
                        multi_precision=parts[3] == "mp1")
        except (IndexError, ValueError):
            pass
        for k in ("flops", "bytes_accessed", "peak_bytes"):
            if isinstance(e, dict) and e.get(k) is not None:
                info[k] = e[k]
        out.append(info)
    return out


def _fused_report_lines(buckets):
    lines = ["", "== fused update buckets =="]
    if not buckets:
        lines.append("  (none captured — legacy per-param path, or "
                     "MXTPU_DIAG_COMPILE=0)")
        return lines
    for b in buckets:
        desc = f"  {b['variant']}:"
        if "params" in b:
            desc += f" {b['params']} params"
        if "dtype" in b:
            desc += f", {b['dtype']}"
        if b.get("multi_precision"):
            desc += ", multi-precision"
        if "flops" in b:
            desc += f", {b['flops']:.3g} flops"
        lines.append(desc)
    return lines


def _whole_step_report():
    """Per-step dispatch accounting + the whole-step program's compile
    cost/memory, next to the fused-bucket report: how many training
    steps ran as ONE donated dispatch (path=whole_step) vs the legacy
    three-phase sequence (path=phased), and what XLA built for the
    one-dispatch program (flops / peak HBM from the compile registry)."""
    from mxnet_tpu import diagnostics
    from mxnet_tpu.telemetry import instruments as ti

    dispatches = {labels[0]: c.value
                  for labels, c in ti.step_dispatch_total.series()}
    programs = []
    for (block, variant), e in sorted(diagnostics.compile_registry()
                                      .items()):
        if block != "whole_step":
            continue
        info = {"variant": variant}
        for k in ("flops", "bytes_accessed", "peak_bytes",
                  "compile_seconds"):
            if isinstance(e, dict) and e.get(k) is not None:
                info[k] = e[k]
        programs.append(info)
    return {
        "step_dispatches": dispatches,
        "donated_bytes": ti.step_donated_bytes.value,
        "programs": programs,
    }


def _whole_step_report_lines(ws):
    lines = ["", "== whole-step dispatches =="]
    d = ws["step_dispatches"]
    if not d:
        lines.append("  (no steps recorded)")
        return lines
    for path, n in sorted(d.items()):
        per = "1 dispatch/step" if path == "whole_step" \
            else "fwd + bwd + update dispatches"
        lines.append(f"  {path}: {int(n)} steps ({per})")
    if ws["donated_bytes"]:
        lines.append(f"  donated in place: {int(ws['donated_bytes'])} "
                     "bytes (params + optimizer state, cumulative)")
    for p in ws["programs"]:
        desc = f"  program {p['variant']}:"
        if "flops" in p:
            desc += f" {p['flops']:.3g} flops"
        if "peak_bytes" in p:
            desc += f", peak HBM {int(p['peak_bytes'])} bytes"
        if "compile_seconds" in p:
            desc += f", compiled in {p['compile_seconds']:.2f}s"
        lines.append(desc)
    return lines


def _passes_demo(hidden):
    """Short graph-pass workload: one AMP-converted block through the
    pipeline, so the pass series below have something to show."""
    import mxnet_tpu as mx
    from mxnet_tpu import amp
    from mxnet_tpu.gluon import nn

    x = mx.np.ones((8, hidden))
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu"), nn.Dense(4))
    net.initialize()
    net.hybridize()
    amp.convert_hybrid_block(net, graph_pass=True, example_inputs=(x,))
    mx.waitall()


def _passes_report():
    """Graph-pass pipeline state: resolved env config, per-pass apply
    counts/rewrite timing and the sharding plan (docs/passes.md)."""
    from mxnet_tpu import env as _env
    from mxnet_tpu import passes
    from mxnet_tpu.telemetry import instruments as ti

    return {
        "config": {"MXTPU_PASSES": _env.get("MXTPU_PASSES")},
        "pipeline_enabled": passes.pipeline_enabled(),
        "pass_applied": {labels[0]: int(c.value)
                         for labels, c in ti.pass_applied_total.series()},
        "pass_rewrites": {labels[0]: int(h.count)
                          for labels, h in ti.pass_rewrite_ms.series()},
        "sharding": _sharding_report(),
    }


def _sharding_report():
    """Sharding-subsystem state: resolved env config, plan applications,
    per-axis mesh sizes, and the most recently applied plan's param →
    spec → bytes/device table (docs/sharding.md)."""
    from mxnet_tpu import env as _env
    from mxnet_tpu import sharding
    from mxnet_tpu.telemetry import instruments as ti

    return {
        "config": {k: _env.get(k) for k in
                   ("MXTPU_SHARDING", "MXTPU_MESH")},
        "mode": sharding.mode(),
        "applied": {labels[0]: int(c.value) for labels, c in
                    ti.sharding_plan_applied_total.series()},
        "mesh_axes": {labels[0]: int(g.value) for labels, g in
                      ti.sharding_mesh_axis_size.series()},
        "last_applied": sharding.last_applied(),
    }


def _passes_report_lines(pr):
    lines = ["", "== graph passes =="]
    cfg = " ".join(f"{k}={v!r}" for k, v in pr["config"].items())
    lines.append(f"  config: {cfg} (enabled={pr['pipeline_enabled']})")
    if pr["pass_applied"]:
        for name, n in sorted(pr["pass_applied"].items()):
            lines.append(f"  pass {name}: applied {n}x")
    else:
        lines.append("  (no passes applied)")
    sh = pr.get("sharding") or {}
    sh_cfg = " ".join(f"{k}={v!r}" for k, v in
                      (sh.get("config") or {}).items())
    lines.append(f"  sharding: {sh_cfg} mode={sh.get('mode')}")
    for label, n in sorted((sh.get("applied") or {}).items()):
        lines.append(f"    plan {label}: applied {n}x")
    if sh.get("mesh_axes"):
        axes = " ".join(f"{a}={n}" for a, n in
                        sorted(sh["mesh_axes"].items()))
        lines.append(f"    mesh axes: {axes}")
    la = sh.get("last_applied")
    if la:
        lines.append(f"    last plan: mesh={la['mesh']} over "
                     f"{la['devices']} device(s)"
                     + (f" zero_axis={la['zero_axis']}"
                        if la.get("zero_axis") else ""))
        lines.append("    param                                    "
                     "spec                      bytes/device "
                     "opt-state B/dev")
        for row in la["params"]:
            lines.append(f"    {row['param']:<40} {row['spec']:<25} "
                         f"{row['bytes_per_device']:>12} "
                         f"{row.get('state_bytes_per_device', '-'):>15}")
    return lines


def _promparse():
    """Load telemetry/promparse.py by path — the --live mode must work
    from a bastion without importing mxnet_tpu (and its jax)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "mxnet_tpu", "telemetry", "promparse.py")
    spec = importlib.util.spec_from_file_location("_mxtpu_promparse", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _live_fetch(endpoint, timeout=5.0):
    """Pull one running rank's diagnostics surfaces: parsed /metrics,
    /steps, /flight tail, /identity."""
    import urllib.request

    base = f"http://{endpoint}"

    def get_json(path):
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return json.load(r)

    with urllib.request.urlopen(base + "/metrics", timeout=timeout) as r:
        metrics_text = r.read().decode("utf-8")
    pp = _promparse()
    return {
        "identity": get_json("/identity"),
        "steps": get_json("/steps"),
        "flight": get_json("/flight?n=40"),
        "metrics": pp.parse_text(metrics_text),
        "_pp": pp,
    }


def _live_report_lines(live):
    pp = live["_pp"]
    fam = live["metrics"]

    def v(name, labels=None):
        return pp.sample_value(fam, name, labels)

    ident = live["identity"]
    lines = [f"== live diagnostics: rank {ident.get('rank')} "
             f"(job {ident.get('job')!r}, world {ident.get('world')}, "
             f"pid {ident.get('pid')}) =="]

    steps = live["steps"]
    lines += ["", "== per-step phase breakdown =="]
    table = steps.get("step_table", {})
    if table:
        phases = sorted({p for row in table.values() for p in row})
        hdr = "  step  " + "  ".join(f"{p:>10}" for p in phases)
        lines.append(hdr)
        for s in sorted(table, key=lambda k: int(k))[-8:]:
            row = table[s]
            lines.append("  " + f"{s:>4}  " + "  ".join(
                f"{row.get(p, 0) * 1e3:>8.2f}ms" for p in phases))
    else:
        lines.append("  (no steps recorded)")
    lines.append(f"  last step: {steps.get('last_step')}  "
                 f"avg step: {steps.get('step_time_ms_avg')}ms  "
                 f"examples/s: {steps.get('examples_per_second')}")
    if steps.get("step_dispatches"):
        lines.append("  dispatches: " + "  ".join(
            f"{p}={int(n)}" for p, n in
            sorted(steps["step_dispatches"].items())))

    lines += ["", "== telemetry (scraped /metrics) =="]
    for name in ("step_total", "jit_compile_total", "transfer_bytes_total",
                 "engine_sync_total", "collective_calls_total",
                 "flight_events_total", "postmortem_dump_total"):
        val = v(name)
        if val is None:  # labeled family: sum its series
            f = fam.get(name)
            if f and f["samples"]:
                val = sum(s["value"] for s in f["samples"]
                          if not s["name"].endswith(("_sum", "_count"))
                          and "le" not in s["labels"])
        if val is not None:
            lines.append(f"  {name}: {val:g}")
    lines.append(f"  ({len(fam)} metric families scraped)")

    lines += ["", "== flight tail =="]
    evs = live["flight"].get("events", [])
    for ev in evs[-12:]:
        extra = {k: v for k, v in ev.items()
                 if k not in ("kind", "t", "pc", "step")}
        ex = " ".join(f"{k}={v}" for k, v in extra.items())
        lines.append(f"  step {ev.get('step', 0):>5}  "
                     f"{ev.get('kind', '?'):<18} {ex}".rstrip())
    if not evs:
        lines.append("  (flight ring empty)")
    lines.append("")
    lines.append(f"  {live['flight'].get('total', 0)} events in ring "
                 f"(capacity {live['flight'].get('capacity')})")
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--live", metavar="HOST:PORT", default=None,
                    help="render the report from a running rank's ops "
                         "server (MXTPU_OPS_PORT) instead of an "
                         "in-process workload")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable JSON instead of the text report")
    ap.add_argument("--watchdog-demo", action="store_true",
                    help="stall on purpose and show the watchdog dump")
    ap.add_argument("--startup", action="store_true",
                    help="add the start-up report: seconds to the first "
                         "finished step by phase and program, and the "
                         "compile cache's contents by module name")
    ap.add_argument("--passes", action="store_true",
                    help="run the graph-pass demo (pipeline AMP) and "
                         "print the pass report section")
    args = ap.parse_args(argv)

    if args.live:
        live = _live_fetch(args.live)
        if args.json:
            out = {k: v for k, v in live.items() if k != "_pp"}
            print(json.dumps(out, default=str))
        else:
            print("\n".join(_live_report_lines(live)))
        return

    os.environ.setdefault("MXTPU_TELEMETRY", "1")
    from mxnet_tpu import diagnostics, telemetry

    telemetry.enable()
    _train(args.steps, args.batch, args.hidden)
    if args.passes:
        _passes_demo(args.hidden)
    diagnostics.update_device_memory_gauge()

    if args.watchdog_demo:
        from mxnet_tpu.diagnostics import watchdog

        watchdog.configure(MXTPU_WATCHDOG=1,
                           MXTPU_WATCHDOG_TIMEOUT_S=0.2,
                           MXTPU_WATCHDOG_FILE=os.devnull)
        print("-- watchdog demo: stalling 0.5s under a 0.2s deadline --",
              file=sys.stderr)
        with watchdog.guard("diagnose-demo-stall"):
            time.sleep(0.5)
        watchdog.configure(MXTPU_WATCHDOG=None,
                           MXTPU_WATCHDOG_TIMEOUT_S=None,
                           MXTPU_WATCHDOG_FILE=None)

    if args.json:
        reg = {f"{b}/{v}": e
               for (b, v), e in diagnostics.compile_registry().items()}
        print(json.dumps({
            "step_table": {str(k): v
                           for k, v in diagnostics.step_table().items()},
            "compile_registry": reg,
            "fused_buckets": _fused_buckets(),
            "whole_step": _whole_step_report(),
            "passes": _passes_report(),
            "device_memory": diagnostics.device_memory(),
            "telemetry": telemetry.dump(),
            **({"startup": diagnostics.startup_report(cache=True)}
               if args.startup else {}),
        }, default=str))
    else:
        print(diagnostics.report())
        print("\n".join(_fused_report_lines(_fused_buckets())))
        print("\n".join(_whole_step_report_lines(_whole_step_report())))
        if args.passes:
            print("\n".join(_passes_report_lines(_passes_report())))
        if args.startup:
            print("\n== start-up (s) " + "=" * 54 + "\n")
            print(diagnostics.format_startup_table(
                diagnostics.startup_report(cache=True)))


if __name__ == "__main__":
    main()
