#!/usr/bin/env python3
"""chipbench: one process, one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves ``workloads/<cell>.json`` -> ``configs/<config>.json`` ->
``models/<builder>.py`` + ``reference/<builder>.py`` and
``drivers/<driver>.py``, and, in a traced run, every per-layer metric of
the cell by name under ``layer_metrics/``.  This file knows no model, no
driver and no metric by name.  The last line of standard output is the
result: one JSON object (see README.md).
"""
import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
T0_ENV = "CHIPBENCH_T0"


def _load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _load_reader(name):
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Harness:
    """What a driver gets: the cell's files, the clock of the run, spans
    and annotations, the profiler, and the list of checks."""

    def __init__(self, args, workload, cfg, jax, mx, t_process):
        from compare import Checks

        self.args, self.workload, self.cfg = args, workload, cfg
        self.traffic = workload["traffic_params"]
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.jax, self.mx = jax, mx
        self.reference = importlib.import_module(
            "reference." + cfg["builder"])
        self.model = importlib.import_module("models." + cfg["builder"])
        self.t_process = t_process
        self.spans, self.notes, self.checks = [], {}, Checks()
        self.cache_events = {"hits": 0, "misses": 0}
        self.setup_cache_events = None
        self.trace_dir = os.path.join(ROOT, ".chipbench", "trace",
                                      args.workload)
        self.traced = False

    @contextlib.contextmanager
    def span(self, name, compile=False):
        """A host-clock span of set-up; ``compile`` marks the first call
        of a shape, whose time is compilation."""
        t = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append({"name": name, "compile": bool(compile),
                               "s": time.perf_counter() - t})

    def annotate(self, name):
        """The host's activity, written into the profiler's trace (on
        the devices' clock) while one is being taken."""
        if self.traced:
            return self.jax.profiler.TraceAnnotation("chipbench:" + name)
        return contextlib.nullcontext()

    def window_opens(self):
        """Set-up ends here; returns ``setup_s``."""
        self.setup_cache_events = dict(self.cache_events)
        return time.monotonic() - self.t_process

    def trace_start(self):
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        # the host's TraceMe events (the annotations) stay on; Python's
        # own call tracer would slow the very loop that is being traced
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.traced = True

    def trace_stop(self):
        if self.traced:
            self.jax.profiler.stop_trace()
            self.traced = False

    def memory_peak(self):
        """Peak bytes on the fullest chip, read when the window closes
        and before the reference runs: live arrays plus what the runtime
        reserved for the running programs' temporaries."""
        stats = [d.memory_stats() or {} for d in self.jax.local_devices()]
        self.note(memory_stats=stats[0])
        return int(max(s.get("peak_bytes_in_use", 0)
                       + s.get("peak_bytes_reserved", 0) for s in stats))

    def dump(self, name, obj):
        """Keep what a run compared, for whoever has to find out why."""
        d = os.path.join(ROOT, ".chipbench", "checks")
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(
                d, f"{self.args.workload}.{self.seed}.{name}.json"), "w") as f:
            json.dump(obj, f)

    def note(self, **kv):
        self.notes.update(kv)


def _metrics_of(bench, cell, group):
    """The metrics of ``group`` that BENCHMARK.json gives this cell."""
    return [m for m in bench.get(group, [])
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # Four programs' argument order follows the hash seed (PERF.md
    # section 7), which changes their cache keys from process to
    # process: pin it, once, before anything imports JAX.
    if os.environ.get("PYTHONHASHSEED") != "0":
        env = dict(os.environ, PYTHONHASHSEED="0")
        env.setdefault(T0_ENV, repr(time.monotonic()))
        os.execve(sys.executable, [sys.executable, os.path.abspath(__file__)]
                  + (sys.argv[1:] if argv is None else list(argv)), env)
    t_process = float(os.environ.get(T0_ENV) or time.monotonic())
    # the compile cache: a fixed directory inside the checkout
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (HERE, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)

    workload = _load_json("workloads", args.workload + ".json")
    cfg = _load_json("configs", workload["config"] + ".json")
    toy = args.workload.startswith("toy_")
    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    bench = {}
    if os.path.exists(bench_path):
        with open(bench_path) as f:
            bench = json.load(f)
    listed = any(w["name"] == args.workload
                 for w in bench.get("workloads", []))
    if toy and listed:
        raise SystemExit("a toy_* preset may not be a cell of BENCHMARK.json")

    import mxnet_tpu as mx      # first: it places JAX's defaults
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not toy:
        print(f"chipbench: {args.workload} needs a TPU, found "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    if len(devs) < int(workload.get("chips", 1)):
        print(f"chipbench: {args.workload} needs {workload['chips']} "
              f"chip(s), JAX sees {len(devs)}; nothing was run",
              file=sys.stderr)
        return 2

    h = Harness(args, workload, cfg, jax, mx, t_process)

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            h.cache_events["hits"] += 1
        elif event.endswith("/cache_misses"):
            h.cache_events["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    driver = importlib.import_module("drivers." + workload["driver"])
    out = driver.run(h)
    h.trace_stop()

    units = {m["name"]: m["unit"]
             for g in ("end_to_end", "per_layer") for m in bench.get(g, [])}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": h.checks.ok, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    run = dict(out["run"], setup_s=out["setup_s"], spans=h.spans,
               cache_events=h.setup_cache_events, cfg=cfg,
               workload=workload, device_kind=dev.device_kind,
               platform=dev.platform)
    if not args.trace:
        values = dict(out["end_to_end"], setup_s=out["setup_s"])
        wanted = ([m["name"] for m in _metrics_of(bench, args.workload,
                                                  "end_to_end")]
                  if listed else sorted(values))
        for name in wanted:
            result["metrics"][name] = {"value": values[name],
                                       "unit": units.get(name, "")}
    else:
        import trace_reduce

        loaded = trace_reduce.load(
            trace_reduce.newest_xplane(h.trace_dir), dev.platform)
        with open(os.path.join(h.trace_dir, "layout.json"), "w") as f:
            json.dump({"layout": sorted(set(loaded["layout"])),
                       "kernels": loaded["kernels"]}, f)
        trace = trace_reduce.reduce(loaded["devices"], loaded["notes"],
                                    kernels=loaded["kernels"])
        if trace is None:
            raise RuntimeError(
                "the traced window holds no device operation; planes and "
                f"lines seen: {sorted(set(loaded['layout']))[:40]}")
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        if listed:
            names = [m["name"] for m in _metrics_of(bench, args.workload,
                                                    "per_layer")]
        else:
            names = sorted(f[:-3] for f in os.listdir(
                os.path.join(HERE, "layer_metrics")) if f.endswith(".py"))
        for name in names:
            value = _load_reader(name).read(trace, run)
            if value is not None:
                result["metrics"][name] = {"value": float(value),
                                           "unit": units.get(name, "")}
    print(json.dumps({"notes": h.notes, "spans": h.spans,
                      "cache_events": h.cache_events}), flush=True)
    # every number compared beside its limit: the last lines of standard
    # error, and the last key of the result
    result["checks"] = {r["name"]: [r["value"], r["limit"]]
                        for r in h.checks.rows}
    for r in h.checks.rows:
        print(f"check {r['name']} {r['value']!r} limit {r['limit']!r}"
              + ("" if r["ok"] else " FAILED"), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
