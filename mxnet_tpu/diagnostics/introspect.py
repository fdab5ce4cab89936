"""XLA executable introspection: compile registry + device-memory gauge.

Every jit compile on the CachedOp path (gluon/block.py) calls
:func:`capture_compile` with the jitted callable and its concrete example
arguments. We ask for the same signature's executable
(``fn.lower(*args).compile()``) and harvest what XLA knows about the
program:

  * ``compiled.cost_analysis()``   -> flops, bytes accessed, transcendentals
  * ``compiled.memory_analysis()`` -> argument/output/temp/generated-code
                                      bytes, whose sum approximates the
                                      executable's peak HBM footprint

into a per-(block, variant) registry, so MFU and the HBM-bound claim in
the perf audit are *measured* per compiled program, not modeled. The
numbers also land on the telemetry registry as ``mxtpu_compile_flops`` /
``mxtpu_compile_peak_hbm_bytes`` gauges, so they flow through every
existing exporter (Prometheus / JSON / chrome counters).

Cost: reading the text and the analyses of the executable jit just
built.  ``lower`` and ``compile`` answer from jit's own caches (the traced
jaxpr, the lowered module, its executable) when they are asked for exactly
what jit was called with: the same shapes and types AND, for an operand
that is committed to its devices, the same sharding.  A caller whose
buffers were donated passes ``ShapeDtypeStruct``s that keep those
shardings (``optimizer._specs``); a spec that leaves one out lowers and
compiles the whole program a second time.  ``MXTPU_DIAG_COMPILE=0`` skips
the capture.

``device_memory()`` reads ``jax.local_devices()[*].memory_stats()`` live —
a real HBM gauge on TPU/GPU, ``None`` per device on CPU (surfaced as
``stats: None``, never a crash).
"""
from __future__ import annotations

import os
import re
import threading

from . import spans as _spans

__all__ = [
    "capture_compile", "compile_registry", "reset", "op_scopes",
    "device_memory", "update_device_memory_gauge",
    "format_compile_table", "capture_enabled",
]

_entries = {}  # (block, variant) -> entry dict
_lock = threading.Lock()


def capture_enabled():
    try:
        from .. import env as _env

        return bool(_env.get("MXTPU_DIAG_COMPILE"))
    except Exception:
        return os.environ.get("MXTPU_DIAG_COMPILE", "1") != "0"


_FUSION_BODY = re.compile(r" fusion\(.*?calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r'^\s+(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*?op_name="([^"]*)"')


def op_scopes(hlo_text):
    """{instruction name: op_name} of an optimized HLO module's text.

    The op_name is the JAX name stack the instruction was traced under
    (``jit(whole_step)/jvp(forward)/BottleneckV1_3/BatchNorm_bn2/mul``),
    which is how a device trace's ``fusion.20`` gets back to the block
    that emitted it.  A fusion carries the op_name of its root; the
    instructions INSIDE fusion bodies never show in a trace and are left
    out."""
    bodies = set(_FUSION_BODY.findall(hlo_text))
    out, skip = {}, False
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            if m:
                skip = m.group(1) in bodies
            continue
        if not skip:
            m = _INSTRUCTION.match(line)
            if m:
                out[m.group(1)] = m.group(2)
    return out


def capture_compile(block, variant, jitted, args, kwargs=None,
                    compile_seconds=None):
    """Record the cost/memory analysis of the executable ``jitted`` has
    for ``args`` under ``(block, variant)``. Never raises: introspection must
    not be able to fail a training step. Returns the entry dict or None
    (disabled / analysis unavailable on this backend)."""
    if not capture_enabled():
        return None
    try:
        # each step of the capture under its own span: whether the second
        # lowering and the second look at the cache cost anything is read
        # off these (docs/diagnostics.md, "The spans of start-up")
        with _spans.span("compile_capture.lower", cat="compile"):
            lowered = jitted.lower(*args, **(kwargs or {}))
        with _spans.span("compile_capture.compile", cat="compile"):
            compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        with _spans.span("compile_capture.text", cat="compile"):
            text = compiled.as_text()
        with _spans.span("compile_capture.op_scopes", cat="compile"):
            scopes = op_scopes(text)
        entry = {
            "block": str(block), "variant": str(variant),
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
            "transcendentals": float(
                cost.get("transcendentals", 0.0) or 0.0),
            "compile_seconds": compile_seconds,
            # Mosaic (Pallas) kernels in the optimized program: 0 on a
            # program that was meant to carry a kernel means it took the
            # XLA twin — the registry is where that shows
            "tpu_custom_calls": text.count(
                'custom_call_target="tpu_custom_call"'),
            # cross-device sums the partitioner / shard_map put in
            "all_reduces": (text.count(" all-reduce(")
                            + text.count(" all-reduce-start(")),
            # the map from a device trace's instruction names to the
            # scopes of the program (forward / backward / optimizer /
            # block), for whoever reduces a trace of this program
            "op_scopes": scopes,
        }
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        arg_b = out_b = tmp_b = gen_b = 0
        if mem is not None:
            arg_b = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
            out_b = int(getattr(mem, "output_size_in_bytes", 0) or 0)
            tmp_b = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
            gen_b = int(
                getattr(mem, "generated_code_size_in_bytes", 0) or 0)
            alias_b = int(
                getattr(mem, "alias_size_in_bytes", 0) or 0)
            entry.update({
                "argument_bytes": arg_b, "output_bytes": out_b,
                "temp_bytes": tmp_b, "generated_code_bytes": gen_b,
                # aliased buffers (donated args) are counted inside
                # argument_bytes AND output_bytes; subtract once
                "peak_hbm_bytes": max(
                    0, arg_b + out_b + tmp_b + gen_b - alias_b),
            })
        else:
            entry.update({"argument_bytes": 0, "output_bytes": 0,
                          "temp_bytes": 0, "generated_code_bytes": 0,
                          "peak_hbm_bytes": 0})
    except Exception:
        return None
    with _lock:
        _entries[(str(block), str(variant))] = entry
    _export_to_telemetry(entry)
    return entry


def _export_to_telemetry(entry):
    try:
        from .. import telemetry
        if not telemetry.REGISTRY.enabled:
            return
        labels = {"block": entry["block"], "variant": entry["variant"]}
        telemetry.instruments.compile_flops.labels(**labels).set(
            entry["flops"])
        telemetry.instruments.compile_peak_hbm_bytes.labels(**labels).set(
            entry["peak_hbm_bytes"])
    except Exception:
        pass


def compile_registry():
    """Snapshot: {(block, variant): entry dict}."""
    with _lock:
        return dict(_entries)


def reset():
    with _lock:
        _entries.clear()


def device_memory():
    """Live per-device memory stats: a list of {device, platform, stats}
    where stats is the ``memory_stats()`` dict (bytes_in_use,
    peak_bytes_in_use, bytes_limit, ... on TPU/GPU) or None when the
    backend doesn't report (CPU)."""
    import jax

    out = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        out.append({"device": str(d), "platform": d.platform,
                    "stats": stats})
    return out


def update_device_memory_gauge():
    """Push bytes_in_use per device onto the telemetry gauge; returns the
    number of devices that reported stats."""
    reported = 0
    try:
        from .. import telemetry
        if not telemetry.REGISTRY.enabled:
            return 0
        for dm in device_memory():
            stats = dm["stats"]
            if not stats:
                continue
            telemetry.instruments.device_memory_bytes.labels(
                device=dm["device"]).set(
                    float(stats.get("bytes_in_use", 0)))
            reported += 1
    except Exception:
        return reported
    return reported


def format_compile_table(registry=None):
    """Compile registry as a fixed-width text table (GFLOP / MB units)."""
    reg = compile_registry() if registry is None else registry
    lines = [f"{'block':<28}{'variant':<14}{'GFLOP':>10}{'MB acc':>10}"
             f"{'peak MB':>10}{'arg MB':>9}{'out MB':>9}{'tmp MB':>9}"]
    for (block, variant), e in sorted(reg.items()):
        lines.append(
            f"{block[:27]:<28}{variant[:13]:<14}"
            f"{e['flops'] / 1e9:>10.3f}"
            f"{e['bytes_accessed'] / 1e6:>10.2f}"
            f"{e['peak_hbm_bytes'] / 1e6:>10.2f}"
            f"{e['argument_bytes'] / 1e6:>9.2f}"
            f"{e['output_bytes'] / 1e6:>9.2f}"
            f"{e['temp_bytes'] / 1e6:>9.2f}")
    if len(lines) == 1:
        lines.append("  (no compiles captured"
                     + ("" if capture_enabled()
                        else " — MXTPU_DIAG_COMPILE=0") + ")")
    return "\n".join(lines)
