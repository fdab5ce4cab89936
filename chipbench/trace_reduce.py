"""From a profiler trace to numbers: device busy and idle time, time per
operation name, and the longest idle gaps with what the host was doing.

``load`` reads an ``.xplane.pb`` with nothing but JAX; ``reduce`` works on
plain tuples (name, start_ns, duration_ns), so it can be checked by hand
on a small recorded trace (tests/trace_small.json).
"""
import glob
import os

NOTE_PREFIX = "chipbench:"
KERNEL_MARK = "tpu_custom_call"     # a Pallas kernel's HLO line names it
_SKIP = ("ThreadpoolListener", "end: ")


def short_name(name):
    """An XLA operation's event is named by its whole HLO line
    ("%fusion.4 = bf16[...] fusion(...)"): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")[:64]


def newest_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, platform="tpu"):
    """{"devices": {plane: [(name, start_ns, dur_ns)]}, "notes": [...]}.

    A device's operations are the events of the line "XLA Ops" of each
    plane "/device:TPU:<n>".  On the CPU (toy rehearsals only) the XLA
    client's host threads stand in for one device.  Notes are the
    benchmark's own TraceAnnotations (names starting "chipbench:") on
    the host planes, which the profiler puts on the devices' clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, notes, layout, kernels = {}, [], [], set()
    for plane in pd.planes:
        for line in plane.lines:
            layout.append((plane.name, line.name))
            is_dev = (plane.name.startswith("/device:TPU:")
                      and line.name == "XLA Ops")
            is_cpu_dev = (platform == "cpu"
                          and line.name.startswith("tf_XLAPjRtCpuClient"))
            is_host = plane.name.startswith("/host:")
            if not (is_dev or is_cpu_dev or is_host):
                continue
            for ev in line.events:
                item = (short_name(ev.name), int(ev.start_ns),
                        int(ev.duration_ns))
                if is_dev:
                    devices.setdefault(plane.name, []).append(item)
                    if KERNEL_MARK in ev.name:
                        kernels.add(item[0])
                elif is_cpu_dev and not ev.name.startswith(_SKIP):
                    devices.setdefault("cpu-as-device", []).append(item)
                if is_host and ev.name.startswith(NOTE_PREFIX):
                    notes.append(item)
    return {"devices": devices, "notes": notes, "layout": layout,
            "kernels": sorted(kernels)}


def union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _attribute(gap, notes):
    """Name of the note overlapping ``gap`` most, innermost on a tie."""
    best, best_key = "unattributed", (0, 0)
    for name, s, d in notes:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > 0 and (ov, -d) > best_key:
            best, best_key = name[len(NOTE_PREFIX):], (ov, -d)
    return best


def reduce(devices, notes=(), top=10, kernels=()):
    """Busy and idle time of the traced window.

    The window of a device runs from the start of its first operation
    to the end of its last one; busy is the union of its operations'
    intervals (nested operations count once).  Returns busy_s and
    window_s averaged over the devices, the ``top`` operation names by
    summed duration, and the ``top`` idle gaps of the first device as
    [what the host was doing, seconds], summed by name.  ``op_s`` is the
    time of every operation name, for readers of single kernels, and
    ``kernels`` the names among them that are Pallas calls."""
    if not devices:
        return None
    busy, window, per_op, gap_sum = [], [], {}, {}
    for k, (_plane, evs) in enumerate(sorted(devices.items())):
        merged = union((s, s + d) for _n, s, d in evs)
        if not merged:
            continue
        busy.append(sum(e - s for s, e in merged) / 1e9)
        window.append((merged[-1][1] - merged[0][0]) / 1e9)
        for name, _s, d in evs:
            per_op[name] = per_op.get(name, 0.0) + d / 1e9
        if k == 0:
            for a, b in zip(merged, merged[1:]):
                what = _attribute((a[1], b[0]), notes)
                gap_sum[what] = gap_sum.get(what, 0.0) + (b[0] - a[1]) / 1e9
    if not busy:
        return None
    n = len(busy)
    rank = lambda d: [[k, v] for k, v in sorted(        # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    op_s = {k: v / n for k, v in per_op.items()}
    return {"busy_s": sum(busy) / n, "window_s": sum(window) / n,
            "op_s": op_s, "kernels": [k for k in kernels if k in op_s],
            "device_ops": rank(op_s), "idle_gaps": rank(gap_sum)}
