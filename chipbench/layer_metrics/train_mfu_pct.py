"""Device: algorithmic FLOPs of the traced steps, forward and backward
(flops.py), over the device's time for them in the trace, from its first
operation to its last, against the published bf16 peak (peaks.json)."""
import flops


def read(trace, run):
    if not run.get("traced_steps") or run["platform"] != "tpu":
        return None
    done = flops.train_flops(run["cfg"]) * run["batch"] * run["traced_steps"]
    peak = flops.peaks(run["device_kind"])["bf16_flops"]
    return 100.0 * done / trace["window_s"] / peak
