"""Kernels: the least time the chip could take to move the bytes of the
short convolution's mix in the traced steps, forward and backward
(kernel_counts_hybrid.mix_bytes: the three streams read and the result
written, then the streams and the cotangent read and the streams'
cotangent written, 2 bytes an element, every convolution layer, against
peaks.json's memory bandwidth — the least any implementation must move),
over the device time of the operations under the ``short_conv.mix``
scope.  A recomputed forward counts in the time and not in the bytes.
None off a TPU, on a configuration with no convolution layer and on a
program without the scope."""
import flops
import kernel_counts_hybrid
import program_spans


def read(trace, run):
    cfg = run["cfg"]
    if (not run.get("traced_steps") or run["platform"] != "tpu"
            or "conv" not in cfg.get("layer_types", ())):
        return None
    seconds = program_spans.scope_seconds(
        trace, lambda s: "/short_conv.mix/" in s)
    if not seconds:
        return None
    least = (kernel_counts_hybrid.mix_bytes(cfg, run["batch"])
             / flops.peaks(run["device_kind"])["hbm_bytes_per_s"])
    return 100.0 * least * run["traced_steps"] / seconds
