"""Kernels: the least time the chip could take for the flash kernels of
the traced steps of a looped model, forward and backward, at every one of
its loop steps x layers applications
(kernel_counts_looped.attention_kernels: the causal pairs' FLOPs at keys
and values one head_dim wide, and the tensors' bytes, against peaks.json;
the FLOPs bound it), over the device time of the kernels under the
``attention`` scope.  A recomputed forward counts in the time and not in
the operations.  None off a TPU, and on a configuration that has no loop
steps."""
import flops
import kernel_counts
import kernel_counts_looped


def read(trace, run):
    if (not run.get("traced_steps") or run["platform"] != "tpu"
            or "total_ut_steps" not in run["cfg"]):
        return None
    seconds = kernel_counts.kernel_seconds(trace, scope_part="/attention/")
    if not seconds:
        return None
    least = kernel_counts.roofline_seconds(
        *kernel_counts_looped.attention_kernels(run["cfg"], run["batch"]),
        flops.peaks(run["device_kind"]))
    return 100.0 * least * run["traced_steps"] / seconds
