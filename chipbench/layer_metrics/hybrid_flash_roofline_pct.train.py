"""Kernels: the least time the chip could take for the flash kernels of
the traced steps of a stack in which only some layers attend, forward and
backward (kernel_counts_hybrid.attention_kernels: the causal pairs' FLOPs
at keys and values one head wide — 64 here, half a lane — over the
attention layers alone, and the tensors' bytes, against peaks.json), over
the device time of the kernels under the ``attention`` scope.  A
recomputed forward counts in the time and not in the operations.  None
off a TPU, and on a configuration that does not choose its layers'
operators one by one (`kernel_counts_hybrid.applies`)."""
import flops
import kernel_counts
import kernel_counts_hybrid


def read(trace, run):
    if (not run.get("traced_steps") or run["platform"] != "tpu"
            or not kernel_counts_hybrid.applies(run["cfg"])):
        return None
    seconds = kernel_counts.kernel_seconds(trace, scope_part="/attention/")
    if not seconds:
        return None
    least = kernel_counts.roofline_seconds(
        *kernel_counts_hybrid.attention_kernels(run["cfg"], run["batch"]),
        flops.peaks(run["device_kind"]))
    return 100.0 * least * run["traced_steps"] / seconds
