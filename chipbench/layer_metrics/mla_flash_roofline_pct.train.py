"""Kernels: the least time the chip could take for the flash kernels of
the traced steps at latent attention's two widths, forward and backward
(kernel_counts_mla.attention_kernels: the causal pairs' FLOPs, keys 192
and values 128 wide, and the tensors' bytes at their own widths, against
peaks.json; the FLOPs bound it), over the device time of the kernels
under the ``attention`` scope.  A recomputed forward counts in the time
and not in the operations."""
import flops
import kernel_counts
import kernel_counts_mla


def read(trace, run):
    if not run.get("traced_steps") or run["platform"] != "tpu":
        return None
    seconds = kernel_counts.kernel_seconds(trace, scope_part="/attention/")
    if not seconds:
        return None
    least = kernel_counts.roofline_seconds(
        *kernel_counts_mla.attention_kernels(run["cfg"], run["batch"]),
        flops.peaks(run["device_kind"]))
    return 100.0 * least * run["traced_steps"] / seconds
