"""Kernels: the least time the chip could take for the grouped products
of the traced steps, forward and backward, on the rows that the plain
reference's own routing sends to the held experts of the sparse layers
(the driver's ``reference_held_rows``: the pool's batches at the seeded
weights, never the program's counter; kernel_counts_hybrid.expert_kernels
counts the sparse layers alone, experts 1,536 wide here), over the device
time of the ``ragged-dot*`` kernels.  A recomputed forward counts in the
time and not in the operations.  None off a TPU and on a configuration
of another kind (`kernel_counts_hybrid.applies`)."""
import flops
import kernel_counts
import kernel_counts_hybrid


def read(trace, run):
    rows = run.get("reference_held_rows")
    if (not rows or not run.get("traced_steps") or run["platform"] != "tpu"
            or not kernel_counts_hybrid.applies(run["cfg"])):
        return None
    seconds = kernel_counts.kernel_seconds(trace, name_prefix="ragged-dot")
    if not seconds:
        return None
    least = kernel_counts.roofline_seconds(
        *kernel_counts_hybrid.expert_kernels(run["cfg"], rows),
        flops.peaks(run["device_kind"]))
    return 100.0 * least * run["traced_steps"] / seconds
