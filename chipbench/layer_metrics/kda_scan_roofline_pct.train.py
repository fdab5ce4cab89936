"""Kernels: the least time the chip could take for the delta rule's scans
of the traced steps, forward and backward
(kernel_counts_kda.scan_kernels: the recurrence's own 6 d_k d_v FLOPs a
token a head, twice that back, and the operands and gradients moved once
— a floor no chunking can go under; against peaks.json, and the bytes
bound it), over the device time of the Pallas calls under the
``kda.scan`` scope.  A recomputed forward counts in the time and not in
the operations.  None off a TPU, on a configuration without delta layers
and on a program whose scan runs no kernel."""
import kernel_counts_kda


def read(trace, run):
    return kernel_counts_kda.scan_roofline_pct(trace, run)
