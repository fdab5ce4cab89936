"""Kernels: the least time the chip could take for the flash kernels of
the traced steps over the latent-attention layers that carry no
positions, forward and backward (kernel_counts_mla.attention_kernels on
the latent layers alone: the causal pairs' FLOPs, keys 192 and values 128
wide, and the tensors' bytes, against peaks.json; the FLOPs bound it),
over the device time of the Pallas calls under ``attention`` inside
``mla``.  A recomputed forward counts in the time and not in the
operations.  None off a TPU, on a configuration of another kind and on a
program without the scope."""
import kernel_counts_kda


def read(trace, run):
    return kernel_counts_kda.nope_flash_roofline_pct(trace, run)
