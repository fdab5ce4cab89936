"""Kernels: the least time the chip could take for the flash kernels of
the traced steps over the layers that slide a window (the band's kept pairs, W (W + 1) / 2 +
(S - W) W a head), forward and backward
(kernel_counts_window.attention_kernels: the kept pairs' FLOPs and the
tensors' bytes, against peaks.json), over the device time of the kernels
under the ``attention.window`` scope.  Dead and padded tiles never count
in the operations, a recomputed forward counts in the time.  None off a
TPU, on a program without the scope and on a configuration that does not
choose its layers' masks one by one (`kernel_counts_window.applies`)."""
import kernel_counts_window


def read(trace, run):
    return kernel_counts_window.flash_roofline_pct(trace, run,
                                                   sliding=True)
