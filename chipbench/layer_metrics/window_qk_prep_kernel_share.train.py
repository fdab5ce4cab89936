"""Kernels: of the call sites of ``npx.rms_norm_rotary`` in the traced
program of a stack whose layers differ in mask, the share that took the
fused kernels, in per cent — the program's gauge ``qk_prep_kernel_share``,
set on the host while the step is traced.  Two sites a layer (queries,
keys); a global layer's carry no positions (``positions=None``: the norm
and the move alone, the rotation compiled out of the same kernels) and
count like the others.  None on a program without the gauge and on a
configuration of another kind (`kernel_counts_window.applies`)."""
import kernel_counts_window


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "qk_prep_kernel_share", None)
    if gauge is None or not kernel_counts_window.applies(run["cfg"]):
        return None
    return 100.0 * gauge.value
