"""Kernels: of the call sites of ``npx.rms_norm_rotary`` in the traced
program, the share that took the fused kernels, in per cent — the
program's gauge ``qk_prep_kernel_share``, set on the host while the step
is traced, so a process that loads its step from the compile cache has it
too.  100 is every site on the kernels, 0 every site on the composition
of XLA ops.  None on a program without the gauge."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "qk_prep_kernel_share", None)
    return None if gauge is None else 100.0 * gauge.value
