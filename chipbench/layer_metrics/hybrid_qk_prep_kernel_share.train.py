"""Kernels: of the call sites of ``npx.rms_norm_rotary`` in the traced
program of a stack whose attention heads are narrower than a lane, the
share that took the fused kernels, in per cent — the program's gauge
``qk_prep_kernel_share``, set on the host while the step is traced.  0
says every site took the composition of XLA ops (64-wide heads: two heads
a lane block, which the kernels do not tile yet); the witness a later
change to the kernels moves.  None on a program without the gauge and on
a configuration of another kind (`kernel_counts_hybrid.applies`)."""
import kernel_counts_hybrid


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "qk_prep_kernel_share", None)
    if gauge is None or not kernel_counts_hybrid.applies(run["cfg"]):
        return None
    return 100.0 * gauge.value
