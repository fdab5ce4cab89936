"""Kernels: of the `flash_attention` signatures the traced program planned,
the share whose backward is the one fused kernel (dQ, dK and dV from one
pass over the scores, a head's keys, values and their float32 gradients
resident in the core's fast memory), in per cent — the program's gauge
``attention_fused_backward_share``, set on the host when the plan of a
signature is built, which is while the step is traced, so a process that
loads its step from the compile cache has it too.  100 is every signature
fused, 0 every signature on the dQ and the dK/dV kernel.  None on a
program without the gauge, or one whose step built no plan."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "attention_fused_backward_share", None)
    # a plan sets this gauge and ``attention_maskfree_share{kernel}``
    # together: no series there, no plan yet (an unset gauge reads 0)
    if gauge is None or not ti.attention_maskfree_share.series():
        return None
    return 100.0 * gauge.value
