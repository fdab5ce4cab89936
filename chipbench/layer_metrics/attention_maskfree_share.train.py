"""Kernels: of the sub-tiles of scores the flash kernels' span schedules
visit, the share that takes the mask-free body (every pair kept: no codes
read, no compare, no select), in per cent, averaged over the three
kernels — the program's gauge ``attention_maskfree_share{kernel}``, set
on the host when the plan of a signature is built, which is while the
step is traced, so a process that loads its step from the compile cache
has it too.  100 is a call with no mask, 0 a schedule with no whole
sub-tile.  None on a program without the gauge, or one whose step built
no plan."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "attention_maskfree_share", None)
    if gauge is None:
        return None
    shares = [child.value for _kernel, child in gauge.series()]
    return 100.0 * sum(shares) / len(shares) if shares else None
