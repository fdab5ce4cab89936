"""Compile: copies of the layer stack that the traced step of a looped
model holds — the program's gauge ``looped_stack_copies``, set on the
host while the step is traced (`model_zoo.decoder.run_looped`), so a
process that loads its step from the compile cache has it too.  1 says
the loop steps are one rolled loop: trace, lowering, the code on the
device and the cache entry are those of one pass; the number of loop
steps would say they were unrolled.  None on a program without the gauge,
or one whose step ran no looped stack."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "looped_stack_copies", None)
    return None if gauge is None else gauge.value or None
