"""Expert layers: rows of the buffers the window's last step worked on
over the rows routed to the held experts, summed over the layers — the
program's gauges ``moe_buffer_rows`` and ``moe_rows_routed_here``, set
from the device's counters when the driver asks for ``moe_load`` after
the window.  1 is a buffer with no dead row; a program that always works
on tokens x experts-per-token rows reads experts / held.  None on a
program without the gauge."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    buffers = getattr(ti, "moe_buffer_rows", None)
    if buffers is None:
        return None
    buffer_rows = sum(child.value for _layer, child in buffers.series())
    routed = sum(child.value
                 for _layer, child in ti.moe_rows_routed_here.series())
    return buffer_rows / routed if buffer_rows and routed else None
