"""Entry points (gluon.TrainStep): Python scalars among the operands of
the whole-step call, each a host-to-device transfer inside every call --
the program's gauge ``step_scalar_operands``."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "step_scalar_operands", None)
    return gauge.value if gauge is not None and gauge.value else None
