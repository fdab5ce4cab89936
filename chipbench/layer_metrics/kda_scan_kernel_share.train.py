"""Kernels: of the call sites of ``npx.kda_scan`` in the traced program,
the share that took the Pallas kernels, in per cent — the program's gauge
``kda_scan_calls{path}``, set on the host while the step is traced.  0
says every site ran the composition of XLA ops.  None on a program
without the gauge and on a configuration without delta layers."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "kda_scan_calls", None)
    if gauge is None or "linear_attn_config" not in run["cfg"]:
        return None
    calls = {k[0]: g.value for k, g in gauge.series()}
    total = sum(calls.values())
    return 100.0 * calls.get("kernel", 0.0) / total if total else None
