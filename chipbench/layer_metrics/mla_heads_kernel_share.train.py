"""Kernels: of the call sites of ``npx.mla_heads`` in the traced program
of a latent-attention stack, the share that took the fused kernels, in per
cent — the program's gauge ``mla_heads_kernel_share``, set on the host
while the step is traced.  0 says every site took the composition of XLA
ops.  None on a program without the gauge and on a configuration without
latent attention (no ``kv_lora_rank``)."""


def read(trace, run):
    from mxnet_tpu.telemetry import instruments as ti

    gauge = getattr(ti, "mla_heads_kernel_share", None)
    if gauge is None or "kv_lora_rank" not in run["cfg"]:
        return None
    return 100.0 * gauge.value
