"""Perf lab: on-chip timing breakdown for the headline ResNet-50 bench.

Usage:  python tools/perf_lab.py [layout] [batch] [mode]
  mode: step (default) | fwd | fwdbwd | profile

Prints one JSON line with measured time/step, img/s, and the XLA
cost-analysis FLOPs of the timed computation so MFU is computed against
the same flop counting everywhere.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402
from mxnet_tpu import telemetry  # noqa: E402

from mxnet_tpu.telemetry.instruments import device_peaks  # noqa: E402


def _analyze(compiled):
    """(flops, peak_hbm_bytes) of an AOT-compiled executable."""
    cost = compiled.cost_analysis() or {}
    fl = float(cost.get("flops", 0.0) or 0.0)
    peak = 0
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        peak = (int(getattr(mem, "argument_size_in_bytes", 0) or 0)
                + int(getattr(mem, "output_size_in_bytes", 0) or 0)
                + int(getattr(mem, "temp_size_in_bytes", 0) or 0)
                + int(getattr(mem, "generated_code_size_in_bytes", 0) or 0)
                - int(getattr(mem, "alias_size_in_bytes", 0) or 0))
    return fl, max(0, peak)


def main():
    layout = sys.argv[1] if len(sys.argv) > 1 else "NHWC"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    mode = sys.argv[3] if len(sys.argv) > 3 else "step"
    iters, warmup = 20, 3
    # every line names the device it ran on; a device without a
    # published peak (the CPU included) has no MFU and fails here
    dev = jax.devices()[0]
    platform = dev.platform
    peak_bf16 = device_peaks(dev.device_kind)["bf16_flops"]

    net, step, params, momenta, x, y = bench.build_resnet_train(
        layout, batch, donate=(mode == "step"))
    key = jax.random.PRNGKey(7)

    if mode in ("fwd", "fwdbwd"):
        fwd, _ = net.as_pure_function(training=True)

        if mode == "fwd":
            @jax.jit
            def run(p, k, x):
                out, _ = fwd(p, k, x)
                return out.astype(jnp.float32).sum()
        else:
            def loss_fn(p, k, x, y):
                out, _ = fwd(p, k, x)
                logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
                return -jnp.take_along_axis(logp, y[:, None], -1).mean()

            @jax.jit
            def run(p, k, x):
                l, g = jax.value_and_grad(loss_fn)(p, k, x, y)
                return l + sum(jnp.sum(v.astype(jnp.float32) ** 2)
                               for v in g.values())

        compiled = run.lower(params, key, x).compile()
        fl, peak_hbm = _analyze(compiled)

        def one():
            return compiled(params, key, x)

        dt, _ = bench._timeit(one, lambda o: float(o), iters, warmup)
    elif mode == "profile":
        state = {"p": params, "m": momenta}

        def one():
            state["p"], state["m"], loss = step(state["p"], state["m"],
                                                x, y, key)
            return loss

        for _ in range(3):
            out = one()
        float(out)
        trace_dir = os.environ.get("MXTPU_PERFLAB_TRACE_DIR",
                                   "/tmp/xplane")
        with jax.profiler.trace(trace_dir):
            for _ in range(10):
                out = one()
            float(out)
        print(json.dumps({"profile": trace_dir, "platform": platform}))
        return
    else:
        compiled = step.lower(params, momenta, x, y, key).compile()
        fl, peak_hbm = _analyze(compiled)
        state = {"p": params, "m": momenta}

        def one():
            state["p"], state["m"], loss = compiled(state["p"], state["m"],
                                                    x, y, key)
            return loss

        dt, _ = bench._timeit(one, lambda o: float(o), iters, warmup)

    step_ms = dt / iters * 1e3
    # MFU comes FROM the telemetry gauge, not a local recomputation: the
    # measured XLA flop count is declared as the per-step budget and the
    # measured step time observed, so every consumer (this JSON line,
    # prometheus_text scrapes, bench snapshots) reads the same number
    # (docs/telemetry.md).
    telemetry.set_flop_budget(fl, peak=peak_bf16)
    telemetry.observe_step(dt / iters, examples=batch)
    mfu = (telemetry.instruments.mfu_ratio.value if telemetry.enabled()
           else fl / (dt / iters) / peak_bf16)  # MXTPU_TELEMETRY=0 runs
    print(json.dumps({
        "mode": mode, "layout": layout, "batch": batch,
        "platform": platform, "device_kind": dev.device_kind,
        "step_ms": round(step_ms, 2),
        "img_s": round(batch * iters / dt, 1),
        "xla_gflops_per_step": round(fl / 1e9, 2),
        "peak_hbm_mb": round(peak_hbm / 1e6, 2),
        "mfu": round(mfu, 4), "peak_bf16_flops": peak_bf16,
    }))


if __name__ == "__main__":
    main()
