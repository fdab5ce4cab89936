"""Measure NCHW vs NHWC conv training-step throughput on the real chip.

Decides the default layout for the TPU conv path (VERDICT r1 #1). Each case
is a representative ResNet-50 conv (fwd+bwd, bf16, b=128) in both layouts.
The repeat loop lives INSIDE the jit (lax.fori_loop with grad feedback) so
per-call dispatch overhead doesn't mask device time.
"""
import time

import jax
import jax.numpy as jnp
from jax import lax

B = 128
INNER = 30
CASES = [
    # (name, H, Cin, Cout, k, stride)
    ("stem7x7", 224, 3, 64, 7, 2),
    ("b1_3x3", 56, 64, 64, 3, 1),
    ("b3_1x1", 28, 256, 512, 1, 2),
    ("b4_3x3", 14, 512, 512, 3, 1),
]


def flops(h, cin, cout, k, s):
    ho = h // s
    return 3 * 2 * B * ho * ho * cout * cin * k * k  # fwd + 2 bwd passes


def run(layout):
    results = {}
    for name, h, cin, cout, k, s in CASES:
        if layout == "NCHW":
            xshape = (B, cin, h, h)
            dn = ("NCHW", "OIHW", "NCHW")
            wshape = (cout, cin, k, k)
        else:
            xshape = (B, h, h, cin)
            dn = ("NHWC", "HWIO", "NHWC")
            wshape = (k, k, cin, cout)
        x = jax.random.normal(jax.random.PRNGKey(0), xshape, jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), wshape, jnp.bfloat16) * 0.01

        def fwd(x, w):
            y = lax.conv_general_dilated(
                x, w, (s, s), [(k // 2, k // 2)] * 2,
                dimension_numbers=lax.conv_dimension_numbers(
                    xshape, wshape, dn))
            return jnp.sum(y.astype(jnp.float32))

        grad = jax.grad(fwd, argnums=(0, 1))

        @jax.jit
        def many(x, w):
            def body(_, xw):
                x, w = xw
                gx, gw = grad(x, w)
                # feed grads back so no iteration can be DCE'd
                return (x + 1e-6 * gx.astype(x.dtype),
                        w + 1e-6 * gw.astype(w.dtype))
            return lax.fori_loop(0, INNER, body, (x, w))

        xo, wo = many(x, w)
        float(jnp.sum(wo.astype(jnp.float32)))  # warm + sync
        t0 = time.perf_counter()
        xo, wo = many(x, w)
        float(jnp.sum(wo.astype(jnp.float32)))
        dt = (time.perf_counter() - t0) / INNER
        tf = flops(h, cin, cout, k, s) / dt / 1e12
        results[name] = dt * 1e3
        print(f"{layout} {name}: {dt*1e3:.3f} ms/step  {tf:.1f} TFLOP/s")
    return results


if __name__ == "__main__":
    print("platform:", jax.devices()[0].platform)
    r1 = run("NCHW")
    r2 = run("NHWC")
    for name in r1:
        print(f"{name}: NCHW {r1[name]:.3f}ms  NHWC {r2[name]:.3f}ms  "
              f"speedup {r1[name]/r2[name]:.2f}x")
