"""Cross-backend numeric check bodies for test_consistency_tpu.py.

Run as a SCRIPT in a child process without the CPU pin
(no JAX_PLATFORMS=cpu), so `tpu(0)` resolves to the actual
chip and `cpu(0)` to the host — the reference's CPU<->GPU comparison
harness (test_utils.check_consistency, mirrored from
tests/python/gpu/test_operator_gpu.py) compares genuinely different
backends. Inside the pytest process the conftest pins jax to CPU for
hermeticity, which would silently alias both devices to the host; that
is exactly the failure mode this layout avoids.

Prints one JSON object: {"platform": ..., "<check>": "ok" | "FAIL: ..."}.
"""
import json
import sys

import numpy as onp


def _checks():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import numpy_extension as npx
    from mxnet_tpu import test_utils
    from mxnet_tpu.device import cpu, tpu

    def matmul():
        rs = onp.random.RandomState(0)
        a = rs.rand(32, 64).astype("float32")
        b = rs.rand(64, 16).astype("float32")
        test_utils.check_consistency(
            lambda x, y: mx.np.matmul(x, y), [a, b],
            devices=[cpu(0), tpu(0)], rtol=1e-4, atol=1e-4)

    def conv_bn_relu():
        rs = onp.random.RandomState(1)
        x = rs.rand(2, 8, 16, 16).astype("float32")
        w = rs.rand(4, 8, 3, 3).astype("float32")

        def f(xd, wd):
            y = npx.convolution(xd, wd, stride=(1, 1), pad=(1, 1))
            return npx.activation(y, "relu")

        test_utils.check_consistency(f, [x, w], devices=[cpu(0), tpu(0)],
                                     rtol=1e-3, atol=1e-3)

    def softmax_reduce():
        rs = onp.random.RandomState(2)
        x = rs.rand(8, 100).astype("float32") * 10

        def f(xd):
            return npx.softmax(xd, axis=-1).sum(axis=0)

        test_utils.check_consistency(f, [x], devices=[cpu(0), tpu(0)],
                                     rtol=1e-4, atol=1e-5)

    def bf16_matmul_tolerance():
        # bf16-on-TPU vs f32-on-CPU within bf16 tolerance (the dtype
        # dimension of the reference oracle).
        rs = onp.random.RandomState(3)
        a = rs.rand(16, 32).astype("float32")
        b = rs.rand(32, 8).astype("float32")
        ref = a @ b
        xa = mx.np.array(a, device=tpu(0)).astype("bfloat16")
        xb = mx.np.array(b, device=tpu(0)).astype("bfloat16")
        got = mx.np.matmul(xa, xb).astype("float32").asnumpy()
        onp.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)

    return {
        "platform": jax.devices()[0].platform,
        "devices_distinct": (
            tpu(0).jax_device.platform != cpu(0).jax_device.platform),
        "checks": {
            "matmul": matmul,
            "conv_bn_relu": conv_bn_relu,
            "softmax_reduce": softmax_reduce,
            "bf16_matmul_tolerance": bf16_matmul_tolerance,
        },
    }


def main():
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        # nothing to compare against; the pytest side skips on this
        print(json.dumps({"platform": platform}))
        return 0
    info = _checks()
    results = {"platform": info["platform"],
               "devices_distinct": info["devices_distinct"]}
    for name, fn in info["checks"].items():
        try:
            fn()
            results[name] = "ok"
        except Exception as e:  # report every check; pytest side asserts
            results[name] = f"FAIL: {type(e).__name__}: {e}"
    print(json.dumps(results))


if __name__ == "__main__":
    sys.exit(main())
