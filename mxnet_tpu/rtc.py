"""Runtime kernel compilation (reference: python/mxnet/rtc.py CudaModule —
NVRTC-compiled CUDA kernels launched on NDArrays).

TPU translation: runtime-compiled device kernels are Pallas kernels.
`PallasModule` wraps a user kernel function and compiles it per
shape/dtype via `pl.pallas_call` — the CudaModule.get_kernel/launch shape
with a TPU-native body. `CudaModule` remains as a guard that explains the
mapping to users porting reference code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .ndarray.ndarray import NDArray

__all__ = ["CudaModule", "PallasModule"]


class CudaModule:
    """Reference-parity guard (reference: rtc.py:41). CUDA source cannot
    run on TPU; port the kernel body to Pallas and use PallasModule."""

    def __init__(self, source, options=(), exports=()):  # noqa: ARG002
        raise NotImplementedError(
            "CudaModule compiles CUDA C++ via NVRTC, which has no TPU "
            "counterpart. Port the kernel to a Pallas body and wrap it in "
            "mx.rtc.PallasModule (see mxnet_tpu/ops/pallas_attention.py "
            "for a production example).")


class PallasKernel:
    """One compiled kernel (the CudaKernel analog): `launch(args, grid,
    ...)` runs the Pallas body over NDArrays."""

    def __init__(self, body, name):
        self._body = body
        self.name = name
        self._compiled = {}

    def launch(self, args, out_shape, out_dtype="float32", grid=None,
               **pallas_kwargs):
        """Run the kernel. args: NDArrays/arrays; out_shape/out_dtype
        describe the output buffer (the reference passed explicit grid and
        block dims — `grid` maps directly; blocks are XLA's concern).

        Like the reference CudaKernel.launch, the launch is OUTSIDE
        autograd — raw kernels have no registered gradient. For a
        differentiable kernel, wrap the body in `jax.custom_vjp` and call
        it through `ndarray.apply_op` (see ops/pallas_attention.py).
        """
        from jax.experimental import pallas as pl

        key = (tuple(out_shape), str(out_dtype), grid)
        fn = self._compiled.get(key)
        if fn is None:
            if grid is not None:
                pallas_kwargs = dict(pallas_kwargs, grid=grid)
            # the kernel's own name in the HLO and in a device trace
            pallas_kwargs.setdefault("name", self.name)
            if "interpret" not in pallas_kwargs:
                # Mosaic lowering needs a TPU; elsewhere run the kernel in
                # interpret mode (numerics-identical, like
                # ops/pallas_attention.py)
                pallas_kwargs["interpret"] = \
                    jax.default_backend() != "tpu"
            call = pl.pallas_call(
                self._body,
                out_shape=jax.ShapeDtypeStruct(tuple(out_shape),
                                               jnp.dtype(out_dtype)),
                **pallas_kwargs,
            )
            fn = jax.jit(call)
            self._compiled[key] = fn
        datas = [a._data if isinstance(a, NDArray) else jnp.asarray(a)
                 for a in args]
        return NDArray(fn(*datas))


class PallasModule:
    """Collection of named Pallas kernel bodies (the CudaModule analog).

    Example:
        def add_one(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1.0
        mod = mx.rtc.PallasModule({"add_one": add_one})
        k = mod.get_kernel("add_one")
        y = k.launch([x], out_shape=x.shape)
    """

    def __init__(self, kernels):
        if callable(kernels):
            kernels = {kernels.__name__: kernels}
        self._kernels = dict(kernels)

    def get_kernel(self, name, signature=None):  # noqa: ARG002 - parity arg
        if name not in self._kernels:
            raise KeyError(f"no kernel {name!r}; have "
                           f"{sorted(self._kernels)}")
        return PallasKernel(self._kernels[name], name)
