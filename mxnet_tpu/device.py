"""Device / Context abstraction.

Re-design of the reference's `python/mxnet/device.py` (Context/Device) for TPU:
`mx.tpu(i)` resolves to a PJRT TPU device; `mx.cpu()` to host. The reference's
`mx.gpu(i)` is kept as an alias for "the i-th accelerator" so models written
against the MXNet API keep running.

Device placement semantics: creation ops honor the *current device* (a
thread-local stack, entered with `with mx.Device('tpu', 0):` exactly like the
reference's `with mx.Context(...)`). Compute follows its inputs (XLA runs the op
where the operands live), matching the reference's "ops run on the context of
their inputs" rule (src/imperative/imperative_utils.h GetContext).
"""
from __future__ import annotations

import contextlib
import threading
import warnings

import jax

from .base import MXNetError

__all__ = ["Device", "Context", "cpu", "gpu", "tpu", "cpu_pinned", "num_gpus",
           "num_tpus", "current_device", "default_device"]

_DEVTYPE_ALIASES = {
    "cpu_pinned": "cpu",
    "cpu_shared": "cpu",
}

# Accelerator device types: all resolve to the TPU. 'gpu'/'cuda' are accepted
# for reference-API compatibility (models written `ctx=mx.gpu(0)`).
_ACCEL_TYPES = ("tpu", "gpu", "cuda")


_backend_taken = False


def _taking_backend():
    """The span ``startup.backend`` around the process's FIRST device
    resolution, where JAX takes its client (seconds on a TPU; ~0 where the
    caller asked JAX for its devices before); nothing afterwards."""
    global _backend_taken
    if _backend_taken:
        return contextlib.nullcontext()
    _backend_taken = True
    from .diagnostics import spans

    return spans.span("startup.backend", cat="startup")


class Device:
    """A device descriptor, hashable and comparable.

    Also usable as a context manager to set the default creation device,
    mirroring `with mx.Context(...)` in the reference
    (python/mxnet/device.py:Device.__enter__).
    """

    _tls = threading.local()
    _warned_fallback = set()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Device):
            device_id = device_type.device_id
            device_type = device_type.device_type
        device_type = _DEVTYPE_ALIASES.get(device_type, device_type)
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Device)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- resolution to a PJRT device -------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax (PJRT) device.

        An accelerator type resolves to a TPU or raises: a training run
        that asked for the chip must not continue on the host.  The one
        exception is a process explicitly pinned to the CPU
        (``JAX_PLATFORMS=cpu``, as the test suite is), where code written
        for `mx.tpu(i)` runs on the CPU devices, with one warning.
        """
        # NB: local_devices, not jax.devices() — under jax.distributed the
        # global list spans all processes and devices of other ranks are
        # non-addressable; mx.cpu(0)/mx.tpu(0) always mean THIS process's
        # devices (the reference's per-worker ctx semantics).
        dt = self.device_type
        with _taking_backend():
            if dt not in _ACCEL_TYPES:
                devs = jax.local_devices(backend=dt)
            elif jax.config.jax_platforms == "cpu":
                if dt not in Device._warned_fallback:
                    Device._warned_fallback.add(dt)
                    warnings.warn(
                        f"process is pinned to the CPU (JAX_PLATFORMS=cpu); "
                        f"device type '{dt}' resolves to CPU devices",
                        stacklevel=2,
                    )
                devs = jax.local_devices()
            else:
                try:
                    devs = jax.local_devices(backend="tpu")
                except RuntimeError as e:
                    raise MXNetError(
                        f"{self!r} asked for a TPU and none is available: "
                        f"{e}") from e
        return devs[self.device_id % len(devs)]

    # -- default-device stack --------------------------------------------
    def __enter__(self):
        stack = getattr(Device._tls, "stack", None)
        if stack is None:
            stack = Device._tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        Device._tls.stack.pop()
        return False


# The reference calls this class Context in 1.x and Device in 2.x; keep both.
Context = Device


def cpu(device_id=0):
    """Return a CPU device."""
    return Device("cpu", device_id)


def cpu_pinned(device_id=0):
    """Pinned host memory context (parity alias; host memory on TPU hosts)."""
    return Device("cpu", device_id)


def tpu(device_id=0):
    """Return the i-th TPU device — the native accelerator context."""
    return Device("tpu", device_id)


def gpu(device_id=0):
    """Reference-compat alias: the i-th accelerator (TPU here)."""
    return Device("gpu", device_id)


def _accel_count():
    with _taking_backend():
        try:
            return len(jax.devices("tpu"))
        except RuntimeError:
            return 0


def num_gpus():
    """Number of accelerator devices (reference: mx.device.num_gpus)."""
    return _accel_count()


def num_tpus():
    """Number of TPU devices visible to this process."""
    return _accel_count()


def current_device():
    """The device new arrays are created on (innermost `with device:` scope)."""
    stack = getattr(Device._tls, "stack", None)
    if stack:
        return stack[-1]
    return default_device()


_default = None


def default_device():
    """Process default: the first accelerator if present, else cpu."""
    global _default
    if _default is None:
        with _taking_backend():
            backend = jax.default_backend()
        _default = Device("tpu" if backend == "tpu" else "cpu", 0)
    return _default


def from_jax_device(d):
    """Map a concrete jax device back to a Device descriptor."""
    if d.platform == "tpu":
        return Device("tpu", d.id)
    return Device("cpu", d.id)
