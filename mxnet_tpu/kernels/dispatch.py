"""Kernel dispatch policy: who decides, and how the decision is audited.

The bandwidth kernels (norm.py, opt.py) never rewrite a traced program —
each eligible CALL SITE (ops/nn.py batch_norm's training branch, the
optimizer's `_fused_step_body` loop) consults this module at trace time
and emits either the Pallas kernel or the existing XLA path into the
program being captured.  That keeps the kill switch trivial and exact:
``MXTPU_KERNELS`` unset/off means no site even looks here, so the
captured programs are bitwise-identical to main with zero extra traces.

The decision ladder (docs/kernels.md has the full table):

  off    site never consulted — the XLA path verbatim;
  force  kernel whenever platform + shape/dtype/rule support allows;
  auto   additionally require the passes/memory.py analytic byte model
         to predict an external-HBM saving — the decision is the byte
         model's, not a hardcode: sites where the model finds no
         widening/reduce root to kill (pure-f32 optimizer chains, tiny
         tensors) keep the XLA path with outcome 'no_savings' /
         'too_small'.

Every consult records ONE `kernel_dispatch_total{kernel,outcome}`
sample per trace (never per step); fallbacks also drop a
``kernel_fallback`` flight-recorder event so postmortems show which
path a program compiled with.
"""
from __future__ import annotations

import jax

from .. import env as _env
from ..telemetry import instruments as _telemetry

__all__ = [
    "mode", "platform_ok", "interpret_requested", "record",
    "auto_accepts", "pallas_call", "MIN_AUTO_BYTES", "MIN_AUTO_SAVINGS",
]

# auto mode declines sites below this size — kernel launch overhead and
# tiny-region bookkeeping swamp any bandwidth win
MIN_AUTO_BYTES = 1 << 20
# and sites where the model predicts less than this fractional saving
MIN_AUTO_SAVINGS = 0.15

_MODES = {
    "": "off", "0": "off", "off": "off", "false": "off", "no": "off",
    "none": "off",
    "1": "auto", "auto": "auto", "on": "auto", "true": "auto",
    "yes": "auto",
    "force": "force", "always": "force",
}


def mode():
    """Resolved MXTPU_KERNELS mode: 'off' | 'auto' | 'force'."""
    raw = str(_env.get("MXTPU_KERNELS")).strip().lower()
    try:
        return _MODES[raw]
    except KeyError:
        raise ValueError(
            f"MXTPU_KERNELS={raw!r} is not a recognized mode; expected "
            f"off | auto | force") from None


def interpret_requested():
    """MXTPU_KERNELS_INTERPRET: run kernels in Pallas interpret mode so
    they execute off-TPU (parity tests)."""
    return bool(_env.get("MXTPU_KERNELS_INTERPRET"))


def platform_ok():
    """True when Pallas kernels can actually execute here: a TPU
    backend, or interpret mode was requested explicitly."""
    return interpret_requested() or jax.devices()[0].platform == "tpu"


def pallas_call(kernel, *, name, **kwargs):
    """`pl.pallas_call` under a stable `name` (what a device trace and
    the HLO show instead of `jvp__.N`), whose kernel body and BlockSpec
    index maps trace with x64 off.  The package turns `jax_enable_x64`
    on for user arrays (the 64-bit dtype contract); under it a Python
    `0` in an index map is an i64, which Mosaic refuses to legalize.
    Kernel operands are bf16/f32/int32, so nothing 64-bit crosses this
    boundary."""
    import jax.experimental.pallas as pl

    def call(*operands):
        with jax.enable_x64(False):
            return pl.pallas_call(kernel, name=name, **kwargs)(*operands)

    return call


def auto_accepts(xla_bytes, kernel_bytes):
    """The `auto` decision on one site, given the analytic byte model's
    (xla, kernel) external-bytes estimates.  Returns (ok, reason,
    bytes_saved): reason is 'kernel' on accept, else the fallback
    outcome name."""
    saved = int(xla_bytes) - int(kernel_bytes)
    if xla_bytes < MIN_AUTO_BYTES:
        return False, "too_small", 0
    if xla_bytes <= 0 or saved <= 0 \
            or saved < MIN_AUTO_SAVINGS * xla_bytes:
        return False, "no_savings", 0
    return True, "kernel", saved


def record(kernel, outcome, bytes_saved=0, xla_bytes=None,
           kernel_bytes=None):
    """Record one trace-time decision (telemetry + flight recorder);
    guarded — a broken observability layer must not fail a trace.
    Sites that reached the byte model also pass their (xla, kernel)
    analytic scores so the measurement plane can audit the prediction
    against measured wall time (observability/measure.note_site)."""
    try:
        _telemetry.record_kernel_dispatch(kernel, outcome, bytes_saved)
    except Exception:
        pass
    if xla_bytes is not None or kernel_bytes is not None:
        try:
            from ..observability import measure as _measure

            _measure.note_site(kernel, outcome, xla_bytes=xla_bytes,
                               kernel_bytes=kernel_bytes,
                               bytes_saved=bytes_saved)
        except Exception:
            pass
