"""Fused BatchNorm-training Pallas kernels: the r5 audit's two worst
regions (BN-statistics forward, BN backward) as hand-fused TPU kernels.

The XLA path (ops/nn.py `_bn_train`) is already a custom-VJP two-pass
design, but XLA materializes the f32-centered population at the
sum/sum² reduce boundary — the audit's single largest source of f32 HBM
traffic.  These kernels keep the statistics in VMEM scratch instead:

  forward   grid (2, M/bm): phase 0 streams x blocks once, accumulating
            Σ(x−shift) and Σ(x−shift)² per channel in f32 scratch;
            phase 1 streams x again, computes mean/var/inv from the
            finished sums and writes the normalized output — two HBM
            reads of x, one write of out, nothing else big.
  backward  same two-phase shape for dbeta/dgamma then dx.

The math mirrors `_bn_train_impl` / `_bn_train_bwd` line for line (same
shifted-variance form, same MXTPU_BN_COMPUTE elementwise dtype, f32
accumulators) — parity is allclose, not bitwise, only because the
blocked reduction order differs from XLA's.

`bn_train` is the drop-in custom_vjp twin of `_bn_train`: same
signature, same residuals, same (dx, dgamma, dbeta, 0·shift) cotangent
contract.  Unsupported shape/dtype (C not 64 or a multiple of 128, rows % 8,
non-float) falls back to
the exact XLA implementation inside the same wrapper, recording the
outcome via kernels.dispatch; a channel-axis-not-last site that would
otherwise qualify records "channels_first" — the LayoutPass
(MXTPU_LAYOUT) exists to turn those into kernel hits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import dispatch as _dispatch

__all__ = ["bn_train"]


def _nn():
    from ..ops import nn
    return nn


def _block_rows(m, c):
    """Largest power-of-two row-block dividing m that keeps one (bm, C)
    block (plus its f32 working copies) comfortably inside VMEM."""
    cap = max(8, (1 << 21) // max(1, 4 * c))
    for cand in (1024, 512, 256, 128, 64, 32, 16, 8):
        if cand <= cap and m % cand == 0:
            return cand
    return 8


def _lanes_ok(c):
    """Channel counts the (bm, C) blocks tile on: whole 128-lane vregs,
    or ResNet's 64 (a half-filled lane dim the compiler accepts because
    the block spans the array's full last dim)."""
    return c > 0 and (c % 128 == 0 or c == 64)


def _supported(x, axis):
    """None when the kernel pair can run on this site, else the fallback
    outcome name (the docs/kernels.md fallback table).

    "channels_first" singles out the sites where ONLY the layout — not
    the size or dtype — blocks the kernel: the same tensor with its
    channel axis moved last would qualify.  These are exactly the sites
    the LayoutPass (MXTPU_LAYOUT, passes/layout.py) converts, so the
    fusion-audit coverage numbers distinguish "needs NHWC" from
    "genuinely unkernelable"."""
    if x.ndim < 2:
        return "unsupported_shape"
    if axis != x.ndim - 1:
        c = x.shape[axis] if 0 <= axis < x.ndim else 0
        m = x.size // c if c else 0
        if (_lanes_ok(c) and c <= 8192 and m >= 8 and m % 8 == 0
                and x.dtype in (jnp.float32, jnp.bfloat16)):
            return "channels_first"
        return "unsupported_shape"
    c = x.shape[-1]
    m = x.size // c if c else 0
    if not _lanes_ok(c) or c > 8192 or m < 8 or m % 8:
        return "unsupported_shape"
    if x.dtype not in (jnp.float32, jnp.bfloat16):
        return "unsupported_dtype"
    return None


def _decide(x, axis):
    """(use_kernel, outcome, bytes_saved, xla_bytes, kernel_bytes) for
    one BN training site; the byte scores are None when the ladder
    exits before reaching the analytic model. Records nothing — callers
    record under their kernel name."""
    mode = _dispatch.mode()
    if mode == "off":
        return False, "off", 0, None, None
    reason = _supported(x, axis)
    if reason is not None:
        return False, reason, 0, None, None
    if not _dispatch.platform_ok():
        return False, "platform", 0, None, None
    from ..passes import memory as _memory
    ew = _nn()._bn_ew_dtype(x)
    xla_b, k_b = _memory.norm_region_bytes(x.shape, x.dtype, ew)
    if mode == "force":
        return True, "kernel", max(0, xla_b - k_b), xla_b, k_b
    ok, outcome, saved = _dispatch.auto_accepts(xla_b, k_b)
    return ok, outcome, saved, xla_b, k_b


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, gamma_ref, beta_ref, shift_ref,
                out_ref, mean_ref, var_ref, inv_ref, s1_ref, s2_ref, *,
                ew, n, eps):
    import jax.experimental.pallas as pl

    phase = pl.program_id(0)
    m_idx = pl.program_id(1)

    @pl.when((phase == 0) & (m_idx == 0))
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    sh = shift_ref[...]                       # (1, C) f32
    s_ew = sh.astype(ew)
    xf = x_ref[...].astype(ew) - s_ew         # (bm, C)

    @pl.when(phase == 0)
    def _accumulate():
        xf32 = xf.astype(jnp.float32)
        s1_ref[...] += jnp.sum(xf, axis=0, keepdims=True,
                               dtype=jnp.float32)
        s2_ref[...] += jnp.sum(xf32 * xf32, axis=0, keepdims=True,
                               dtype=jnp.float32)
        # phase 0 visits every out block before phase 1 rewrites it;
        # write zeros so the buffer never round-trips undefined bytes
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(phase == 1)
    def _normalize():
        s1 = s1_ref[...]
        s2 = s2_ref[...]
        mean_c = s1 / n
        var = jnp.maximum(s2 / n - mean_c * mean_c, 0.0)
        inv = lax.rsqrt(var + eps)
        g32 = gamma_ref[...]
        scale = g32 * inv
        offset = beta_ref[...] - mean_c * g32 * inv
        out_ref[...] = (xf * scale.astype(ew)
                        + offset.astype(ew)).astype(out_ref.dtype)
        mean_ref[...] = mean_c + s_ew.astype(jnp.float32)
        var_ref[...] = var
        inv_ref[...] = inv


def _fwd_pallas(x, gamma, beta, shift, eps):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = x.shape[-1]
    m = x.size // c
    x2 = x.reshape(m, c)
    g32 = gamma.astype(jnp.float32).reshape(1, c)
    b32 = beta.astype(jnp.float32).reshape(1, c)
    sh32 = lax.stop_gradient(shift.astype(jnp.float32)).reshape(1, c)
    ew = _nn()._bn_ew_dtype(x)
    bm = _block_rows(m, c)
    row = pl.BlockSpec((1, c), lambda p, i: (0, 0))
    out, mean, var, inv = _dispatch.pallas_call(
        functools.partial(_fwd_kernel, ew=ew, n=m, eps=eps),
        name="batchnorm_train_fwd",
        grid=(2, m // bm),
        in_specs=[
            pl.BlockSpec((bm, c), lambda p, i: (i, 0)),
            row, row, row,
        ],
        out_specs=[
            pl.BlockSpec((bm, c), lambda p, i: (i, 0)),
            row, row, row,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, c), x.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),   # Σ(x−shift)
            pltpu.VMEM((1, c), jnp.float32),   # Σ(x−shift)²
        ],
        interpret=_dispatch.interpret_requested(),
    )(x2, g32, b32, sh32)
    return (out.reshape(x.shape), mean.reshape(c), var.reshape(c),
            inv.reshape(c))


# ---------------------------------------------------------------------------
# backward kernel
# ---------------------------------------------------------------------------


def _bwd_kernel(x_ref, dy_ref, gamma_ref, mean_ref, inv_ref, shift_ref,
                dmean_ref, dvar_ref, dx_ref, dgamma_ref, dbeta_ref,
                db_ref, dg_ref, *, ew, n):
    import jax.experimental.pallas as pl

    phase = pl.program_id(0)
    m_idx = pl.program_id(1)

    @pl.when((phase == 0) & (m_idx == 0))
    def _init():
        db_ref[...] = jnp.zeros_like(db_ref)
        dg_ref[...] = jnp.zeros_like(dg_ref)

    s = shift_ref[...]                            # (1, C) f32
    inv = inv_ref[...]
    xf = x_ref[...].astype(ew) - s.astype(ew)
    mean_c = (mean_ref[...] - s).astype(ew)
    xhat = (xf - mean_c) * inv.astype(ew)
    dyf = dy_ref[...].astype(ew)

    @pl.when(phase == 0)
    def _accumulate():
        db_ref[...] += jnp.sum(dyf, axis=0, keepdims=True,
                               dtype=jnp.float32)
        dg_ref[...] += jnp.sum(dyf * xhat, axis=0, keepdims=True,
                               dtype=jnp.float32)
        dx_ref[...] = jnp.zeros_like(dx_ref)

    @pl.when(phase == 1)
    def _dx():
        dbeta = db_ref[...]
        dgamma = dg_ref[...]
        g32 = gamma_ref[...]
        dx = (g32 * inv).astype(ew) * (
            dyf - (dbeta.astype(ew) + xhat * dgamma.astype(ew)) / n)
        dx = dx + (dmean_ref[...].astype(ew) / n
                   + dvar_ref[...].astype(ew) * 2.0
                   * (xf - mean_c) / n)
        dx_ref[...] = dx.astype(dx_ref.dtype)
        dbeta_ref[...] = dbeta
        dgamma_ref[...] = dgamma


def _bwd_pallas(x, gamma, shift, mean, inv, dy, dmean_ct, dvar_ct):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = x.shape[-1]
    m = x.size // c
    x2 = x.reshape(m, c)
    dy2 = dy.reshape(m, c)
    g32 = gamma.astype(jnp.float32).reshape(1, c)
    mean2 = mean.astype(jnp.float32).reshape(1, c)
    inv2 = inv.astype(jnp.float32).reshape(1, c)
    sh32 = lax.stop_gradient(shift.astype(jnp.float32)).reshape(1, c)
    dm2 = dmean_ct.astype(jnp.float32).reshape(1, c)
    dv2 = dvar_ct.astype(jnp.float32).reshape(1, c)
    ew = _nn()._bn_ew_dtype(x)
    bm = _block_rows(m, c)
    row = pl.BlockSpec((1, c), lambda p, i: (0, 0))
    big = pl.BlockSpec((bm, c), lambda p, i: (i, 0))
    dx, dgamma, dbeta = _dispatch.pallas_call(
        functools.partial(_bwd_kernel, ew=ew, n=m),
        name="batchnorm_train_bwd",
        grid=(2, m // bm),
        in_specs=[big, big, row, row, row, row, row, row],
        out_specs=[big, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((m, c), x.dtype),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
            jax.ShapeDtypeStruct((1, c), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, c), jnp.float32),   # Σ dy
            pltpu.VMEM((1, c), jnp.float32),   # Σ dy·x̂
        ],
        interpret=_dispatch.interpret_requested(),
    )(x2, dy2, g32, mean2, inv2, sh32, dm2, dv2)
    return dx.reshape(x.shape), dgamma.reshape(c), dbeta.reshape(c)


# ---------------------------------------------------------------------------
# the custom_vjp drop-in for ops.nn._bn_train
# ---------------------------------------------------------------------------


def _fwd_impl(x, gamma, beta, shift, eps, axis):
    use_kernel, outcome, saved, xla_b, k_b = _decide(x, axis)
    # the combined fwd+bwd prediction is attributed to the forward
    # dispatch (a site adopts the kernel PAIR or neither)
    _dispatch.record("bn_fwd", outcome, saved, xla_bytes=xla_b,
                     kernel_bytes=k_b)
    if use_kernel:
        return _fwd_pallas(x, gamma, beta, shift, eps)
    return _nn()._bn_train_impl(x, gamma, beta, shift, eps, axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def bn_train(x, gamma, beta, shift, eps, axis):
    """Pallas-backed twin of ops.nn._bn_train: (out, mean, var) with the
    identical custom-VJP contract.  Falls back to the XLA implementation
    (same numerics) when the kernel can't run on this site."""
    out, mean, var, _ = _fwd_impl(x, gamma, beta, shift, eps, axis)
    return out, mean, var


def _bn_train_fwd(x, gamma, beta, shift, eps, axis):
    out, mean, var, inv = _fwd_impl(x, gamma, beta, shift, eps, axis)
    return (out, mean, var), (x, gamma, beta, shift, mean, inv)


def _bn_train_bwd(eps, axis, res, cts):
    x, gamma, beta, shift, mean, inv = res
    use_kernel, outcome, _, xla_b, k_b = _decide(x, axis)
    _dispatch.record("bn_bwd", outcome, xla_bytes=xla_b,
                     kernel_bytes=k_b)
    if not use_kernel:
        return _nn()._bn_train_bwd(eps, axis, res, cts)
    dy, dmean_ct, dvar_ct = cts
    dx, dgamma, dbeta = _bwd_pallas(x, gamma, shift, mean, inv, dy,
                                    dmean_ct, dvar_ct)
    return (dx, dgamma.astype(gamma.dtype), dbeta.astype(beta.dtype),
            jnp.zeros_like(shift))


bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)
