"""Matmul precision policy (SURVEY §7 hard-part (5) for f32).

On TPU the default f32 matmul is a single bf16x bf16 MXU pass (~8e-3
relative unit roundoff) — fine for ML, but LAPACK-parity residual bounds
(error <= tol * eps_f32) require true f32 accumulation, which XLA
provides via precision=HIGHEST (multi-pass).  The reference never faces
this: cuBLAS SGEMM is full f32 by default.

``accurate_matmul`` wraps a driver so every jnp matmul/einsum traced
inside it uses HIGHEST precision whenever a 32-bit float operand is
involved; f64/c128 paths are unaffected (TPU f64 emulation is already
exact-width).  Opt out per-process with SLATE_TPU_FAST_F32=1 to trade
accuracy for the single-pass MXU rate (the TF32-style mode GPUs opt
*into*).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

_F32 = (jnp.dtype("float32"), jnp.dtype("complex64"))


def _has32(x) -> bool:
    dt = getattr(x, "dtype", None)
    if dt is None:
        return False
    try:
        return jnp.dtype(dt) in _F32
    except TypeError:
        return False


def _any32(x) -> bool:
    """Matrix-aware 32-bit scan that also looks INSIDE sequences: the
    mixed drivers pass factor tuples / lists of matrices, which the old
    top-level-only scan treated as "no 32-bit operand" — silently
    running an f32 refinement at bf16-pass precision (the displaced-
    decorator failure mode the activation counter exists to catch)."""
    if isinstance(x, (list, tuple)):
        return any(_any32(e) for e in x)
    if isinstance(x, dict):
        return any(_any32(e) for e in x.values())
    return _has32(x)


def fast_f32() -> bool:
    return os.environ.get("SLATE_TPU_FAST_F32", "0") not in ("", "0")


def accurate_matmul(fn):
    """Decorator: run the driver under default_matmul_precision('highest')
    when any argument (or matrix argument's data) is f32/c64.

    Each activation bumps the ``precision.accurate_matmul_activations``
    metrics counter (a no-op with metrics off), so a displaced decorator
    — an f32 driver silently running at bf16-pass precision, the round-5
    eig.py regression — is visible as a missing count."""

    @functools.wraps(fn)
    def wrapper(*args, **kw):
        if not fast_f32() and any(
            _any32(a) for a in list(args) + list(kw.values())
        ):
            from ..aux import metrics

            metrics.inc("precision.accurate_matmul_activations")
            with jax.default_matmul_precision("highest"):
                return fn(*args, **kw)
        return fn(*args, **kw)

    # marker so tests can assert the policy is attached to a driver
    wrapper._accurate_matmul = True
    return wrapper


# HIGHEST-precision matmul: the TPU f64 emulation's default accumulation
# is ~f32 grade, so every kernel that owes LAPACK-parity accuracy
# contracts through this helper (single point for the precision policy).
import functools as _functools

from jax import lax as _lax

_hdot_raw = _functools.partial(jnp.matmul, precision=_lax.Precision.HIGHEST)

# The chip's f64 emulation additionally LOSES ITS COMPENSATION TERMS on
# cancellation-heavy contractions once the contraction length reaches
# 4096: Q^T Q off-diagonals (sums of +-1e-2 terms cancelling to ~1e-16)
# measure 6.5e-7 ABSOLUTE error at k=4096 vs 1e-15 at k=2048, while
# non-cancelling random products stay at ~1e-13 (round-5 diagnosis;
# tools/profile_* reproduce it).  Chunking the contraction at 2048 and
# accumulating in f64 restores 3.8e-15.  hdot therefore k-chunks every
# emulated-f64 matmul with k >= 4096 — the chunk loop is python-static,
# two extra adds per 8192-contraction, MXU throughput unaffected.
_KCHUNK = 2048
KCHUNK = _KCHUNK  # public alias: sites that chunk non-matmul einsums
_F64 = (jnp.dtype("float64"), jnp.dtype("complex128"))


def emulated_f64(dtype) -> bool:
    """True when `dtype` runs through the TPU f64 emulation (i.e. the
    k-chunk cliff workaround applies); False on real-f64 backends
    (CPU, GPU)."""
    try:
        return (
            jnp.dtype(dtype) in _F64
            and jax.default_backend() not in ("cpu", "gpu")
        )
    except TypeError:
        return False


def hdot(a, b, **kw):
    k = a.shape[-1]
    emul64 = emulated_f64(getattr(a, "dtype", None))
    if not emul64 or k < 2 * _KCHUNK or a.ndim != 2 or b.ndim != 2:
        return _hdot_raw(a, b, **kw)
    acc = None
    for s in range(0, k, _KCHUNK):
        part = _hdot_raw(a[:, s : s + _KCHUNK], b[s : s + _KCHUNK, :], **kw)
        acc = part if acc is None else acc + part
    return acc


def short_dot(a, b):
    """a @ b for a short contraction (a panel's strip width, a few
    dozen).  Where f64 is emulated, one fused elementwise product and
    sum: the emulated dot costs about as much per output for any
    contraction up to a few hundred.  Elsewhere ``hdot`` (the MXU for
    f32/c64, a native dot for real f64)."""
    if emulated_f64(a.dtype):
        return (a[:, :, None] * b[None, :, :]).sum(1)
    return hdot(a, b)
