"""Classical iterative refinement core (reference: the IR loop of
src/gesv_mixed.cc:90-160; Carson & Higham SISC 2018 for the
three-precision convergence analysis the stopping test follows).

Device-resident: one ``lax.while_loop`` instead of ~2 dispatches per
iteration (each of which pays a host round trip); the host reads back
only the final ``(X, iters, converged, berr)``.
Fully traceable — the serve mixed-bucket executables inline this loop
into their jit (the lazy-info contract: nothing here forces a host
sync; the eager drivers in ``drivers/mixed.py`` do the one readback).

Stopping test: the **componentwise backward error** (Oettli–Prager;
Carson & Higham eq. (1.2))

    berr = max_ij |B - A X|_ij / (|A| |X| + |B|)_ij

which, unlike the normwise test the reference uses, certifies the
solution column-by-column and is scale-invariant per entry.  Both the
residual and the denominator are evaluated in the working precision
under ``accurate_matmul`` semantics (``internal.precision.hdot`` —
``Precision.HIGHEST`` plus the emulated-f64 k-chunking), the closest
this hardware has to Carson & Higham's wider residual precision.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Tuple

import jax.numpy as jnp
from jax import lax

from ..internal.precision import hdot


class RefineResult(NamedTuple):
    """Device-resident refinement outcome (lazy-info: every field is a
    jax array until a caller forces it)."""

    X: jnp.ndarray  # working-precision solution estimate
    iters: jnp.ndarray  # int32 count of correction steps taken
    converged: jnp.ndarray  # bool: berr <= tol before the budget ran out
    berr: jnp.ndarray  # final componentwise backward error (real scalar)


def residual_berr(
    A2: jnp.ndarray, X: jnp.ndarray, B2: jnp.ndarray
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(R, berr): the working-precision residual B - A X (HIGHEST-
    precision accumulation) and its componentwise backward error
    max |R| / (|A||X| + |B|).  The single definition of the stopping
    test — ir and gmres loop bodies both call it, so the two methods
    cannot drift apart on what "converged" means.  An exactly-zero
    denominator entry (identity padding in serve buckets, zero RHS
    columns) means that entry's residual is exactly zero too, so it
    contributes 0, not 0/0 — guarded with a where, NOT an absolute
    floor (a float literal floor underflows to 0.0 in float32 working
    precision and would NaN every f32/bf16 solve with a zero row)."""
    R = B2 - hdot(A2, X)
    denom = hdot(jnp.abs(A2), jnp.abs(X)) + jnp.abs(B2)
    ratio = jnp.where(denom == 0, 0, jnp.abs(R) / jnp.where(denom == 0, 1, denom))
    return R, ratio.max()


def backward_error(A2: jnp.ndarray, X: jnp.ndarray, B2: jnp.ndarray) -> jnp.ndarray:
    """Componentwise (Oettli–Prager) backward error of X; see
    :func:`residual_berr`."""
    return residual_berr(A2, X, B2)[1]


def refine_while(
    A2: jnp.ndarray,
    B2: jnp.ndarray,
    solve_factor: Callable[[jnp.ndarray], jnp.ndarray],
    tol: float,
    max_it: int,
) -> RefineResult:
    """Classical IR: ``X <- X + solve_factor(B - A X)`` until the
    componentwise backward error drops below ``tol`` or ``max_it``
    correction steps are spent.

    ``solve_factor`` applies the low-precision factors (cast in, solve,
    cast back to working precision).  A run that passes the test on the
    first residual check reports ``iters == 0``; a stalled or diverging
    run reports ``converged == False`` with the last (possibly
    non-finite) berr — the caller owns the fallback decision."""

    def cond(carry):
        _X, it, done, _b = carry
        return (~done) & (it < max_it)

    def body(carry):
        X, it, _done, _b = carry
        R, berr = residual_berr(A2, X, B2)
        conv = berr <= tol
        Xn = jnp.where(conv, X, X + solve_factor(R))
        # count only actual correction steps (parity with the old
        # host-loop accounting in drivers/lu.py)
        return Xn, it + jnp.where(conv, 0, 1), conv, berr

    X0 = solve_factor(B2)
    X, iters, converged, berr = lax.while_loop(
        cond, body, (X0, jnp.int32(0), jnp.bool_(False),
                     jnp.asarray(jnp.inf, jnp.abs(B2).dtype))
    )
    # a budget-exhausted loop exits with the berr of its LAST CHECK, one
    # correction behind X — recheck so `converged` never under-reports.
    # Guarded by cond: the converged (common) path must not pay two
    # extra O(n^2 nrhs) products for a value the select would discard
    # (under vmap — the serve cores — cond lowers to both-branches
    # select, which is no worse than the unconditional recompute).
    final_berr = lax.cond(
        converged, lambda _: berr, lambda _: backward_error(A2, X, B2), None
    )
    return RefineResult(
        X=X,
        iters=iters,
        converged=converged | (final_berr <= tol),
        berr=final_berr,
    )


def ir_refine_while(
    A2, B2, solve_lo, tol, anorm, max_it
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Back-compat shim for the pre-refine/ call sites (drivers/lu.py
    exported this normwise-test loop): same signature, same
    ``(X, iters, converged)`` triple.  NOTE the stopping semantics
    changed with the refine/ extraction: ``tol`` now bounds the
    componentwise backward error ``max |R| / (|A||X| + |B|)``, not the
    old normwise ``|R|max <= tol * anorm * |X|max`` (``anorm`` is kept
    for signature parity and ignored).  The two tests are close for
    well-scaled systems but neither implies the other in general — a
    caller with a normwise-calibrated ``tol`` should migrate to
    :func:`refine_while` and pick ``tol`` for the componentwise test
    (the refine.policy defaults).  A DeprecationWarning fires so the
    semantic change is visible at the call site, not just here."""
    import warnings

    warnings.warn(
        "ir_refine_while now stops on the componentwise backward error "
        "(anorm is ignored); migrate to refine.ir.refine_while and "
        "calibrate tol for the componentwise test",
        DeprecationWarning,
        stacklevel=2,
    )
    del anorm
    res = refine_while(A2, B2, solve_lo, tol, max_it)
    return res.X, res.iters, res.converged
