"""Cholesky family drivers (reference: src/potrf.cc, potrs.cc, posv.cc,
trtri.cc, trtrm.cc, potri.cc, posv_mixed.cc, pocondest.cc).

potrf is the factorization archetype (SURVEY §3.2): panel factor ->
broadcast -> trsm -> trailing herk with lookahead.  On TPU the global path
runs the native blocked schedule in ops/chol_kernels.py (the vendor
cholesky lowering is ~3% of the chip's gemm rate on this toolchain; CPU
keeps the vendor LAPACK kernel); the spmd path runs the explicit mesh
algorithm in parallel/spmd_chol.py.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..enums import Diag, Op, Option, Side, Uplo
from ..exceptions import DimensionError, NumericalError, slate_assert
from ..matrix.base import BaseMatrix, conj_transpose
from ..matrix.matrix import HermitianMatrix, Matrix, SymmetricMatrix, TriangularMatrix
from ..options import Options, get_option, resolve_schedule_opts
from ..ops import blas2d, chol_kernels
from ..parallel import spmd_chol
from ..parallel.layout import eye_splice, tiles_from_global
from . import blas3

from ..aux import metrics
from ..aux.metrics import instrumented


from ..matrix.base import is_distributed as _is_distributed
from ..internal import fallbacks

# metrics-gated jitted kernel: with metrics ON the eager global path
# dispatches through this wrapper so the compile/run split and the
# cost_analysis flops are attributed to "potrf.kernel"; with metrics off
# the original unjitted call runs, bit-identical to before.  The
# operand (a freshly mirrored full_global copy, never user storage) is
# donated on accelerators when this jit dispatches (metrics-on eager
# calls; inside an outer jit — serve cores, bench steps — the outer
# boundary owns donation, see serve/cache.py).
_cholesky_kernel = metrics.gated_jit(
    chol_kernels.cholesky, "potrf.kernel",
    static_argnums=(1, 2, 3, 4), donate_argnums=(0,),
)


def _hermitian_full_tiles(A: HermitianMatrix) -> jnp.ndarray:
    """Mirror the stored triangle into a full tile array (keeps sharding)."""
    return tiles_from_global(A.full_global().astype(A.dtype), A.layout)


@instrumented("potrf")
def potrf(
    A: HermitianMatrix, opts: Optional[Options] = None
) -> Tuple[TriangularMatrix, jnp.ndarray]:
    """Cholesky: A = L L^H (uplo Lower) or U^H U (Upper)
    (reference: src/potrf.cc:84-209).

    Returns (factor, info); info > 0 signals a non-SPD matrix, detected
    from non-finite entries like internal::reduce_info aggregates the
    per-rank codes (potrf.cc:208).
    """
    slate_assert(A.m == A.n, "potrf requires square A")
    slate_assert(A.layout.mb == A.layout.nb, "potrf requires square tiles")

    use_spmd = _is_distributed(A) and get_option(opts, Option.UseShardMap)
    if use_spmd:
        if A.uplo == Uplo.Lower and A.op == Op.NoTrans:
            # spmd_potrf_lower reads only the stored lower triangle —
            # no mirror round trip needed
            T = A.data
        else:
            fallbacks.record(
                "potrf.mirror", opts, "upper/viewed Hermitian mirrors globally"
            )
            T = _hermitian_full_tiles(A)
        T = eye_splice(A.layout, T)
        Ld = spmd_chol.spmd_potrf_lower(A.grid, T, A.layout)
        L = TriangularMatrix(Ld, A.layout, grid=A.grid, uplo=Uplo.Lower)
    else:
        if _is_distributed(A):
            fallbacks.record("potrf", opts, "UseShardMap disabled")
        full = A.full_global()
        n = A.n
        lay = A.layout
        # schedule-dispatched kernel (ops/chol_kernels.py; handles
        # padding/splicing for any n internally): the vendor lowering
        # runs at ~3% of the chip's gemm rate, the flat blocked loop
        # burns ~2-3x the model FLOPs, the recursive schedule factors
        # exact halving-lattice shapes.  nb is clamped to 512: larger
        # blocks would push chol_unblocked into its bandwidth-bound
        # regime.
        sched, nb_switch, lookahead = resolve_schedule_opts(opts)
        nb_kernel = 512 if n >= 2048 else min(lay.nb, 512)
        if metrics.is_on():
            route = chol_kernels.resolve_schedule(n, full.dtype, sched)
            metrics.record_factor_flops(
                "potrf",
                chol_kernels.chol_schedule_flops(
                    n, nb_kernel, route, nb_switch, lookahead
                ),
            )
        L2 = _cholesky_kernel(full, nb_kernel, sched, nb_switch, lookahead)
        L = TriangularMatrix.from_global(L2, lay.mb, lay.nb, grid=A.grid, uplo=Uplo.Lower)

    info = jnp.where(jnp.all(jnp.isfinite(L.data)), 0, 1).astype(jnp.int32)

    if A.uplo == Uplo.Upper:
        U = conj_transpose(L).resolved()
        U = TriangularMatrix(U.data, U.layout, grid=A.grid, uplo=Uplo.Upper)
        return U, info
    return L, info


@instrumented("potrs")
def potrs(
    L: TriangularMatrix, B: Matrix, opts: Optional[Options] = None
) -> Matrix:
    """Solve A X = B given the Cholesky factor (reference: src/potrs.cc:
    two trsm sweeps).  Operands on one device solve through
    ``potrs_from_global`` under the options' schedule."""
    if not (_is_distributed(L) or _is_distributed(B)) and (
        L.op == B.op == Op.NoTrans
    ):
        Lg = L._with(op=Op.NoTrans).to_global()
        if L.uplo == Uplo.Upper:
            Lg = jnp.conj(Lg).T if L.is_complex else Lg.T
        X = potrs_from_global(
            Lg, B.to_global(), resolve_schedule_opts(opts)[0]
        )
        return B._with(data=tiles_from_global(X, B.layout)).shard()
    if L.uplo == Uplo.Lower:
        Y = blas3.trsm(Side.Left, 1.0, L, B, opts)
        X = blas3.trsm(Side.Left, 1.0, conj_transpose(L), Y, opts)
    else:
        Y = blas3.trsm(Side.Left, 1.0, conj_transpose(L), B, opts)
        X = blas3.trsm(Side.Left, 1.0, L, Y, opts)
    return X


def _solve_trsm_route(n: int, dtype, schedule: str) -> str:
    """Schedule routing for the solve-phase trsm pair: ``"pallas"``
    (the Pallas pair), ``"blocked"`` (its algorithm in plain jnp,
    ``trsm_blocked``) or ``"vendor"``.  Explicit ``pallas`` is honored
    everywhere and the other explicit native schedules solve blocked —
    custom-call-free, so their serve artifacts export on CPU.  ``auto``
    keeps the vendor solve on CPU and below the factor schedules'
    crossover; above it the Pallas pair where its kernels compile
    (``chol_kernels.pallas_compiles``), the blocked loop otherwise."""
    if schedule == "pallas":
        return "pallas"
    if schedule != "auto":
        return "blocked"
    if jax.default_backend() == "cpu" or n < chol_kernels.RECURSIVE_MIN_N:
        return "vendor"
    return "pallas" if chol_kernels.pallas_compiles(dtype) else "blocked"


def potrs_from_global(
    Lg: jnp.ndarray, Bg: jnp.ndarray, schedule: str = "auto"
) -> jnp.ndarray:
    """potrs-style solve-only entry point over global arrays: solve
    L L^H X = B by two trsm sweeps against a clean lower-triangular
    factor.  The O(n^2) steady-state kernel of the serve factor
    cache's trsm-only (``phase="solve"``) bucket family; fully
    traceable (jit/vmap).  ``schedule="pallas"`` (or ``auto`` on an
    accelerator above the crossover) runs both sweeps through the
    fused Pallas trsm pair (ops/pallas/panel_kernels.py)."""
    from ..ops.pallas import panel_kernels as pk

    cplx = jnp.iscomplexobj(Lg)
    route = _solve_trsm_route(Lg.shape[0], Lg.dtype, schedule)
    with jax.named_scope("potrs.trsm_lower"):
        if route == "pallas":
            Y = pk.trsm_lower(Lg, Bg)
        elif route == "blocked":
            Y = pk.trsm_blocked(Lg, Bg, lower=True)
        else:
            Y = lax.linalg.triangular_solve(Lg, Bg, left_side=True,
                                            lower=True)
    with jax.named_scope("potrs.trsm_upper"):
        if route == "vendor":
            return lax.linalg.triangular_solve(
                Lg, Y, left_side=True, lower=True, transpose_a=True,
                conjugate_a=cplx,
            )
        U = jnp.conj(Lg).T if cplx else Lg.T
        if route == "pallas":
            return pk.trsm_upper(U, Y)
        return pk.trsm_blocked(U, Y, lower=False)


@instrumented("posv")
def posv(
    A: HermitianMatrix, B: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, TriangularMatrix, jnp.ndarray]:
    """Solve SPD A X = B (reference: src/posv.cc = potrf + potrs).

    Returns (X, factor, info)."""
    L, info = potrf(A, opts)
    X = potrs(L, B, opts)
    return X, L, info


@instrumented("trtri")
def trtri(T: TriangularMatrix, opts: Optional[Options] = None) -> TriangularMatrix:
    """Triangular inverse (reference: src/trtri.cc) via solve vs identity."""
    slate_assert(T.m == T.n, "trtri requires square")
    A2 = T._with(op=Op.NoTrans).to_global()
    eye = jnp.eye(T.m, dtype=T.dtype)
    inv = blas2d.trsm2d(Side.Left, T.uplo, T.op, T.diag, 1.0, A2, eye)
    # op(A)^-1 lives in the triangle of op(A), not of the storage: a
    # transposed view inverts into the opposite triangle (mirrors
    # resolved()'s uplo swap).
    out_uplo = T.uplo
    if T.op != Op.NoTrans:
        out_uplo = Uplo.Upper if T.uplo == Uplo.Lower else Uplo.Lower
    out = TriangularMatrix.from_global(
        inv, T.layout.mb, T.layout.nb, grid=T.grid, uplo=out_uplo, diag=T.diag
    )
    return out


def trtrm(L: TriangularMatrix, opts: Optional[Options] = None) -> HermitianMatrix:
    """L^H L (or U U^H) keeping the triangle — the second half of potri
    (reference: src/trtrm.cc)."""
    Lg = L._with(op=Op.NoTrans).to_global()
    if L.uplo == Uplo.Lower:
        tri = jnp.tril(Lg)
        out = jnp.conj(tri).T @ tri if L.is_complex else tri.T @ tri
    else:
        tri = jnp.triu(Lg)
        out = tri @ jnp.conj(tri).T if L.is_complex else tri @ tri.T
    return HermitianMatrix.from_global(
        out, L.layout.mb, L.layout.nb, grid=L.grid, uplo=L.uplo
    )


@instrumented("potri")
def potri(L: TriangularMatrix, opts: Optional[Options] = None) -> HermitianMatrix:
    """SPD inverse from the Cholesky factor: A^-1 = L^-H L^-1
    (reference: src/potri.cc = trtri + trtrm)."""
    Linv = trtri(L, opts)
    return trtrm(Linv, opts)


# Mixed-precision SPD solvers: implementations live in
# drivers/mixed.py, routed through the refine/ subsystem (policy +
# IR/GMRES-IR cores); re-exported here for reference-parity import
# paths (chol.posv_mixed).
from .mixed import posv_mixed, posv_mixed_gmres  # noqa: E402,F401


def pocondest(
    L: TriangularMatrix, anorm, opts: Optional[Options] = None
):
    """Reciprocal condition estimate from the Cholesky factor (reference:
    src/pocondest.cc via Hager/Higham 1-norm estimation,
    internal_norm1est.cc:1-511): O(n^2) factor solves per probe instead
    of the O(n^3) explicit inverse; A^-1 is self-adjoint so one solve
    closure serves both directions."""
    from ..internal.norm1est import norm1est

    G = L._with(op=Op.NoTrans).to_global()
    n = G.shape[0]
    lower = L.uplo == Uplo.Lower
    cplx = L.is_complex

    def solve(R):
        Y = lax.linalg.triangular_solve(
            G, R, left_side=True, lower=lower, transpose_a=not lower,
            conjugate_a=cplx and not lower,
        )
        return lax.linalg.triangular_solve(
            G, Y, left_side=True, lower=lower, transpose_a=lower,
            conjugate_a=cplx and lower,
        )

    est = norm1est(solve, solve, n, L.dtype)
    rcond = 1.0 / (jnp.asarray(anorm) * est)
    return jnp.where(jnp.isfinite(rcond), rcond, 0.0)


