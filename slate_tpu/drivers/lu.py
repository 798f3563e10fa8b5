"""LU family drivers (reference: src/getrf.cc, getrf_nopiv.cc,
getrf_tntpiv.cc, getrs.cc, getrs_nopiv.cc, gesv.cc, gesv_nopiv.cc,
gesv_rbt.cc + gerbt.cc + internal_rbt_generate.cc, gesv_mixed.cc,
gesv_mixed_gmres.cc, getri.cc, getriOOP.cc, gecondest.cc, trcondest.cc).

Pivoted LU under a static schedule is hard part (1) of SURVEY §7; the
global path hands the panel-pivot search to XLA's lu, the spmd path runs
the explicit mesh algorithm (parallel/spmd_lu.py).  The schedule-friendly
alternatives the reference offers — no-pivot LU and the random butterfly
transform — are first-class here for the same reason they exist there.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..enums import Diag, MethodLU, Norm, Op, Option, Side, Uplo
from ..exceptions import slate_assert
from ..internal.precision import hdot as _dot
from ..matrix.base import BaseMatrix
from ..matrix.matrix import Matrix, TriangularMatrix
from ..options import Options, get_option, resolve_schedule_opts
from ..ops import lu_kernels
from ..parallel import spmd_lu, spmd_trsm
from ..parallel.layout import eye_splice, tiles_from_global, tiles_to_global
from ..types import Pivots
from . import blas3
from .aux import norm as _norm

from ..aux import metrics
from ..aux.metrics import instrumented


from ..matrix.base import is_distributed as _is_distributed
from ..internal import fallbacks

# metrics-gated jitted kernel: attributes the eager global LU's
# compile/run split + cost_analysis to "getrf.kernel" (unjitted original
# call with metrics off).  The padded-global operand (always a fresh
# temporary) is donated on accelerators when this jit dispatches —
# getrf overwrites A in place like the reference; under an outer jit
# (serve cores) the outer boundary donates instead (serve/cache.py).
_lu_global_kernel = metrics.gated_jit(
    lu_kernels.lu_global, "getrf.kernel",
    static_argnums=(1, 2, 3, 4), donate_argnums=(0,),
)


def _padded_global(A: BaseMatrix, splice_diag=True) -> jnp.ndarray:
    Ar = A.resolved()
    lay = Ar.layout
    G = Ar.to_global()
    mp, np_ = lay.P * lay.mb, lay.Q * lay.nb
    Gp = jnp.pad(G, ((0, mp - lay.m), (0, np_ - lay.n)))
    if splice_diag:
        d = jnp.zeros(min(mp, np_), dtype=G.dtype)
        d = d.at[min(lay.m, lay.n):].set(1)
        Gp = Gp + jnp.zeros_like(Gp).at[
            jnp.arange(min(mp, np_)), jnp.arange(min(mp, np_))
        ].set(d)
    return Gp


def _udiag_info(LU: Matrix, lay) -> jnp.ndarray:
    """info code: exact zero / non-finite on U's diagonal.

    Evaluated as a masked reduction over the storage tile array — on a
    mesh GSPMD lowers it to a local reduction + psum, never a gather
    (the reference's internal::reduce_info, potrf.cc:208; the old
    to_global() here round-tripped the whole matrix to check n scalars)."""
    dmin = min(lay.m, lay.n)
    gr = jnp.asarray(lay.global_rows_np)[:, None, :, None]
    gc = jnp.asarray(lay.global_cols_np)[None, :, None, :]
    dmask = (gr == gc) & (gr < dmin)
    T = LU.data
    bad = (T == 0) | ~jnp.isfinite(T)
    if jnp.issubdtype(T.dtype, jnp.complexfloating):
        bad = (T == 0) | ~(jnp.isfinite(jnp.real(T)) & jnp.isfinite(jnp.imag(T)))
    return jnp.where(jnp.any(bad & dmask), 1, 0).astype(jnp.int32)


@instrumented("getrf")
def getrf(
    A: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, Pivots, jnp.ndarray]:
    """LU with partial pivoting: P A = L U (reference: src/getrf.cc).

    Returns (LU, pivots, info): LU holds unit-lower L below the diagonal
    and U on/above (LAPACK layout); pivots is the net forward row
    permutation; info > 0 flags an exactly-singular U diagonal.
    """
    slate_assert(A.op == Op.NoTrans, "getrf expects a non-transposed view")
    lay = A.layout
    method = get_option(opts, Option.MethodLU, MethodLU.Auto)
    if isinstance(method, str):
        method = MethodLU.from_string(method)
    if method in (MethodLU.CALU, MethodLU.BEAM):
        # tournament pivoting (reference: getrf_tntpiv.cc; BEAM maps to
        # the tournament too — both trade the per-column pivot search for
        # a communication-free reduction, the fit for static schedules)
        if (
            _is_distributed(A)
            and get_option(opts, Option.UseShardMap)
            and lay.mb == lay.nb
        ):
            # mesh tournament: local election per process row + one
            # winner all_gather over 'p' (parallel/spmd_lu.py)
            T = eye_splice(lay, A.data)
            Td, perm = spmd_lu.spmd_getrf_tntpiv(A.grid, T, lay)
            LU = A._with(data=Td)
            return LU, Pivots(perm), _udiag_info(LU, lay)
        if _is_distributed(A):
            import warnings

            warnings.warn(
                "getrf(MethodLU.CALU) on a distributed matrix gathers to a "
                "global array (non-square tiles or UseShardMap disabled)",
                stacklevel=2,
            )
            fallbacks.record("getrf_tntpiv", opts, "tournament gathers")
        Gp = _padded_global(A)
        lu2d, perm = lu_kernels.blocked_getrf_tntpiv(Gp, lay.nb)
        LU = A._with(data=tiles_from_global(lu2d[: lay.m, : lay.n], lay)).shard()
        return LU, Pivots(perm), _udiag_info(LU, lay)
    use_spmd = _is_distributed(A) and get_option(opts, Option.UseShardMap)
    if use_spmd and lay.mb == lay.nb:
        T = eye_splice(lay, A.data)
        Td, perm = spmd_lu.spmd_getrf(A.grid, T, lay)
        LU = A._with(data=Td)
        m_valid = lay.m
    else:
        if _is_distributed(A):
            fallbacks.record("getrf", opts, "non-square tiles")
        Gp = _padded_global(A)
        # schedule-dispatched kernel: vendor LU when auto on a backend
        # that supports the dtype (TPU: f32/c64 only), recursive divide
        # & conquer at large n / on request, else the flat blocked
        # right-looking kernel (ops/lu_kernels.py; src/getrf.cc:85-214)
        sched, nb_switch, lookahead = resolve_schedule_opts(opts)
        mp, np_ = Gp.shape
        if metrics.is_on():
            route = lu_kernels.resolve_lu_schedule(mp, np_, Gp.dtype, sched)
            metrics.record_factor_flops(
                "getrf",
                lu_kernels.getrf_schedule_flops(
                    mp, np_, lay.nb, route, nb_switch, lookahead,
                    m_true=lay.m, n_true=lay.n,
                ),
            )
        lu2d, perm = _lu_global_kernel(
            Gp, lay.nb, sched, nb_switch, lookahead
        )
        LU = A._with(data=tiles_from_global(lu2d[: lay.m, : lay.n], lay)).shard()
        m_valid = lay.m

    return LU, Pivots(perm), _udiag_info(LU, lay)


@instrumented("getrf_nopiv")
def getrf_nopiv(
    A: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, jnp.ndarray]:
    """LU without pivoting (reference: src/getrf_nopiv.cc) — the
    schedule-friendly variant: one triangular recursion, no row traffic."""
    slate_assert(A.m == A.n, "getrf_nopiv requires square A")
    slate_assert(A.layout.mb == A.layout.nb, "getrf_nopiv requires square tiles")
    lay = A.layout
    Gp = _padded_global(A)
    n = Gp.shape[0]

    # blocked right-looking no-pivot LU via scan-free recursion: XLA's lu
    # always pivots, so build L/U from it only when the permutation is
    # identity; otherwise do the blocked elimination directly.
    def nopiv_lu(G):
        nb = lay.nb

        def body(k, G):
            # diag block
            akk = lax.dynamic_slice(G, (k * nb, k * nb), (nb, nb))
            # factor diag block without pivoting: unrolled nb Gauss steps
            # via triangular solves against the strictly-lower recursion:
            lkk_ukk = _nopiv_block(akk)
            G = lax.dynamic_update_slice(G, lkk_ukk, (k * nb, k * nb))
            Lkk = jnp.tril(lkk_ukk, -1) + jnp.eye(nb, dtype=G.dtype)
            Ukk = jnp.triu(lkk_ukk)
            # panel below: A(i,k) Ukk^-1
            col = lax.dynamic_slice(G, (0, k * nb), (n, nb))
            col_solved = lax.linalg.triangular_solve(
                Ukk, col, left_side=False, lower=False
            )
            row_sel = (jnp.arange(n) >= (k + 1) * nb)[:, None]
            col = jnp.where(row_sel, col_solved, col)
            G = lax.dynamic_update_slice(G, col, (0, k * nb))
            # row to the right: Lkk^-1 A(k,j)
            row = lax.dynamic_slice(G, (k * nb, 0), (nb, n))
            row_solved = lax.linalg.triangular_solve(
                Lkk, row, left_side=True, lower=True, unit_diagonal=True
            )
            col_sel = (jnp.arange(n) >= (k + 1) * nb)[None, :]
            row = jnp.where(col_sel, row_solved, row)
            G = lax.dynamic_update_slice(G, row, (k * nb, 0))
            # trailing update
            Lpan = jnp.where(row_sel, lax.dynamic_slice(G, (0, k * nb), (n, nb)), 0)
            Urow = jnp.where(col_sel, lax.dynamic_slice(G, (k * nb, 0), (nb, n)), 0)
            return G - _dot(Lpan, Urow)

        return lax.fori_loop(0, n // nb, body, G)

    lu2d = nopiv_lu(Gp)
    LU = A._with(data=tiles_from_global(lu2d[: lay.m, : lay.n], lay)).shard()
    return LU, _udiag_info(LU, lay)


def _nopiv_block(a: jnp.ndarray) -> jnp.ndarray:
    """Unblocked no-pivot LU of one tile via Schur-complement scan."""
    nb = a.shape[0]

    def body(j, a):
        pivot = a[j, j]
        col = a[:, j] / jnp.where(pivot == 0, 1, pivot)
        below = jnp.arange(nb) > j
        lcol = jnp.where(below, col, a[:, j] * 0)
        a = a.at[:, j].set(jnp.where(below, lcol, a[:, j]))
        right = jnp.arange(nb) > j
        upd = jnp.outer(lcol, jnp.where(right, a[j], 0))
        return a - upd

    return lax.fori_loop(0, nb, body, a)


@instrumented("getrs")
def getrs(
    LU: Matrix,
    pivots: Optional[Pivots],
    B: Matrix,
    opts: Optional[Options] = None,
) -> Matrix:
    """Solve A X = B from getrf factors (reference: src/getrs.cc:
    permuteRows forward, trsm L, trsm U).

    Distributed path: SPMD permute-rows + two shard_map trsm pipelines
    over the LU-packed tile array — B never gathers to a global array
    (reference: internal::permuteRows + work::trsm, getrs.cc)."""
    lay = LU.layout
    layB = B.layout
    if (
        _is_distributed(B)
        and get_option(opts, Option.UseShardMap)
        and lay.mb == lay.nb == layB.mb
        and (lay.p, lay.q) == (layB.p, layB.q)
        and layB.mt == lay.mt
        and LU.op == Op.NoTrans
        and B.op == Op.NoTrans
        and (pivots is None or pivots.perm.shape[0] == lay.P * lay.mb)
    ):
        TBd = B.data
        if pivots is not None:
            TBd = spmd_trsm.spmd_permute_rows(B.grid, TBd, layB, pivots.perm)
        TT = eye_splice(lay, LU.data)
        Y = spmd_trsm.spmd_trsm_left(
            B.grid, TT, lay, TBd, layB,
            lower=True, trans=False, conj=False, unit_diag=True,
        )
        X = spmd_trsm.spmd_trsm_left(
            B.grid, TT, lay, Y, layB,
            lower=False, trans=False, conj=False, unit_diag=False,
        )
        return B._with(data=X)
    if _is_distributed(B):
        fallbacks.record("getrs", opts, "layout/view not spmd-conformable")
    G = LU.to_global()
    B2 = B.to_global()
    if pivots is not None:
        with jax.named_scope("getrs.permute"):
            B2 = pivots.apply(jnp.pad(
                B2, ((0, pivots.perm.shape[0] - B2.shape[0]), (0, 0))
            ))[: B.m]
    X = getrs_from_global(G, B2, resolve_schedule_opts(opts)[0])
    return B._with(data=tiles_from_global(X.astype(B.dtype), B.layout)).shard()


def getrs_nopiv(LU: Matrix, B: Matrix, opts=None) -> Matrix:
    """(reference: src/getrs_nopiv.cc)"""
    return getrs(LU, None, B, opts)


def getrs_from_global(
    LUg: jnp.ndarray, Bg: jnp.ndarray, schedule: str = "auto"
) -> jnp.ndarray:
    """getrs-style solve-only entry point over global arrays: two trsm
    sweeps against a packed LU (unit-lower L below the diagonal, U on
    and above), B already row-permuted (P B).  This is the O(n^2)
    steady-state kernel of the serve factor cache's trsm-only
    (``phase="solve"``) bucket family — the factorization's row
    permutation is a host-side gather, so the traced program is pure
    triangular algebra and exports custom-call-free under the native
    schedules (``chol._solve_trsm_route``).  Fully traceable
    (jit/vmap).  The Pallas pair and its blocked jnp form read only
    their own triangle, so the packed storage needs no unpacking."""
    from ..ops.pallas import panel_kernels as pk
    from .chol import _solve_trsm_route

    route = _solve_trsm_route(LUg.shape[0], LUg.dtype, schedule)
    with jax.named_scope("getrs.trsm_lower"):
        if route == "pallas":
            Y = pk.trsm_lower(LUg, Bg, unit=True)
        elif route == "blocked":
            Y = pk.trsm_blocked(LUg, Bg, lower=True, unit=True)
        else:
            Y = lax.linalg.triangular_solve(
                LUg, Bg, left_side=True, lower=True, unit_diagonal=True
            )
    with jax.named_scope("getrs.trsm_upper"):
        if route == "pallas":
            return pk.trsm_upper(LUg, Y)
        if route == "blocked":
            return pk.trsm_blocked(LUg, Y, lower=False)
        return lax.linalg.triangular_solve(LUg, Y, left_side=True,
                                           lower=False)


@instrumented("gesv")
def gesv(
    A: Matrix, B: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, Matrix, Pivots, jnp.ndarray]:
    """Solve A X = B (reference: src/gesv.cc; method dispatch
    MethodLU Partial/NoPiv/RBT per gesv.cc + enums MethodLU)."""
    method = get_option(opts, Option.MethodLU, MethodLU.Auto)
    if isinstance(method, str):
        method = MethodLU.from_string(method)
    if method == MethodLU.NoPiv:
        LU, info = getrf_nopiv(A, opts)
        return getrs_nopiv(LU, B, opts), LU, Pivots(jnp.arange(0)), info
    if method == MethodLU.RBT:
        return gesv_rbt(A, B, opts)
    LU, piv, info = getrf(A, opts)
    X = getrs(LU, piv, B, opts)
    return X, LU, piv, info


def gesv_nopiv(A: Matrix, B: Matrix, opts=None):
    """(reference: src/gesv_nopiv.cc)"""
    return gesv(A, B, {**(dict(opts) if opts else {}), Option.MethodLU: MethodLU.NoPiv})


# ---------------------------------------------------------------------------
# Random butterfly transform (reference: src/gerbt.cc +
# src/internal/internal_rbt_generate.cc, gesv_rbt.cc).
# ---------------------------------------------------------------------------


def _butterfly_diags(n: int, depth: int, seed: int, dtype) -> jnp.ndarray:
    """Random diagonals for the recursive butterflies, from the Philox
    counter RNG so the transform is reproducible across distributions
    (reference: internal_rbt_generate.cc uses the same matgen RNG)."""
    from ..matgen.philox import random_jnp

    i = jnp.arange(depth * n, dtype=jnp.int64).reshape(depth, n)
    r = random_jnp("uniform_signed", seed, i, jnp.zeros_like(i), jnp.float64)
    # scale into [~0.9, ~1.1] exponentials like the reference's e^{r/10}
    vals = jnp.exp(r / 10.0)
    return vals.astype(dtype)


def _apply_butterfly(X: jnp.ndarray, diags: jnp.ndarray, transpose: bool) -> jnp.ndarray:
    """Y = B^T X (transpose=True) or B X, B = recursive butterfly of depth d.

    One depth-ell butterfly on vector x of even length 2h:
      B = 1/sqrt(2) [[D1, D2], [D1, -D2]]  (diagonal blocks)
      B^T x = 1/sqrt(2) [D1 (x1 + x2); D2 (x1 - x2)]
      B x   = 1/sqrt(2) [D1' x1 + D2' x2 ...]  -- with B orthogonal-like.
    Applied blockwise at each recursion level (reference gerbt.cc kernel
    structure).
    """
    from ..ops.pallas.kernels import butterfly_level

    d, n = diags.shape
    Y = X
    levels = range(d) if transpose else range(d - 1, -1, -1)
    for ell in levels:
        blocks = 2**ell
        h = n // (2 * blocks)
        if h == 0:
            continue
        D = diags[ell]
        Yr = Y.reshape(blocks, 2 * h, -1)
        Dr = D[: blocks * 2 * h].reshape(blocks, 2 * h)
        # per recursion block: the device butterfly pair kernel
        # (ops/pallas: one VMEM pass; jnp twin elsewhere)
        out = jax.vmap(
            lambda x, dr: butterfly_level(x, dr[:h], dr[h:], transpose)
        )(Yr, Dr)
        Y = out.reshape(n, -1)
    return Y


def _gerbt_full(A: Matrix, depth: int, seed: int):
    """Full power-of-2-padded two-sided butterfly transform.

    Returns (A'_2d of size n2, du, dv, n2).  The whole n2 x n2 transformed
    matrix must be kept: the butterfly mixes the identity padding into the
    valid block, so truncating before factoring breaks the algebra."""
    slate_assert(A.m == A.n, "rbt requires square A")
    n2 = 1 << int(np.ceil(np.log2(max(A.n, 1))))
    G = A.to_global()
    Gp = jnp.pad(G, ((0, n2 - A.n), (0, n2 - A.n)))
    Gp = Gp + jnp.diag(
        jnp.concatenate([jnp.zeros(A.n), jnp.ones(n2 - A.n)]).astype(G.dtype)
    )
    du = _butterfly_diags(n2, depth, seed, G.dtype)
    dv = _butterfly_diags(n2, depth, seed + 1, G.dtype)
    # A' = U^T A V: columns through U^T on the left, rows through V
    Gp = _apply_butterfly(Gp, du, transpose=True)
    Gp = _apply_butterfly(Gp.T, dv, transpose=True).T
    return Gp, du, dv, n2


def gerbt(
    A: Matrix, depth: int = 2, seed: int = 42, opts: Optional[Options] = None
) -> Tuple[Matrix, jnp.ndarray, jnp.ndarray]:
    """Two-sided random butterfly transform A' = U^T A V (reference:
    src/gerbt.cc); returns (A', diags_U, diags_V)."""
    Gp, du, dv, _ = _gerbt_full(A, depth, seed)
    out = Matrix.from_global(Gp[: A.n, : A.n], A.layout.mb, A.layout.nb, grid=A.grid)
    return out, du, dv


@instrumented("gesv_rbt")
def gesv_rbt(
    A: Matrix, B: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, Matrix, Pivots, jnp.ndarray]:
    """RBT solve: butterfly-randomize, factor without pivoting, solve,
    then iterative refinement (reference: src/gesv_rbt.cc)."""
    depth = int(get_option(opts, Option.Depth, 2))
    seed = 42
    Gp, du, dv, n2 = _gerbt_full(A, depth, seed)
    mb = min(A.layout.mb, n2)
    Arbt = Matrix.from_global(Gp, mb, grid=A.grid)
    LU, info = getrf_nopiv(Arbt, opts)
    G_lu = LU.to_global()  # n2 x n2
    A2 = A.to_global()
    B2 = B.to_global()

    def solve(Rhs):
        Rp = jnp.pad(Rhs, ((0, n2 - A.n), (0, 0)))
        Rp = _apply_butterfly(Rp, du, transpose=True)
        Y = lax.linalg.triangular_solve(
            G_lu, Rp, left_side=True, lower=True, unit_diagonal=True
        )
        Z = lax.linalg.triangular_solve(G_lu, Y, left_side=True, lower=False)
        Z = _apply_butterfly(Z, dv, transpose=False)
        return Z[: A.n]

    X = solve(B2)
    # refinement steps (gesv_rbt.cc does IR to recover accuracy)
    for _ in range(2):
        R = B2 - _dot(A2, X)
        X = X + solve(R)
    Xm = B._with(data=tiles_from_global(X.astype(B.dtype), B.layout)).shard()
    return Xm, LU, Pivots(jnp.arange(0)), info


# ---------------------------------------------------------------------------
# Inverse, mixed precision, condition estimation
# ---------------------------------------------------------------------------


@instrumented("getri")
def getri(LU: Matrix, pivots: Pivots, opts: Optional[Options] = None) -> Matrix:
    """Matrix inverse from LU factors (reference: src/getri.cc /
    getriOOP.cc): A^-1 = U^-1 L^-1 P."""
    eye = Matrix.from_global(
        jnp.eye(LU.m, dtype=LU.dtype), LU.layout.mb, LU.layout.nb, grid=LU.grid
    )
    return getrs(LU, pivots, eye, opts)


# Mixed-precision solvers: implementations live in drivers/mixed.py,
# routed through the refine/ subsystem (policy + IR/GMRES-IR cores);
# re-exported here for reference-parity import paths (lu.gesv_mixed).
from .mixed import gesv_mixed, gesv_mixed_gmres  # noqa: E402,F401

# Back-compat shim for the pre-refine/ helper name (the IR while_loop
# used to live here; chol.py and external callers imported it).
from ..refine.ir import ir_refine_while  # noqa: E402,F401


@instrumented("gecondest")
def gecondest(
    LU: Matrix, pivots: Pivots, anorm, norm_type: Norm = Norm.One, opts=None
):
    """Reciprocal condition estimate from LU (reference: src/gecondest.cc
    via the Hager/Higham 1-norm estimator, internal_norm1est.cc:1-511):
    O(n^2) factor solves instead of an explicit inverse."""
    from ..internal.norm1est import norm1est

    G = LU.to_global()
    n = G.shape[0]
    perm = jnp.clip(pivots.perm[:n], 0, n - 1)
    inv_perm = jnp.zeros((n,), perm.dtype).at[perm].set(
        jnp.arange(n, dtype=perm.dtype)
    )

    def solve(R):  # A^-1 R  (A = P^T L U)
        Y = lax.linalg.triangular_solve(
            G, R[perm], left_side=True, lower=True, unit_diagonal=True
        )
        return lax.linalg.triangular_solve(G, Y, left_side=True, lower=False)

    conj_a = LU.is_complex

    def solve_h(R):  # A^-H R
        Y = lax.linalg.triangular_solve(
            G, R, left_side=True, lower=False, transpose_a=True,
            conjugate_a=conj_a,
        )
        Z = lax.linalg.triangular_solve(
            G, Y, left_side=True, lower=True, unit_diagonal=True,
            transpose_a=True, conjugate_a=conj_a,
        )
        return Z[inv_perm]

    if norm_type == Norm.Inf:
        # ||A^-1||_inf = ||A^-H||_1
        est = norm1est(solve_h, solve, n, LU.dtype)
    else:
        est = norm1est(solve, solve_h, n, LU.dtype)
    rcond = 1.0 / (jnp.asarray(anorm) * est)
    return jnp.where(jnp.isfinite(rcond), rcond, 0.0)


def trcondest(T: TriangularMatrix, norm_type: Norm = Norm.One, opts=None):
    """Triangular condition estimate (reference: src/trcondest.cc via
    internal_norm1est.cc) — Hager/Higham on T^-1 with O(n^2) solves."""
    from ..internal.norm1est import norm1est

    anorm = _norm(norm_type, T)
    G = T._with(op=Op.NoTrans).to_global()
    n = G.shape[0]
    st_lower = T.uplo == Uplo.Lower
    unit = T.diag == Diag.Unit
    cplx = T.is_complex

    def tri(R, *, trans, conj):
        if conj and not trans:  # solve conj(G) X = R
            return jnp.conj(
                lax.linalg.triangular_solve(
                    G, jnp.conj(R), left_side=True, lower=st_lower,
                    unit_diagonal=unit,
                )
            )
        return lax.linalg.triangular_solve(
            G, R, left_side=True, lower=st_lower, unit_diagonal=unit,
            transpose_a=trans, conjugate_a=conj and cplx,
        )

    # op(T) X = R and op(T)^H X = R, expressed in storage (trans, conj)
    vt = T.op != Op.NoTrans
    vc = T.op == Op.ConjTrans

    def solve(R):
        return tri(R, trans=vt, conj=vc)

    def solve_h(R):
        return tri(R, trans=not vt, conj=cplx and not vc)

    if norm_type == Norm.Inf:
        # ||T^-1||_inf = ||T^-H||_1
        est = norm1est(solve_h, solve, n, T.dtype)
    else:
        est = norm1est(solve, solve_h, n, T.dtype)
    rcond = 1.0 / (jnp.asarray(anorm) * est)
    return jnp.where(jnp.isfinite(rcond), rcond, 0.0)
