"""QR/LQ/least-squares drivers (reference: src/geqrf.cc, unmqr.cc,
gelqf.cc, unmlq.cc, cholqr.cc, gels.cc, gels_qr.cc, gels_cholqr.cc).

Factor representation: the returned matrix stores R on/above the diagonal
and the Householder vectors V (implicit unit diagonal) below; the
TriangularFactors hold one compact-WY T per tile panel — the reference's
Tlocal (slate.hh TriangularFactors).  The reference's Treduce (CAQR tree
factors, internal_ttqrt.cc) has no analogue because the spmd path gathers
panels instead of tree-reducing them (see parallel/spmd_qr.py).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..enums import MethodGels, Norm, Op, Option, Side, Uplo
from ..exceptions import DimensionError, slate_assert
from ..matrix.base import BaseMatrix, conj_transpose
from ..matrix.matrix import HermitianMatrix, Matrix, TriangularMatrix
from ..options import Options, get_option
from ..ops.householder import (
    apply_block_reflector,
    geqrf as _geqrf_kernel,
    larft,
    materialize_v,
)
from ..parallel import spmd_qr
from ..parallel.layout import TileLayout, eye_splice, tiles_from_global
from ..types import TriangularFactors
from . import blas3, chol

from ..internal.precision import accurate_matmul

from ..aux import metrics
from ..aux.metrics import instrumented


from ..matrix.base import is_distributed as _is_distributed

# metrics-gated jitted kernels: attribute the eager QR's compile/run
# split + cost_analysis to "geqrf.kernel" (unjitted original call with
# metrics off).  The padded-global operand (always a fresh temporary)
# is donated on accelerators when these jits dispatch — geqrf
# overwrites A with V/R in place like the reference; under an outer
# jit the outer boundary donates instead (serve/cache.py).
_geqrf_global_kernel = metrics.gated_jit(
    _geqrf_kernel, "geqrf.kernel", donate_argnums=(0,)
)

from ..ops import qr_fast as _qr_fast

_geqrf_recursive_kernel = metrics.gated_jit(
    _qr_fast.geqrf_recursive, "geqrf.kernel_recursive",
    static_argnums=(1,), donate_argnums=(0,),
)

_geqrf_flat_kernel = metrics.gated_jit(
    _qr_fast.geqrf_flat, "geqrf.kernel_flat", donate_argnums=(0,)
)

_geqrf_pallas_kernel = metrics.gated_jit(
    _qr_fast.geqrf_pallas, "geqrf.kernel_pallas",
    static_argnums=(1,), donate_argnums=(0,),
)


def _padded_global_splice(A: BaseMatrix) -> jnp.ndarray:
    lay = A.layout
    G = A.resolved().to_global()
    mp, npd = lay.P * lay.mb, lay.Q * lay.nb
    Gp = jnp.pad(G, ((0, mp - lay.m), (0, npd - lay.n)))
    dmin = min(mp, npd)
    idx = jnp.arange(dmin)
    splice = jnp.where(idx >= min(lay.m, lay.n), 1.0, 0.0).astype(G.dtype)
    return Gp.at[idx, idx].add(splice)


@accurate_matmul
@instrumented("geqrf")
def geqrf(
    A: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, TriangularFactors]:
    """Householder QR: A = Q R (reference: src/geqrf.cc CAQR; SURVEY §3.4).

    Returns (factored, T): factored stores V below the diagonal and R on/
    above; T holds the per-panel compact-WY factors."""
    slate_assert(A.layout.mb == A.layout.nb, "geqrf requires square tiles")
    lay = A.layout
    nb = lay.nb
    kt = min(lay.mt, lay.nt)

    if _is_distributed(A) and get_option(opts, Option.UseShardMap):
        T = eye_splice(lay, A.resolved().data)
        Td, Tstack = spmd_qr.spmd_geqrf(A.grid, T, lay)
        return A._with(data=Td), TriangularFactors(Tstack)

    from ..options import resolve_schedule_opts

    Gp = _padded_global_splice(A)
    mp, npd = Gp.shape
    sched, nb_switch, _lookahead = resolve_schedule_opts(opts)
    # one resolver decides both the kernel and the accounting route, so
    # the factor.geqrf.* counters always describe the traced program
    route = _qr_fast.resolve_qr_schedule(mp, npd, Gp.dtype, sched)
    if metrics.is_on():
        metrics.record_factor_flops(
            "geqrf",
            _qr_fast.geqrf_schedule_flops(
                mp, npd, nb, route, nb_switch,
                m_true=lay.m, n_true=lay.n,
            ),
        )
    if route == "pallas":
        vr, taus = _geqrf_pallas_kernel(Gp, nb_switch)
    elif route == "recursive":
        vr, taus = _geqrf_recursive_kernel(Gp, nb_switch)
    elif route == "flat" and sched == "flat":
        # explicit flat runs the native schedule on every backend (the
        # auto flat route lets householder.geqrf pick, same kernel)
        vr, taus = _geqrf_flat_kernel(Gp)
    else:
        vr, taus = _geqrf_global_kernel(Gp)
    m_pad = Gp.shape[0]
    Ts = []
    for k in range(kt):
        Vk = materialize_v(
            lax.dynamic_slice_in_dim(vr, k * nb, nb, axis=1), offset=k * nb
        )
        Ts.append(larft(Vk, lax.dynamic_slice_in_dim(taus, k * nb, nb, 0)))
    Tstack = jnp.stack(Ts) if Ts else jnp.zeros((0, nb, nb), A.dtype)
    fac = A._with(data=tiles_from_global(vr[: lay.m, : lay.n], lay)).shard()
    return fac, TriangularFactors(Tstack)


def _vt_panels(fac: Matrix):
    """Iterate (V_k, offset) panels from the factored matrix's global
    form; V_k is full height with zeros above the panel diagonal."""
    lay = fac.layout
    nb = lay.nb
    G = fac.to_global()
    m = lay.m
    kt = min(lay.mt, lay.nt)
    for k in range(kt):
        ncols = min(nb, lay.n - k * nb)
        panel = G[:, k * nb : k * nb + ncols]
        Vk = materialize_v(panel, offset=k * nb)
        # zero any rows above the panel start
        yield k, Vk


@accurate_matmul
@instrumented("unmqr")
def unmqr(
    side: Side,
    op: Op,
    fac: Matrix,
    T: TriangularFactors,
    C: Matrix,
    opts: Optional[Options] = None,
) -> Matrix:
    """Multiply by Q from geqrf (reference: src/unmqr.cc).

    side Left:  C <- Q C (NoTrans) or Q^H C (ConjTrans);
    side Right: C <- C Q or C Q^H."""
    lay = fac.layout
    nb = lay.nb
    kt = min(lay.mt, lay.nt)
    C2 = C.to_global()
    Tn = T.T
    panels = list(_vt_panels(fac))
    forward = (side == Side.Left) == (op != Op.NoTrans)
    order = range(kt) if forward else range(kt - 1, -1, -1)
    conj_T = op != Op.NoTrans
    for k in order:
        _, Vk = panels[k]
        Tk = Tn[k][: Vk.shape[1], : Vk.shape[1]]
        if side == Side.Left:
            C2 = apply_block_reflector(Vk, Tk, C2, trans=conj_T)
        else:
            # C (I - V T V^H) = ((I - V T^T... ) C^H)^H; do it directly:
            W = C2 @ Vk  # (m, nb)
            Tm = (jnp.conj(Tk).T if fac.is_complex else Tk.T) if conj_T else Tk
            Vh = jnp.conj(Vk).T if fac.is_complex else Vk.T
            C2 = C2 - (W @ Tm) @ Vh
    return C._with(data=tiles_from_global(C2.astype(C.dtype), C.layout)).shard()


@accurate_matmul
def ungqr(
    fac: Matrix, T: TriangularFactors, opts: Optional[Options] = None
) -> Matrix:
    """Materialize the m x n orthogonal factor Q (LAPACK orgqr analogue;
    the reference tester materializes Q via unmqr on identity,
    test_geqrf.cc)."""
    lay = fac.layout
    eye = Matrix.from_global(
        jnp.eye(lay.m, min(lay.m, lay.n), dtype=fac.dtype),
        lay.mb,
        lay.nb,
        grid=fac.grid,
    )
    return unmqr(Side.Left, Op.NoTrans, fac, T, eye, opts)


@accurate_matmul
@instrumented("gelqf")
def gelqf(
    A: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, TriangularFactors]:
    """LQ factorization A = L Q (reference: src/gelqf.cc), computed as the
    dual of QR on A^H: A^H = Qr R  =>  A = R^H Qr^H = L Q.

    Returns (factored, T): factored stores L on/below the diagonal and
    V^H rows above (the dual's reflectors); T is the dual's T stack."""
    Ah = conj_transpose(A).resolved()
    Ah = Matrix(Ah.data, Ah.layout, grid=A.grid)
    facH, T = geqrf(Ah, opts)
    fac = conj_transpose(facH).resolved()
    return A._with(data=fac.data, layout=fac.layout), T


@accurate_matmul
def unmlq(
    side: Side,
    op: Op,
    fac: Matrix,
    T: TriangularFactors,
    C: Matrix,
    opts: Optional[Options] = None,
) -> Matrix:
    """Multiply by Q from gelqf (reference: src/unmlq.cc).  With the dual
    representation Q = Qr^H, so ops flip relative to unmqr."""
    facH = conj_transpose(fac).resolved()
    facH = Matrix(facH.data, facH.layout, grid=fac.grid)
    flip = {Op.NoTrans: Op.ConjTrans, Op.ConjTrans: Op.NoTrans, Op.Trans: Op.NoTrans}
    return unmqr(side, flip[op], facH, T, C, opts)


@accurate_matmul
@instrumented("cholqr")
def cholqr(
    A: Matrix, opts: Optional[Options] = None
) -> Tuple[Matrix, TriangularMatrix, jnp.ndarray]:
    """Cholesky QR (reference: src/cholqr.cc: H = A^H A via herk, potrf,
    Q = A R^-1 via trsm; MethodCholQR variants collapse to herk here).

    Returns (Q, R, info)."""
    lay = A.layout
    h_lay = TileLayout(lay.n, lay.n, lay.nb, lay.nb, lay.p, lay.q)
    H = HermitianMatrix(
        jnp.zeros(h_lay.storage_shape, A.dtype), h_lay, grid=A.grid, uplo=Uplo.Upper
    )
    H = blas3.herk(1.0, conj_transpose(A), 0.0, H)
    R, info = chol.potrf(H, opts)
    Rtri = TriangularMatrix(
        R.data, R.layout, grid=A.grid, uplo=Uplo.Upper
    )
    Q = blas3.trsm(Side.Right, 1.0, Rtri, A, opts)
    return Q, Rtri, info


def gels_solve_from_global(
    Fg: jnp.ndarray, Bg: jnp.ndarray, m: int, nb: int
) -> jnp.ndarray:
    """gels-style solve-only entry point over global arrays: least
    squares against a PRE-COMPUTED packed QR factor.  ``Fg`` is the
    serve factor cache's pack (``serve/buckets.solve_factor_shape``):
    rows [0, m) hold the padded V/R global (Householder vectors below
    the diagonal, R on/above), and each nb-wide column panel's
    compact-WY T factor is flattened below (panel at column offset k
    in rows [m + k, m + k + w), cols [0, w)).  Applies Q^H to B one
    block reflector per panel — no larft rebuild, the cached T rides
    in the pack — then one triangular solve against R: O(m n nrhs)
    against the full phase's O(m n^2) refactor.  Fully traceable
    (jit/vmap over B), so the warmed ``phase="solve"`` gels bucket
    serves a whole coalesced batch from ONE unbatched factor operand."""
    n = Fg.shape[1]
    VR = Fg[:m]
    C = Bg
    for k in range(0, n, nb):
        w = min(nb, n - k)
        Vk = materialize_v(VR[:, k : k + w], offset=k)
        Tk = Fg[m + k : m + k + w, :w]
        C = apply_block_reflector(Vk, Tk, C, trans=True)
    R = jnp.triu(VR[:n, :n])
    return lax.linalg.triangular_solve(
        R, C[:n], left_side=True, lower=False
    )


@accurate_matmul
@instrumented("gels")
def gels(
    A: Matrix, B: Matrix, opts: Optional[Options] = None
) -> Matrix:
    """Least squares / minimum-norm solve (reference: src/gels.cc with
    MethodGels QR | CholQR; gels_qr.cc, gels_cholqr.cc).

    Overdetermined (m >= n): X = argmin ||A X - B||; underdetermined:
    minimum-norm solution via the LQ dual.  Returns X (n x nrhs)."""
    method = get_option(opts, Option.MethodGels, MethodGels.Auto)
    if isinstance(method, str):
        method = MethodGels.from_string(method)
    m, n = A.m, A.n
    if m >= n:
        if method == MethodGels.CholQR:
            Q, R, info = cholqr(A, opts)
            QhB = blas3.gemm(
                1.0,
                conj_transpose(Q),
                B,
                0.0,
                Matrix.zeros(n, B.n, A.layout.nb, dtype=A.dtype, grid=A.grid),
            )
            return blas3.trsm(Side.Left, 1.0, R, QhB, opts)
        fac, T = geqrf(A, opts)
        QhB = unmqr(Side.Left, Op.ConjTrans, fac, T, B, opts)
        QhB_top = Matrix.from_global(
            QhB.to_global()[:n], A.layout.nb, A.layout.nb, grid=A.grid
        )
        Rg = jnp.triu(fac.to_global()[:n, :n])
        R = TriangularMatrix.from_global(
            Rg, A.layout.nb, A.layout.nb, grid=A.grid, uplo=Uplo.Upper
        )
        return blas3.trsm(Side.Left, 1.0, R, QhB_top, opts)
    # underdetermined: A = L Q, X = Q^H L^-1 B (minimum-norm)
    fac, T = gelqf(A, opts)
    Lg = jnp.tril(fac.to_global()[:, :m])
    L = TriangularMatrix.from_global(
        Lg, A.layout.mb, A.layout.mb, grid=A.grid, uplo=Uplo.Lower
    )
    Y = blas3.trsm(Side.Left, 1.0, L, B, opts)
    Yfull = Matrix.from_global(
        jnp.concatenate(
            [Y.to_global(), jnp.zeros((n - m, B.n), A.dtype)], axis=0
        ),
        A.layout.nb,
        A.layout.nb,
        grid=A.grid,
    )
    return unmlq(Side.Left, Op.ConjTrans, fac, T, Yfull, opts)
