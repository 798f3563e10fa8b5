"""Hermitian eigensolver family (reference: src/heev.cc, he2hb.cc,
hb2st.cc, sterf.cc, steqr.cc, stedc*.cc, unmtr_he2hb.cc, unmtr_hb2st.cc,
hegst.cc, hegv.cc; SURVEY §3.5).

Staging mirrors the reference:

  heev:  he2hb (dense -> band, distributed-capable, all the FLOPs)
         -> gather -> tridiagonal/eigen stage on one device.

The reference also runs stage 2+ on ONE node over a gathered band
(heev.cc:135 he2hbGather, hb2st threads+atomics) calling LAPACK
sterf/steqr/stedc; here the gathered stage calls the XLA eigensolver
(jnp.linalg.eigh — our L0 vendor-kernel layer, exactly as the reference
leans on LAPACK).  A native Pallas bulge-chaser is the planned
replacement (SURVEY §7 step 6).

he2hb is implemented as blocked two-sided Householder updates
(he2hb.cc:174-185's panel QR + trailing her2k-style update), using our
QR panel kernels; the back-transform unmtr_he2hb applies the stored
reflectors.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..enums import MethodEig, Norm, Op, Option, Side, Uplo
from ..exceptions import slate_assert
from ..matrix.base import BaseMatrix, conj_transpose
from ..matrix.matrix import HermitianMatrix, HermitianBandMatrix, Matrix, TriangularMatrix
from ..options import Options, get_option
from ..ops.householder import larft, materialize_v
from ..parallel.layout import TileLayout, tiles_from_global
from ..types import TriangularFactors
from . import blas3

from ..aux import metrics
from ..aux.metrics import instrumented
from ..internal.precision import accurate_matmul, hdot


from ..matrix.base import is_distributed as _is_distributed


def _size_bucket_runs(heights, total, floor=1024):
    """Group consecutive panel indices into size buckets: each height is
    assigned S = total / 2^m, the smallest halving of `total` that still
    covers it, floored at min(floor, total) so tiny tails don't multiply
    compiled bodies.  (Buckets are halvings of `total`, NOT pow2ceil(h):
    for total=6144 a height of 2500 buckets to 3072, not 4096.)
    Yields (i0, i1, S) runs; every height in [i0, i1) is <= S.

    The canonical implementation lives in serve/buckets.py — the
    serving layer's request buckets are the same halving lattice, so
    the rule is defined once (serve's __init__ is lazy; this import
    pulls only the pure buckets module, no cycle)."""
    from ..serve.buckets import size_bucket_runs

    return size_bucket_runs(heights, total, floor)


@accurate_matmul
@instrumented("he2hb")
def he2hb(
    A: HermitianMatrix, opts: Optional[Options] = None
) -> Tuple[HermitianBandMatrix, Matrix, TriangularFactors]:
    """Reduce Hermitian A to band form with bandwidth nb
    (reference: src/he2hb.cc: per-panel QR over panel ranks + two-sided
    trailing update).

    Distributed lower-Hermitian inputs run the shard_map panel pipeline
    (parallel/spmd_he2hb.py — panel gather + masked-einsum two-sided
    trailing update, no full_global(); the reference also restricts
    he2hb to Uplo::Lower, he2hb.cc:36).

    Returns (band, V, T): band Hermitian with kd = nb; V stores the block
    reflectors (panel k in tile column k, rows k+1..), T their compact-WY
    factors — the inputs of unmtr_he2hb."""
    slate_assert(A.m == A.n, "he2hb requires square")
    from jax import lax

    from ..ops.householder import _geqrf_panel

    lay = A.layout
    nb = lay.nb
    n = A.n

    if (
        _is_distributed(A)
        and get_option(opts, Option.UseShardMap)
        and A.uplo == Uplo.Lower
        and A.op == Op.NoTrans
        and lay.mb == lay.nb
    ):
        from ..parallel.spmd_he2hb import spmd_he2hb

        band_t, V_t, Tstack = spmd_he2hb(A.grid, A.data, lay)
        if lay.nt - 1 <= 0:
            Tstack = jnp.zeros((0, nb, nb), A.dtype)
        band = HermitianBandMatrix(
            band_t, lay, grid=A.grid, kd=nb, uplo=Uplo.Lower
        )
        return band, Matrix(V_t, lay, grid=A.grid), TriangularFactors(Tstack)

    if _is_distributed(A):
        from ..internal import fallbacks

        fallbacks.record(
            "he2hb", opts, "upper uplo / viewed / non-square tiles gather"
        )
    G = A.full_global()
    kt = lay.nt
    complex_t = A.is_complex

    def C(x):
        return jnp.conj(x) if complex_t else x

    # static-shape pipeline: every step works on the padded array with
    # the active trailing block rolled to the origin — one traced step
    # body per SIZE BUCKET under lax.fori_loop instead of kt unrolled
    # iterations (the reference's per-panel task loop,
    # he2hb.cc:174-185).  Steps whose trailing size h has shrunk crop
    # the rolled array to the _size_bucket_runs size S (the smallest
    # npad/2^m covering h): the full-array version ran
    # every trailing gemm at n x n regardless of h (3x the true flops
    # — measured 27 s of he2hb's 32 s at n=8192 on-chip; rolls and
    # panels are noise).  The update itself uses the LAPACK hetrd W
    # trick (W = P - V Q2/2, Q2 Hermitian) so the rank-2nb two-sided
    # update is ONE concat gemm instead of three rank-nb products.
    npad = kt * nb
    Gp = jnp.pad(G, ((0, npad - n), (0, npad - n)))
    Vs0 = jnp.zeros_like(Gp)
    Ts0 = jnp.zeros((max(kt - 1, 1), nb, nb), Gp.dtype)
    rows = jnp.arange(npad)

    def make_step(S):
        rows_S = jnp.arange(S)

        def step(k, carry):
            Gp, Vs, Ts = carry
            lo = (k + 1) * nb
            h = n - lo  # active trailing size (<= S; may be <= 0)
            # panel: rows lo.., column block k, rolled to the top
            colblk = lax.dynamic_slice(Gp, (0, k * nb), (npad, nb))
            pan = jnp.roll(colblk, -lo, axis=0)[:S]
            pan = jnp.where((rows_S < h)[:, None], pan, jnp.zeros_like(pan))
            vr, taus = _geqrf_panel(pan)
            V = materialize_v(vr, offset=0)  # (S, nb) unit-lower
            Tk = larft(V, taus)
            R = jnp.triu(vr)
            # write [R; 0] back into the panel and its Hermitian mirror
            newcol = jnp.zeros((npad, nb), Gp.dtype).at[:S].set(
                jnp.where((rows_S < h)[:, None], R, 0)
            )
            newcol = jnp.roll(newcol, lo, axis=0)
            keep_above = (rows < lo)[:, None]
            newcol = jnp.where(keep_above, colblk, newcol)
            Gp = lax.dynamic_update_slice(Gp, newcol, (0, k * nb))
            mirror = C(newcol).T  # (nb, npad)
            rowblk = lax.dynamic_slice(Gp, (k * nb, 0), (nb, npad))
            sel = (rows >= lo)[None, :]
            Gp = lax.dynamic_update_slice(
                Gp, jnp.where(sel, mirror, rowblk), (k * nb, 0)
            )
            # two-sided trailing update on the rolled, cropped A22
            G22 = jnp.roll(Gp, (-lo, -lo), (0, 1))
            act = (rows_S < h)[:, None] & (rows_S < h)[None, :]
            A22 = jnp.where(act, G22[:S, :S], 0)
            P = A22 @ (V @ Tk)
            Q2 = C(Tk).T @ (C(V).T @ P)
            W = P - V @ (0.5 * Q2)
            U1 = jnp.concatenate([V, W], axis=1)  # (S, 2nb)
            U2 = jnp.concatenate([W, V], axis=1)
            A22n = A22 - U1 @ C(U2).T
            G22 = G22.at[:S, :S].set(jnp.where(act, A22n, G22[:S, :S]))
            Gp = jnp.roll(G22, (lo, lo), (0, 1))
            # stash reflectors (global row coordinates)
            Vroll = jnp.roll(
                jnp.zeros((npad, nb), Gp.dtype).at[:S].set(
                    jnp.where((rows_S < h)[:, None], V, 0)
                ),
                lo,
                axis=0,
            )
            Vs = lax.dynamic_update_slice(Vs, Vroll, (0, k * nb))
            Ts = Ts.at[k].set(Tk)
            return Gp, Vs, Ts

        return step

    carry = (Gp, Vs0, Ts0)
    heights = [n - (k + 1) * nb for k in range(max(kt - 1, 0))]
    for k0, k1, S in _size_bucket_runs(heights, npad):
        carry = lax.fori_loop(k0, k1, make_step(S), carry)
    Gp, Vs_p, Tstack = carry
    G = Gp[:n, :n]
    Vs = Vs_p[:n, :n]
    if kt - 1 <= 0:
        Tstack = jnp.zeros((0, nb, nb), G.dtype)
    band = HermitianBandMatrix(
        tiles_from_global(G, lay), lay, grid=A.grid, kd=nb, uplo=A.uplo
    )
    Vm = Matrix(tiles_from_global(Vs, lay), lay, grid=A.grid)
    return band, Vm, TriangularFactors(Tstack)


@accurate_matmul
@instrumented("unmtr_he2hb")
def unmtr_he2hb(
    side: Side,
    op: Op,
    V: Matrix,
    T: TriangularFactors,
    C_mat: Matrix,
    opts: Optional[Options] = None,
) -> Matrix:
    """Apply the he2hb back-transform Q (reference: src/unmtr_he2hb.cc).

    Q = H_0 H_1 ... with H_k = I - V_k T_k V_k^H (V_k in tile column k,
    shifted one block down)."""
    lay = V.layout
    nb = lay.nb
    n = V.n
    kt = lay.nt

    if (
        _is_distributed(V)
        and get_option(opts, Option.UseShardMap)
        and side == Side.Left
        and V.op == Op.NoTrans
        and C_mat.op == Op.NoTrans
        and lay.mb == lay.nb
        and C_mat.layout.mb == lay.mb
    ):
        from ..parallel.spmd_he2hb import spmd_unmtr_he2hb_left

        if T.T.shape[0] == 0:
            return C_mat
        Ct = spmd_unmtr_he2hb_left(
            V.grid,
            V.data,
            T.T,
            C_mat.data,
            lay,
            C_mat.layout,
            trans=(op != Op.NoTrans),
        )
        return C_mat._with(data=Ct)

    from jax import lax

    if _is_distributed(V) or _is_distributed(C_mat):
        from ..internal import fallbacks

        fallbacks.record(
            "unmtr_he2hb", opts, "right side / op view / tile mismatch"
        )
    Vg = V.to_global()
    C2 = C_mat.to_global()
    complex_t = V.is_complex

    def CC(x):
        return jnp.conj(x) if complex_t else x

    npanels = T.T.shape[0]
    if npanels == 0:
        return C_mat
    forward = (side == Side.Left) == (op != Op.NoTrans)
    # one traced body under lax.fori_loop (compile time flat in the panel
    # count — the same static-shape batching as he2hb itself): V_k is the
    # full-height column block, zero above row (k+1) nb, so the masked
    # slice updates collapse into full-size matmuls.
    Vp = jnp.pad(Vg, ((0, 0), (0, max(kt * nb - Vg.shape[1], 0))))
    Ts = T.T

    nrows = C2.shape[0]

    def make_step(S):
        def step(i, C2):
            k = i if forward else npanels - 1 - i
            Tk = lax.dynamic_index_in_dim(Ts, k, 0, keepdims=False)
            Tm = CC(Tk).T if op != Op.NoTrans else Tk
            # the V^H C gram contracts over all n rows: at n >= 4096
            # the f64 emulation drops its compensation terms on such
            # products (an old record, not reproduced) — hdot k-chunks
            # them; this gram was the WHOLE heev orthogonality budget
            # at n=4096 (107 n eps from this stage vs 3.4 entering it)
            if side == Side.Left and S < nrows:
                # V_k lives in rows [lo, n): slice BOTH operands at the
                # same clamped origin and the panel support stays
                # aligned — the full-height version ran every product
                # at n x m regardless of the active height
                lo = (k + 1) * nb
                org = jnp.minimum(lo, nrows - S)
                Vk = lax.dynamic_slice(Vp, (org, k * nb), (S, nb))
                Cs = lax.dynamic_slice(C2, (org, 0), (S, C2.shape[1]))
                W = hdot(CC(Vk).T, Cs)
                Cs = Cs - Vk @ (Tm @ W)
                return lax.dynamic_update_slice(C2, Cs, (org, 0))
            Vk = lax.dynamic_slice_in_dim(Vp, k * nb, nb, axis=1)
            if side == Side.Left:
                W = hdot(CC(Vk).T, C2)
                return C2 - Vk @ (Tm @ W)
            W = hdot(C2, Vk)
            return C2 - (W @ Tm) @ CC(Vk).T

        return step

    if side == Side.Left:
        # size buckets over the active height h_k = n - (k+1) nb (the
        # same halving-of-total grouping as he2hb); loop index i maps to
        # panel idx[i] (reverse order for Q C)
        idx = list(range(npanels) if forward else range(npanels - 1, -1, -1))
        heights = [n - (idx[i] + 1) * nb for i in range(npanels)]
        for i0, i1, S in _size_bucket_runs(heights, nrows):
            C2 = lax.fori_loop(i0, i1, make_step(S), C2)
    else:
        C2 = lax.fori_loop(0, npanels, make_step(nrows), C2)
    return C_mat._with(data=tiles_from_global(C2.astype(C_mat.dtype), C_mat.layout))



def _gathered_band_eig(
    band_2d: jnp.ndarray, vectors: bool
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Stage 2+: eigensolve the gathered band matrix on one device via the
    XLA vendor eigensolver (reference analogue: gathered hb2st + LAPACK
    steqr/stedc on one node, heev.cc:135-180).

    On TPU f64 the vendor eigh stops ~1e-7 short of working precision;
    ops/jacobi.py's parallel-order Jacobi polish restores LAPACK-level
    accuracy (SURVEY §7 hard-part (5))."""
    from ..ops.jacobi import eigh_accurate

    return eigh_accurate(band_2d, vectors=vectors)


_STAGED_CACHE: dict = {}


@instrumented("heev_staged")
def heev_staged(
    A: HermitianMatrix,
    opts: Optional[Options] = None,
    vectors: bool = True,
):
    """Two-stage heev with PER-STAGE jits for large n (reference
    staging: src/heev.cc:123-210).

    One whole-problem jit's compile grows past n ~ 1024 (an old
    record, not reproduced), so the product path for large
    eigenproblems compiles the four stages separately — he2hb +
    band gather | hb2st (native host chaser when available, on-device
    wavefront otherwise) | tridiagonal eigensolve + hb2st
    back-transform | he2hb back-transform — and reuses the compiled
    stages across calls of the same shape.

    Returns (w, Z-or-None, stage_seconds)."""
    import jax

    from .. import native as _native
    from ..ops import bulge
    from ..parallel.band_gather import band_storage_tiles, spmd_band_storage

    n = A.n
    b = A.layout.nb
    if b < 2 or n <= 2 or n <= 4 * b:
        w, Z = heev(A, opts, vectors=vectors)
        return w, Z, {}
    n_pad = n + 4 * b + 8
    lay = A.layout
    use_spmd_gather = (
        _is_distributed(A)
        and get_option(opts, Option.UseShardMap)
        and lay.mb == lay.nb
    )
    host_ok = (
        not A.is_complex
        and A.dtype == jnp.float64
        and _native.hb2st_available()
    )
    adtype = A.dtype
    grid = A.grid
    opts_key = tuple(
        sorted((str(k), str(v)) for k, v in (opts or {}).items())
    )
    key = (
        n, b, str(adtype), lay.p, lay.q, vectors, use_spmd_gather,
        id(grid), opts_key,
    )
    stages = _STAGED_CACHE.get(key)
    if stages is None:
        # closures capture only scalars/layout/grid + opts — never the
        # input matrix (a captured A would pin its device buffers for
        # the cache's lifetime).  Each stage jit carries the f32/c64
        # precision policy (accurate_matmul applies during tracing) and
        # is metrics-instrumented: compile-vs-run split + cost_analysis
        # flops per stage under "heev.s*" names.

        def _s1_fn(A):
            band, V, T = he2hb(A, opts)
            if use_spmd_gather:
                W = spmd_band_storage(band.grid, band.data, band.layout, n_pad)
            else:
                W = band_storage_tiles(band.data, band.layout, n_pad)
            return W, V.data, T.T

        def _s3_fn(d, e, u, VS, TAUS):
            wv, ZT = steqr(d, e, vectors=True)
            Z2 = bulge.unmtr_hb2st(
                VS=VS, TAUS=TAUS, Z=(u[:, None] * ZT).astype(adtype),
                n=n, b=b,
            )
            return wv, Z2

        def _s4_fn(Vd, Ts, Zd):
            Z = unmtr_he2hb(
                Side.Left,
                Op.NoTrans,
                Matrix(Vd, lay, grid=grid),
                TriangularFactors(Ts),
                Matrix(Zd, lay, grid=grid),
                opts,
            )
            return Z.data

        _s1 = metrics.instrument_jit(
            jax.jit(accurate_matmul(_s1_fn)), "heev.s1_he2hb_gather"
        )
        _s2_chip = metrics.instrument_jit(
            jax.jit(accurate_matmul(bulge.hb2st), static_argnames=("n", "b")),
            "heev.s2_hb2st",
        )
        _s3 = metrics.instrument_jit(
            jax.jit(accurate_matmul(_s3_fn)), "heev.s3_stedc_unmtr_hb2st"
        )
        _s3v = metrics.instrument_jit(
            jax.jit(bulge.tridiag_eigvals_bisect), "heev.s3v_eigvals"
        )
        _s4 = metrics.instrument_jit(
            jax.jit(accurate_matmul(_s4_fn)), "heev.s4_unmtr_he2hb"
        )
        _pack = metrics.instrument_jit(
            jax.jit(lambda Z2: tiles_from_global(Z2, lay)), "heev.pack"
        )

        stages = (_s1, _s2_chip, _s3, _s3v, _s4, _pack)
        _STAGED_CACHE[key] = stages
    _s1, _s2_chip, _s3, _s3v, _s4, _pack = stages

    times = {}
    with metrics.phase("heev.he2hb+gather", always=True) as ph:
        W, Vd, Ts = jax.block_until_ready(_s1(A))
    times["he2hb+gather"] = round(ph.seconds, 2)
    with metrics.phase("heev.hb2st", always=True) as ph:
        if host_ok:
            W_h = np.asarray(W)
            metrics.inc("transfer.d2h_bytes", W_h.nbytes)
            d_h, e_h, VS, TAUS = _native.hb2st_host_device(W_h, n, b)
            d, e = jnp.asarray(d_h), jnp.asarray(e_h)
            u = jnp.ones((n,), A.dtype)
        else:
            d, e, u, VS, TAUS = _s2_chip(W, n, b)
        jax.block_until_ready((d, e, VS, TAUS))
    times["hb2st"] = round(ph.seconds, 2)
    if not vectors:
        with metrics.phase("heev.eigvals", always=True) as ph:
            w = jax.block_until_ready(_s3v(d, e))
        times["eigvals"] = round(ph.seconds, 2)
        return w, None, times
    with metrics.phase("heev.stedc+unmtr_hb2st", always=True) as ph:
        wv, Z2 = jax.block_until_ready(_s3(d, e, u, VS, TAUS))
    times["stedc+unmtr_hb2st"] = round(ph.seconds, 2)
    with metrics.phase("heev.unmtr_he2hb", always=True) as ph:
        Zd = jax.block_until_ready(_s4(Vd, Ts, _pack(Z2)))
    times["unmtr_he2hb"] = round(ph.seconds, 2)
    Z = Matrix(Zd, lay, grid=A.grid)
    return wv, Z, times


@accurate_matmul
@instrumented("heev")
def heev(
    A: HermitianMatrix,
    opts: Optional[Options] = None,
    vectors: bool = True,
) -> Tuple[jnp.ndarray, Optional[Matrix]]:
    """Hermitian eigendecomposition (reference: src/heev.cc two-stage:
    he2hb -> hb2st bulge chase -> tridiagonal eigensolve -> back-transform
    unmtr_hb2st + unmtr_he2hb, heev.cc:123-210).

    Returns (Lambda ascending, Z or None).  Stage 2 runs the wavefront
    bulge chase (ops/bulge.py) when the band is genuinely narrow
    (n > 4 nb); small problems dense-eigensolve the band directly.
    MethodEig.Bisection forces the two-stage chase + Sturm bisection."""
    import jax

    from ..ops import bulge
    from ..parallel.band_gather import band_storage_tiles, spmd_band_storage

    n = A.n
    b = A.layout.nb

    method = get_option(opts, Option.MethodEig, MethodEig.Auto)
    if isinstance(method, str):
        method = MethodEig.from_string(method)
    two_stage = b >= 2 and n > 2 and (
        method == MethodEig.Bisection or (method == MethodEig.Auto and n > 4 * b)
    )
    # large eager accelerator problems: per-stage jits (one whole-heev
    # jit's compile grows past n ~ 1024); decided BEFORE the he2hb
    # reduction so stage 1 runs exactly once.  Inside a jit trace this
    # re-dispatch is skipped and the whole path traces inline as before.
    if (
        two_stage
        and n >= 1024
        and n > 4 * b  # heev_staged's own guard; avoids a re-dispatch loop
        and not isinstance(A.data, jax.core.Tracer)
        and jax.default_backend() != "cpu"
    ):
        w, Z, _times = heev_staged(A, opts, vectors=vectors)
        return w, Z

    band, V, T = he2hb(A, opts)
    if two_stage:
        # band-limited stage gather (he2hbGather semantics): the packed
        # (2b+1, n_pad) chase storage is built straight from the <= 2
        # relevant tile diagonals — O(n kd) data, never the dense n x n
        # (reference: HermitianBandMatrix.hh:310, heev.cc:133-151)
        n_pad = n + 4 * b + 8
        if (
            _is_distributed(band)
            and get_option(opts, Option.UseShardMap)
            and band.layout.mb == band.layout.nb
        ):
            W = spmd_band_storage(band.grid, band.data, band.layout, n_pad)
        else:
            W = band_storage_tiles(band.data, band.layout, n_pad)
        # stage 2: the native host chaser when running eagerly on real
        # data (the reference's hb2st is likewise a CPU-threaded stage
        # over the gathered band, src/hb2st.cc:44-187); the jittable
        # on-device wavefront otherwise
        from .. import native as _native

        host_ok = (
            not isinstance(W, jax.core.Tracer)
            and not A.is_complex
            and W.dtype == jnp.float64
            and _native.hb2st_available()
        )
        if host_ok:
            W_h = np.asarray(W)
            metrics.inc("transfer.d2h_bytes", W_h.nbytes)
            d_h, e_h, VS, TAUS = _native.hb2st_host_device(W_h, n, b)
            d = jnp.asarray(d_h)
            e = jnp.asarray(e_h)
            u = jnp.ones((n,), A.dtype)
        else:
            d, e, u, VS, TAUS = bulge.hb2st(W, n, b)
        if not vectors:
            return bulge.tridiag_eigvals_bisect(d, e), None
        # tridiagonal stage with vectors (steqr role): dense vendor +
        # Jacobi polish on the (n x n) tridiagonal assembly
        w, ZT = steqr(d, e, vectors=True)
        Z2 = bulge.unmtr_hb2st(
            TAUS=TAUS, VS=VS, Z=(u[:, None] * ZT).astype(A.dtype), n=n, b=b
        )
    else:
        # rebuild the full Hermitian band from the stored triangle (the
        # spmd he2hb band carries the lower triangle only)
        w, Z2 = _gathered_band_eig(band.full_global(), vectors)
        if not vectors:
            return w, None
    Zm = Matrix(
        tiles_from_global(Z2.astype(A.dtype), A.layout), A.layout, grid=A.grid
    ).shard()
    # back-transform: Z = Q_he2hb Z_band (unmtr_he2hb, heev.cc:193-203)
    Z = unmtr_he2hb(Side.Left, Op.NoTrans, V, T, Zm, opts)
    return w, Z


@instrumented("sterf")
def sterf(d: jnp.ndarray, e: jnp.ndarray) -> jnp.ndarray:
    """Eigenvalues of a symmetric tridiagonal matrix, no vectors
    (reference: src/sterf.cc QL/QR iteration) — bisection with
    vectorized Sturm counts (ops/bulge.py), all eigenvalues in
    parallel: the TPU-native replacement for the sequential QL/QR."""
    from ..ops.bulge import tridiag_eigvals_bisect

    return tridiag_eigvals_bisect(jnp.real(d), jnp.real(e))


@instrumented("steqr")
def steqr(
    d: jnp.ndarray, e: jnp.ndarray, vectors: bool = True,
    method: str = "dc",
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Tridiagonal eigensolver (reference: src/steqr.cc implicit QR).

    Values-only runs the parallel Sturm bisection; with vectors, the
    native divide & conquer (ops/stedc.py) — no vendor eigensolver
    anywhere on the path (the vendor f64 eigh is a compile bomb past
    n~512 on this toolchain).  ``method="stein"`` takes the independent
    fallback pairing instead: Sturm-bisection eigenvalues + batched
    inverse-iteration vectors (ops/stein.py — the dstebz+dstein
    analogue, de-risking the D&C path)."""
    if not vectors:
        return sterf(d, e), None
    if method == "stein":
        from ..ops.bulge import tridiag_eigvals_bisect
        from ..ops.stein import stein as _stein

        dr, er = jnp.real(d), jnp.real(e)
        w = tridiag_eigvals_bisect(dr, er)
        return w, _stein(dr, er, w)
    return stedc(d, e, vectors=True)


@instrumented("stedc")
def stedc(
    d: jnp.ndarray, e: jnp.ndarray, vectors: bool = True
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Tridiagonal divide & conquer (reference: src/stedc.cc +
    stedc_deflate/merge/secular/solve/sort/z_vector).

    Native TPU redesign (ops/stedc.py): bottom-up Cuppen merge tree with
    every level's merges vmapped into one batch, vectorized laed4
    secular roots, masked static-shape deflation, Gu-Eisenstat Lowner
    z-vector, and MXU gemms for the back-rotations.  Values-only uses
    the parallel Sturm bisection (no tree needed)."""
    if not vectors:
        return sterf(d, e), None
    from ..ops.stedc import stedc as _stedc_dc

    w, Q = _stedc_dc(jnp.real(d), jnp.real(e))
    return w, Q


@accurate_matmul
@instrumented("hegst")
def hegst(
    itype: int,
    A: HermitianMatrix,
    L: TriangularMatrix,
    opts: Optional[Options] = None,
) -> HermitianMatrix:
    """Reduce the generalized problem to standard form (reference:
    src/hegst.cc + internal_hegst.cc): itype 1: C = L^-1 A L^-H;
    itype 2/3: C = L^H A L.

    Distributed itype-1 inputs run the SPMD composition
    (parallel/spmd_hegst.py): stored-triangle mirror assembly + the two
    column-pipeline trsm sweeps — no global gather."""
    from ..enums import Diag

    if (
        itype == 1
        and _is_distributed(A)
        and get_option(opts, Option.UseShardMap)
        and A.uplo == Uplo.Lower
        and A.op == Op.NoTrans
        and L.uplo == Uplo.Lower
        and L.op == Op.NoTrans
        and A.layout.mb == A.layout.nb
        and L.layout.mb == L.layout.nb
        and A.layout.nb == L.layout.nb
        and A.layout.nt == L.layout.nt
    ):
        from ..parallel.spmd_hegst import spmd_hegst_itype1

        Ct = spmd_hegst_itype1(
            A.grid,
            A.data,
            A.layout,
            L.data,
            L.layout,
            lower_a=True,
            unit_diag=(L.diag == Diag.Unit),
        )
        return HermitianMatrix(
            Ct, A.layout, grid=A.grid, uplo=Uplo.Lower
        )

    from ..ops import blas2d

    if _is_distributed(A) or _is_distributed(L):
        from ..internal import fallbacks

        fallbacks.record(
            "hegst", opts, "itype 2/3 / upper uplo / op view gather"
        )
    Ag = A.full_global()
    Lg = L._with(op=Op.NoTrans).to_global()
    if itype == 1:
        Y = blas2d.trsm2d(Side.Left, L.uplo, Op.NoTrans, L.diag, 1.0, Lg, Ag)
        Ch = blas2d.trsm2d(
            Side.Right, L.uplo, Op.ConjTrans, L.diag, 1.0, Lg, Y
        )
    else:
        LH = jnp.conj(Lg).T if A.is_complex else Lg.T
        Ch = LH @ Ag @ Lg
    return HermitianMatrix.from_global(
        Ch, A.layout.mb, A.layout.nb, grid=A.grid, uplo=A.uplo
    )


@accurate_matmul
@instrumented("hegv")
def hegv(
    itype: int,
    A: HermitianMatrix,
    B: HermitianMatrix,
    opts: Optional[Options] = None,
    vectors: bool = True,
) -> Tuple[jnp.ndarray, Optional[Matrix], jnp.ndarray]:
    """Generalized Hermitian-definite eigenproblem (reference: src/hegv.cc:
    potrf(B) + hegst + heev + triangular back-transform).

    itype 1: A x = lambda B x.  Returns (Lambda, X or None, info)."""
    from . import chol

    L, info = chol.potrf(B, opts)
    C = hegst(itype, A, L, opts)
    w, Z = heev(C, opts, vectors=vectors)
    if not vectors:
        return w, None, info
    # x = L^-H y (itype 1)
    X = blas3.trsm(Side.Left, 1.0, conj_transpose(L), Z, opts)
    return w, X, info


def sygv(itype, A, B, opts=None, vectors=True):
    """Real-symmetric alias of hegv (reference: hegv covers sygv)."""
    return hegv(itype, A, B, opts, vectors)
