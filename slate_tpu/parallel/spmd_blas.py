"""Explicit SPMD BLAS3 over the device mesh (shard_map + ICI collectives).

TPU-native replacement for the reference's SUMMA gemm with MPI tile
broadcasts (reference: src/gemmC.cc:76-201 impl::gemmC — per-k listBcastMT
of A's column k along process rows and B's row k along process columns,
then one batched device gemm per step; internal_gemm.cc:355-518).

The mapping (SURVEY §2.5):
  * tile broadcast along a process row/col  -> lax.all_gather over the
    'q'/'p' mesh sub-axis + static owner select (rides ICI),
  * per-device batched BLAS over local tiles -> one einsum over the local
    (mtl, ntl, mb, nb) tile stack,
  * the OpenMP lookahead pipeline            -> software pipelining in the
    lax.fori_loop carry: the gather for step k+1 is issued before the
    step-k einsum, letting XLA overlap communication with compute.

Everything is static-shape: the k-loop runs over global tile indices with
dynamic_slice into the cyclic local slots (slot = k // q on owner k % q).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.grid import COL_AXIS, ROW_AXIS, ProcessGrid
from ..parallel.layout import TileLayout

from ..aux.metrics import instrumented

#: jax.shard_map with the varying-manual-axes check off: our SPMD kernels
#: mix collective-produced and replicated values in loop carries, which
#: the checker rejects despite being well-defined
shard_map = partial(jax.shard_map, check_vma=False)


def _acc_dtype(dt):
    if jnp.issubdtype(dt, jnp.complexfloating):
        return dt
    return jnp.promote_types(dt, jnp.float32)


@instrumented("spmd.summa_gemm")
def summa_gemm(
    grid: ProcessGrid,
    alpha,
    TA: jnp.ndarray,
    layA: TileLayout,
    TB: jnp.ndarray,
    layB: TileLayout,
    beta,
    TC: jnp.ndarray,
    layC: TileLayout,
) -> jnp.ndarray:
    """C = alpha A B + beta C over storage-order tile arrays on the mesh.

    A: m x k tiles (mb x kb), B: k x n tiles (kb x nb), C: m x n (mb x nb),
    all on the same p x q grid.  Returns C's new tile array.
    """
    p, q = grid.p, grid.q
    kt_total = layA.nt
    assert layB.mt == kt_total, "A/B tile-k mismatch"
    acc_t = _acc_dtype(TC.dtype)

    def local(ta, tb, tc):
        # local shards: ta (mtl, ktlA, mb, kb), tb (ktlB, ntl, kb, nb),
        # tc (mtl, ntl, mb, nb)
        def gather_k(kt):
            a_slice = lax.dynamic_slice_in_dim(ta, kt // q, 1, axis=1)
            a_all = lax.all_gather(a_slice, COL_AXIS)  # (q, mtl, 1, mb, kb)
            a_col = lax.dynamic_index_in_dim(a_all, kt % q, 0, keepdims=False)[:, 0]
            b_slice = lax.dynamic_slice_in_dim(tb, kt // p, 1, axis=0)
            b_all = lax.all_gather(b_slice, ROW_AXIS)  # (p, 1, ntl, kb, nb)
            b_row = lax.dynamic_index_in_dim(b_all, kt % p, 0, keepdims=False)[0]
            return a_col, b_row  # (mtl, mb, kb), (ntl, kb, nb)

        def step(kt, carry):
            acc, (a_col, b_row) = carry
            nxt = gather_k(kt + 1)  # issued before the einsum: lookahead
            upd = jnp.einsum(
                "iak,jkb->ijab", a_col, b_row, preferred_element_type=acc_t
            )
            return acc + upd, nxt

        acc0 = jnp.zeros(tc.shape, acc_t)
        acc, _ = lax.fori_loop(0, kt_total, step, (acc0, gather_k(0)))
        out = alpha * acc + beta * tc.astype(acc_t)
        return out.astype(tc.dtype)

    spec = P(ROW_AXIS, COL_AXIS)
    fn = shard_map(
        local,
        mesh=grid.mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(TA, TB, TC)


def gemm_reduce_a(
    grid: ProcessGrid,
    alpha,
    TA: jnp.ndarray,
    layA: TileLayout,
    TB: jnp.ndarray,
    layB: TileLayout,
    beta,
    TC: jnp.ndarray,
    layC: TileLayout,
) -> jnp.ndarray:
    """Stationary-A gemm (reference: src/gemmA.cc + internal_gemmA.cc):
    each process multiplies its local A tiles by gathered B and the partial
    C contributions are tree-reduced — here a psum_scatter over the 'q'
    axis (SURVEY §2.5 tile-reduce -> psum_scatter).

    Chosen by method auto when k is small relative to m (A tall, C small),
    mirroring gemm.cc:12-24's selection.
    """
    p, q = grid.p, grid.q
    kt_total = layA.nt
    acc_t = _acc_dtype(TC.dtype)
    ntl = layC.ntl
    ktlB = layB.mtl

    def local(ta, tb, tc):
        # Replicate B (the reference broadcasts B's rows in gemmA; B/C are
        # narrow when method A is selected).  Two gathers rebuild B's full
        # storage-order tile array on every process.
        b_p = lax.all_gather(tb, ROW_AXIS)  # (p, ktlB, ntlB, kb, nb)
        b_p = b_p.reshape((p * ktlB,) + tb.shape[1:])  # owner-major == storage
        b_full = lax.all_gather(b_p, COL_AXIS)  # (q, p*ktlB, ntlB, kb, nb)
        b_full = jnp.moveaxis(b_full, 0, 1).reshape(
            p * ktlB, q * tb.shape[1], *tb.shape[2:]
        )  # (p*ktlB, q*ntlB, kb, nb) storage order

        def step(kt, acc):
            # local A column kt (valid only on owner column kt % q)
            a_col = lax.dynamic_slice_in_dim(ta, kt // q, 1, axis=1)[:, 0]
            # full B row kt from the replicated copy (storage row slot)
            b_row = lax.dynamic_index_in_dim(
                b_full, (kt % p) * ktlB + kt // p, 0, keepdims=False
            )  # (q*ntlB, kb, nb)
            is_owner = lax.axis_index(COL_AXIS) == (kt % q)
            upd = jnp.einsum(
                "iak,jkb->ijab", a_col, b_row, preferred_element_type=acc_t
            )
            return acc + jnp.where(is_owner, upd, jnp.zeros_like(upd))

        # partial over ALL C columns (storage order), then reduce-scatter
        # over 'q' so each process keeps the sum for its own column slots
        # (reference: gemmA's reverse-tree tile reduce -> psum_scatter).
        part = lax.fori_loop(
            0, kt_total, step,
            jnp.zeros((tc.shape[0], q * ntl) + tc.shape[2:], acc_t),
        )
        total = lax.psum_scatter(part, COL_AXIS, scatter_dimension=1, tiled=True)
        out = alpha * total + beta * tc.astype(acc_t)
        return out.astype(tc.dtype)

    spec = P(ROW_AXIS, COL_AXIS)
    fn = shard_map(local, mesh=grid.mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(TA, TB, TC)


@instrumented("spmd.herk")
def spmd_herk(
    grid: ProcessGrid,
    alpha,
    TA: jnp.ndarray,
    layA: TileLayout,
    beta,
    TC: jnp.ndarray,
    layC: TileLayout,
    conj: bool,
    trans: bool,
    alpha2=None,
    TB: jnp.ndarray = None,
    layB: TileLayout = None,
    lower: bool = True,
) -> jnp.ndarray:
    """Rank-k update C = alpha op(A) op(A)^(H|T) + beta C directly from
    A's stored tiles (reference: src/herk.cc + internal_herk.cc's batched
    symmetric update).

    Unlike routing through summa_gemm, no transposed copy of A is ever
    materialized (a resolved A^H lives on the TRANSPOSED process grid —
    unusable for p != q meshes) and C needs no Hermitian mirror: per step
    k the full tile column (trans=False) or tile row (trans=True) of A is
    rebuilt on every process by two all_gathers.  With TB given this is
    the rank-2k her2k/syr2k: alpha A B^H + alpha2 B A^H + beta C.

    Triangle-aware accumulation (internal::herk touches stored tiles
    only): each process enumerates its local STORED-triangle tile pairs
    (a static-size packed list, indices traced from the mesh
    coordinates), accumulates the rank-k updates as one batched matmul
    over that packed list per step — half the all-pairs FLOPs — and
    scatters into the tile array once at the end.  Non-stored local
    tiles come back as beta * C only (the Hermitian wrapper never
    references them).
    """
    p, q = grid.p, grid.q
    kt_total = layA.mt if trans else layA.nt
    mtl, ntl = layC.mtl, layC.ntl
    rank2 = TB is not None
    acc_t = _acc_dtype(TC.dtype)
    complex_t = jnp.issubdtype(TC.dtype, jnp.complexfloating)
    row_scatter = jnp.asarray(layA.row_scatter)
    col_scatter = jnp.asarray(layA.col_scatter)

    # static upper bound of stored-triangle local pairs over all
    # processes (the packed batch size; per-process indices are traced)
    npairs = 0
    for rr in range(p):
        for cc in range(q):
            gi_ = np.arange(mtl) * p + rr
            gj_ = np.arange(ntl) * q + cc
            st = (
                (gi_[:, None] >= gj_[None, :])
                if lower
                else (gi_[:, None] <= gj_[None, :])
            )
            st &= (gi_[:, None] < layC.mt) & (gj_[None, :] < layC.nt)
            npairs = max(npairs, int(st.sum()))
    npairs = max(npairs, 1)

    def cj(x):
        return jnp.conj(x) if (conj and complex_t) else x

    def local(ta, tc, *tbs):
        r = lax.axis_index(ROW_AXIS)
        c = lax.axis_index(COL_AXIS)
        gi = jnp.arange(mtl) * p + r
        gj = jnp.arange(ntl) * q + c

        stored = (
            (gi[:, None] >= gj[None, :])
            if lower
            else (gi[:, None] <= gj[None, :])
        )
        stored &= (gi[:, None] < layC.mt) & (gj[None, :] < layC.nt)
        flat = stored.reshape(-1)
        order = jnp.argsort(~flat, stable=True)[:npairs]
        I_idx = order // ntl
        J_idx = order % ntl
        slot_ok = flat[order]  # False on padding slots (non-stored)

        def gather_col(t, k):
            # tile column k in NATURAL tile-row order: (layA.P, mb, kb)
            loc = lax.dynamic_slice_in_dim(t, k // q, 1, axis=1)[:, 0]
            aq = lax.all_gather(loc, COL_AXIS)
            rows = lax.dynamic_index_in_dim(aq, k % q, 0, keepdims=False)
            full = lax.all_gather(rows, ROW_AXIS)
            return full.reshape((layA.P,) + full.shape[2:])[row_scatter]

        def gather_row(t, k):
            # tile row k in NATURAL tile-col order: (layA.Q, kb, nb)
            loc = lax.dynamic_slice_in_dim(t, k // p, 1, axis=0)[0]
            ap = lax.all_gather(loc, ROW_AXIS)
            cols = lax.dynamic_index_in_dim(ap, k % p, 0, keepdims=False)
            full = lax.all_gather(cols, COL_AXIS)
            return full.reshape((layA.Q,) + full.shape[2:])[col_scatter]

        def panels(k):
            if trans:
                pa = gather_row(ta, k)
                pb = gather_row(tbs[0], k) if rank2 else pa
            else:
                pa = gather_col(ta, k)
                pb = gather_col(tbs[0], k) if rank2 else pa
            return pa, pb

        gi_p = gi[I_idx]  # global tile rows of the packed pairs
        gj_p = gj[J_idx]

        def tile_upd(pl, pr):
            # packed batch: C_pair += op(L)_i,k op(R)_j,k^(H|T) over the
            # stored-triangle pairs only (half the all-pairs FLOPs)
            if trans:
                # op(M)_{i,k} = M_{k,i}^(H|T): contraction over panel rows
                return jnp.einsum(
                    "pca,pcb->pab", cj(pl[gi_p]), pr[gj_p],
                    preferred_element_type=acc_t,
                )
            return jnp.einsum(
                "pak,pbk->pab", pl[gi_p], cj(pr[gj_p]),
                preferred_element_type=acc_t,
            )

        def apply(acc, pa, pb):
            if rank2:
                return acc + alpha * tile_upd(pa, pb) + alpha2 * tile_upd(pb, pa)
            return acc + alpha * tile_upd(pa, pa)

        acc = jnp.zeros((npairs,) + tc.shape[2:], acc_t)

        def step(k, carry):
            acc, (pa, pb) = carry
            nxt = panels(k + 1)  # lookahead: gather before the einsum
            return apply(acc, pa, pb), nxt

        if kt_total > 0:
            # loop stops one short so the lookahead never gathers an
            # out-of-range panel; the last panel applies after the loop
            acc, (pa, pb) = lax.fori_loop(
                0, kt_total - 1, step, (acc, panels(0))
            )
            acc = apply(acc, pa, pb)
        # one scatter back to tile-array form (padding slots zeroed; a
        # duplicate padding pair can only target a non-stored tile)
        acc = jnp.where(slot_ok[:, None, None], acc, 0)
        acc_full = (
            jnp.zeros(tc.shape, acc_t).at[I_idx, J_idx].add(acc)
        )
        out = acc_full + beta * tc.astype(acc_t)
        return out.astype(tc.dtype)

    spec = P(ROW_AXIS, COL_AXIS)
    args = (TA, TC) + ((TB,) if rank2 else ())
    fn = shard_map(
        local,
        mesh=grid.mesh,
        in_specs=(spec,) * len(args),
        out_specs=spec,
    )
    return fn(*args)


@instrumented("spmd.trmm")
def spmd_trmm(
    grid: ProcessGrid,
    side_left: bool,
    alpha,
    TA: jnp.ndarray,
    layA: TileLayout,
    lower: bool,
    unit_diag: bool,
    opa_trans: bool,
    opa_conj: bool,
    TB: jnp.ndarray,
    layB: TileLayout,
) -> jnp.ndarray:
    """Triangular multiply B <- alpha op(A) B (side_left) or
    alpha B op(A) over the mesh (reference: src/trmm.cc ->
    work::trmm's in-place pipeline, src/work/work_trmm.cc).

    Being functional, there is no in-place aliasing hazard to pipeline
    around: per step k the needed panel of op(A) is rebuilt (masked to
    the referenced triangle elementwise, honoring Diag::Unit) and B's
    block row/column k is psum-broadcast from its owner — a SUMMA over
    a triangular operand.  `lower`/`unit_diag` describe A's STORAGE
    triangle; `opa_trans`/`opa_conj` the view being multiplied.
    """
    p, q = grid.p, grid.q
    assert layA.m == layA.n and layA.mb == layA.nb
    mb = layA.mb
    nt = layA.nt
    n = layA.n
    mtlA, ntlA = layA.mtl, layA.ntl
    mtlB, ntlB = layB.mtl, layB.ntl
    acc_t = _acc_dtype(TB.dtype)
    complex_t = jnp.issubdtype(TB.dtype, jnp.complexfloating)
    row_scatter = jnp.asarray(layA.row_scatter)
    col_scatter = jnp.asarray(layA.col_scatter)

    def cjA(x):
        return jnp.conj(x) if (opa_conj and complex_t) else x

    def tri_mask_panel(pan, k, panel_is_col):
        """Mask gathered panel tiles to A's stored triangle (elementwise,
        with Diag::Unit substitution and padding zeroed)."""
        t = jnp.arange(pan.shape[0])
        a = jnp.arange(mb)
        if panel_is_col:  # pan[t] = A(t, k): rows t*mb+a, cols k*mb+b
            gr = (t[:, None, None] * mb + a[:, None])
            gc = (k * mb + a)[None, None, :]
        else:  # pan[t] = A(k, t): rows k*mb+a, cols t*mb+b
            gr = (k * mb + a)[None, :, None]
            gc = (t[:, None, None] * mb + a[None, None, :])
        keep = (gr >= gc) if lower else (gr <= gc)
        if unit_diag:
            keep = keep & (gr != gc)
        keep = keep & (gr < n) & (gc < n)
        out = jnp.where(keep, pan, jnp.zeros_like(pan))
        if unit_diag:
            out = out + jnp.where(
                (gr == gc) & (gr < n),
                jnp.ones_like(pan),
                jnp.zeros_like(pan),
            )
        return out

    def local(ta, tb):
        r = lax.axis_index(ROW_AXIS)
        c = lax.axis_index(COL_AXIS)
        gi = jnp.arange(mtlB) * p + r
        gj = jnp.arange(ntlB) * q + c

        def gather_colA(k):
            loc = lax.dynamic_slice_in_dim(ta, k // q, 1, axis=1)[:, 0]
            aq = lax.all_gather(loc, COL_AXIS)
            rows = lax.dynamic_index_in_dim(aq, k % q, 0, keepdims=False)
            full = lax.all_gather(rows, ROW_AXIS)
            return full.reshape(p * mtlA, mb, mb)[row_scatter]

        def gather_rowA(k):
            loc = lax.dynamic_slice_in_dim(ta, k // p, 1, axis=0)[0]
            ap = lax.all_gather(loc, ROW_AXIS)
            cols = lax.dynamic_index_in_dim(ap, k % p, 0, keepdims=False)
            full = lax.all_gather(cols, COL_AXIS)
            return full.reshape(q * ntlA, mb, mb)[col_scatter]

        def opA_col(k):
            """op(A)'s tile column k, natural order, triangle-masked."""
            if not opa_trans:
                return cjA(tri_mask_panel(gather_colA(k), k, True))
            pan = tri_mask_panel(gather_rowA(k), k, False)  # A(k, t)
            return cjA(jnp.swapaxes(pan, -1, -2))

        def opA_row(k):
            """op(A)'s tile row k, natural order, triangle-masked."""
            if not opa_trans:
                return cjA(tri_mask_panel(gather_rowA(k), k, False))
            pan = tri_mask_panel(gather_colA(k), k, True)  # A(t, k)
            return cjA(jnp.swapaxes(pan, -1, -2))

        def step(k, acc):
            if side_left:
                # acc(i, :) += op(A)(gi, k) B(k, :)
                pan = opA_col(k)[gi]
                b_row = lax.dynamic_index_in_dim(tb, k // p, 0, keepdims=False)
                own = r == (k % p)
                b_row = lax.psum(
                    jnp.where(own, b_row, jnp.zeros_like(b_row)), ROW_AXIS
                )
                upd = jnp.einsum(
                    "iab,jbc->ijac", pan, b_row, preferred_element_type=acc_t
                )
            else:
                # acc(:, j) += B(:, k) op(A)(k, gj)
                pan = opA_row(k)[gj]
                b_col = lax.dynamic_slice_in_dim(tb, k // q, 1, axis=1)[:, 0]
                own = c == (k % q)
                b_col = lax.psum(
                    jnp.where(own, b_col, jnp.zeros_like(b_col)), COL_AXIS
                )
                upd = jnp.einsum(
                    "iab,jbc->ijac", b_col, pan, preferred_element_type=acc_t
                )
            return acc + upd

        acc = lax.fori_loop(0, nt, step, jnp.zeros(tb.shape, acc_t))
        return (alpha * acc).astype(tb.dtype)

    spec = P(ROW_AXIS, COL_AXIS)
    fn = shard_map(local, mesh=grid.mesh, in_specs=(spec, spec), out_specs=spec)
    return fn(TA, TB)


@instrumented("spmd.hemm")
def spmd_hemm(
    grid: ProcessGrid,
    side_left: bool,
    alpha,
    TA: jnp.ndarray,
    layA: TileLayout,
    lower: bool,
    TB: jnp.ndarray,
    layB: TileLayout,
    beta,
    TC: jnp.ndarray,
    layC: TileLayout,
    hermitian: bool = True,
) -> jnp.ndarray:
    """C = alpha A B + beta C (side_left) or alpha B A + beta C, with A
    Hermitian and ONE triangle stored (reference: src/hemmA.cc's
    broadcast/reduce DAG).

    SUMMA over k where the op-full tile column (or row) k of A is
    assembled on the fly from the stored triangle: the stored tile
    column supplies the stored side of the diagonal and the stored tile
    ROW supplies the mirror A(i, k) = A(k, i)^H on the other side — two
    panel gathers per step, no global mirror round trip (the previous
    implementation materialized full_global())."""
    p, q = grid.p, grid.q
    mb = layA.mb
    nt = layA.nt
    n = layA.n
    mtlA, ntlA = layA.mtl, layA.ntl
    mtlB, ntlB = layB.mtl, layB.ntl
    acc_t = _acc_dtype(TC.dtype)
    complex_t = jnp.issubdtype(TC.dtype, jnp.complexfloating)
    row_scatter = jnp.asarray(layA.row_scatter)
    col_scatter = jnp.asarray(layA.col_scatter)

    def cj(x):
        # the mirror conjugates for Hermitian A only: complex SYMMETRIC
        # operands (symm) mirror without conjugation
        return jnp.conj(x) if (complex_t and hermitian) else x

    def local(ta, tb, tc):
        r = lax.axis_index(ROW_AXIS)
        c = lax.axis_index(COL_AXIS)
        gi = jnp.arange(layC.mtl) * p + r
        gj = jnp.arange(layC.ntl) * q + c

        def gather_colA(k):
            loc = lax.dynamic_slice_in_dim(ta, k // q, 1, axis=1)[:, 0]
            aq = lax.all_gather(loc, COL_AXIS)
            rows = lax.dynamic_index_in_dim(aq, k % q, 0, keepdims=False)
            full = lax.all_gather(rows, ROW_AXIS)
            return full.reshape(p * mtlA, mb, mb)[row_scatter]

        def gather_rowA(k):
            loc = lax.dynamic_slice_in_dim(ta, k // p, 1, axis=0)[0]
            ap = lax.all_gather(loc, ROW_AXIS)
            cols = lax.dynamic_index_in_dim(ap, k % p, 0, keepdims=False)
            full = lax.all_gather(cols, COL_AXIS)
            return full.reshape(q * ntlA, mb, mb)[col_scatter]

        t_idx_r = jnp.arange(layA.P)
        t_idx_c = jnp.arange(layA.Q)
        a_el = jnp.arange(mb)

        def realify_diag(panel, gr, gc):
            # zhemm contract: the Hermitian diagonal's imaginary parts
            # "need not be set" — drop them (full_global did the same)
            if not (complex_t and hermitian):
                return panel
            return jnp.where(
                gr == gc, jnp.real(panel).astype(panel.dtype), panel
            )

        def herm_col(k):
            """Op-full tile column k of Hermitian A, natural order."""
            colp = gather_colA(k)
            rowp = _resize_rows_3d(gather_rowA(k), layA.P)
            mirror = cj(jnp.swapaxes(rowp, -1, -2))
            gr = t_idx_r[:, None, None] * mb + a_el[:, None]
            gc = k * mb + a_el[None, None, :]
            from_stored = (gr >= gc) if lower else (gr <= gc)
            valid = (gr < n) & (gc < n)
            out = jnp.where(valid & from_stored, colp, 0) + jnp.where(
                valid & ~from_stored, mirror, 0
            )
            return realify_diag(out, gr, gc)

        def herm_row(k):
            """Op-full tile row k of Hermitian A, natural order."""
            rowp = gather_rowA(k)
            colp = _resize_rows_3d(gather_colA(k), layA.Q)
            mirror = cj(jnp.swapaxes(colp, -1, -2))
            gr = k * mb + a_el[None, :, None]
            gc = t_idx_c[:, None, None] * mb + a_el[None, None, :]
            from_stored = (gr >= gc) if lower else (gr <= gc)
            valid = (gr < n) & (gc < n)
            out = jnp.where(valid & from_stored, rowp, 0) + jnp.where(
                valid & ~from_stored, mirror, 0
            )
            return realify_diag(out, gr, gc)

        def step(k, acc):
            if side_left:
                a_col = herm_col(k)[gi]
                b_row = lax.dynamic_slice_in_dim(tb, k // p, 1, axis=0)[0]
                own = r == (k % p)
                b_row = lax.psum(
                    jnp.where(own, b_row, jnp.zeros_like(b_row)), ROW_AXIS
                )
                upd = jnp.einsum(
                    "iab,jbc->ijac", a_col, b_row,
                    preferred_element_type=acc_t,
                )
            else:
                a_row = herm_row(k)[gj]
                b_col = lax.dynamic_slice_in_dim(tb, k // q, 1, axis=1)[:, 0]
                own = c == (k % q)
                b_col = lax.psum(
                    jnp.where(own, b_col, jnp.zeros_like(b_col)), COL_AXIS
                )
                upd = jnp.einsum(
                    "iab,jbc->ijac", b_col, a_row,
                    preferred_element_type=acc_t,
                )
            return acc + upd

        acc = lax.fori_loop(0, nt, step, jnp.zeros(tc.shape, acc_t))
        out = alpha * acc + beta * tc.astype(acc_t)
        return out.astype(tc.dtype)

    spec = P(ROW_AXIS, COL_AXIS)
    fn = shard_map(
        local, mesh=grid.mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    return fn(TA, TB, TC)


def _resize_rows_3d(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    if x.shape[0] == rows:
        return x
    if x.shape[0] > rows:
        return x[:rows]
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0), (0, 0)))
