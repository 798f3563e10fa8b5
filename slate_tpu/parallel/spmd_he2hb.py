"""Distributed two-stage eigenreduction stage 1: he2hb over the mesh.

TPU-native re-design of the reference he2hb driver (reference:
src/he2hb.cc:98-185 — per panel k: internal::geqrf of the subdiagonal
panel over the panel's process column, tileBcast of V/T, then the
two-sided trailing update assembled from internal::he2hb_hemm /
he2hb_her2k_offdiag / he2hb_gemm tasks; SURVEY §3.5).  The reference
asserts Uplo::Lower (he2hb.cc:36); so does this pipeline.

The mesh schedule per panel k (one lax.fori_loop body, static shapes):

1. the subdiagonal panel column is rebuilt on every process by two
   all_gathers (the panel-gather strategy shared with spmd_chol/lu/qr)
   and factored redundantly — panel FLOPs are O(n nb^2) per step,
   negligible next to the O(h^2 nb) trailing update;
2. the Hermitian product P = A22 (V T) is evaluated from the *stored
   lower triangle only*: each stored tile A_ij (i >= j) contributes
   A_ij W_j to P_i and, for i > j, A_ij^H W_i to P_j — two masked
   einsums over the local tile stack + a scatter-add into natural tile
   order + psum over both mesh axes (the reference's he2hb_hemm tile
   reduce, internal_he2hb_hemm.cc);
3. the rank-2b two-sided update A22 -= V P^H + P V^H - V (T^H V^H P) V^H
   is applied tile-locally to the stored lower triangle from the
   replicated V, P (the he2hb_her2k/gemm task group);
4. R overwrites the panel column on its owner; V is stashed into its own
   distributed tile array for unmtr_he2hb.

No full_global() anywhere: the only cross-device traffic is the panel
gather and the P psum, both O(n nb) per step over ICI.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..internal.precision import KCHUNK, emulated_f64
from ..ops.householder import geqrf as _geqrf_kernel, larft
from ..parallel.grid import COL_AXIS, ROW_AXIS, ProcessGrid
from ..parallel.layout import TileLayout
from .spmd_blas import shard_map

from ..aux.metrics import instrumented


@instrumented("spmd.he2hb")
def spmd_he2hb(
    grid: ProcessGrid, T: jnp.ndarray, layout: TileLayout
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Reduce a lower-Hermitian storage tile array to band form (kd = nb).

    T: (P, Q, mb, mb) storage-order tiles; only the lower triangle
    (global element row >= col) is referenced.  Returns
    (band_tiles, V_tiles, Tstack): band_tiles hold the Hermitian band in
    the lower triangle (diagonal blocks + subdiagonal R blocks),
    V_tiles store panel k's reflectors in tile column k (rows k+1..),
    Tstack is (kt-1 or 1, nb, nb) replicated compact-WY factors.
    """
    p, q = grid.p, grid.q
    mb = layout.mb
    assert mb == layout.nb, "he2hb requires square tiles"
    n = layout.n
    kt = layout.nt
    mtl, ntl = layout.mtl, layout.ntl
    m_pad = layout.P * mb
    nsteps = max(kt - 1, 0)
    row_scatter = jnp.asarray(layout.row_scatter)
    row_gather = jnp.asarray(layout.row_gather)
    complex_t = jnp.issubdtype(T.dtype, jnp.complexfloating)

    def conj(x):
        return jnp.conj(x) if complex_t else x

    def local(tl):
        r = lax.axis_index(ROW_AXIS)
        c = lax.axis_index(COL_AXIS)
        gi = jnp.arange(mtl) * p + r  # global tile rows of local slots
        gj = jnp.arange(ntl) * q + c
        g_rows = jnp.arange(m_pad, dtype=jnp.int32)
        # elementwise global coordinates of the local shard
        er = gi[:, None] * mb + jnp.arange(mb)[None, :]  # (mtl, mb)
        ec = gj[:, None] * mb + jnp.arange(mb)[None, :]  # (ntl, mb)
        low_el = er[:, None, :, None] >= ec[None, :, None, :]
        slow_el = er[:, None, :, None] > ec[None, :, None, :]

        def step(k, carry):
            tl, Vs, Ts = carry
            lo = (k + 1) * mb
            active_len = n - lo

            # -- 1. gather subdiagonal panel column k ---------------------
            pan_loc = lax.dynamic_slice_in_dim(tl, k // q, 1, axis=1)[:, 0]
            pan_q = lax.all_gather(pan_loc, COL_AXIS)
            pan_rows = lax.dynamic_index_in_dim(pan_q, k % q, 0, keepdims=False)
            pan_full = lax.all_gather(pan_rows, ROW_AXIS).reshape(p * mtl, mb, mb)
            panel2d = pan_full[row_scatter].reshape(m_pad, mb)
            pact = jnp.roll(panel2d, -lo, axis=0)
            pact = jnp.where((g_rows < active_len)[:, None], pact, 0)

            # -- 2. redundant panel QR + T ------------------------------
            vr, taus = _geqrf_kernel(pact)
            rows_ = g_rows[:, None]
            cols_ = jnp.arange(mb)[None, :]
            V_act = jnp.where(rows_ > cols_, vr, 0) + jnp.where(
                rows_ == cols_, jnp.ones_like(vr), 0
            )
            V_act = jnp.where((g_rows < active_len)[:, None], V_act, 0)
            Tk = larft(V_act, taus)
            Ts = lax.dynamic_update_index_in_dim(
                Ts, Tk.astype(Ts.dtype), k, 0
            )

            # -- 3. write [R; 0] back on the panel's owner column --------
            R2d = jnp.roll(
                jnp.where((g_rows < active_len)[:, None], jnp.triu(vr), 0),
                lo,
                axis=0,
            )
            fac_st = R2d.reshape(layout.P, mb, mb)[row_gather]
            mine = lax.dynamic_slice_in_dim(fac_st, r * mtl, mtl, axis=0)
            cur_col = lax.dynamic_slice_in_dim(tl, k // q, 1, axis=1)[:, 0]
            sel = ((gi > k)[:, None, None]) & (c == k % q)
            new_col = jnp.where(sel, mine, cur_col)
            tl = lax.dynamic_update_slice_in_dim(
                tl, new_col[:, None], k // q, axis=1
            )

            # -- 4. replicated V, W = V Tk in natural tile order ---------
            V2d = jnp.roll(V_act, lo, axis=0)  # global row coords
            W2d = V2d @ Tk
            V_nat = V2d.reshape(layout.P, mb, mb)
            W_nat = W2d.reshape(layout.P, mb, mb)
            V_rows = V_nat[gi]  # (mtl, mb, nb)
            V_cols = V_nat[gj]  # (ntl, mb, nb)
            W_rows = W_nat[gi]
            W_cols = W_nat[gj]

            # -- 5. P = Herm(A22) W from the stored lower triangle -------
            act_r = ((er >= lo) & (er < n))[:, None, :, None]
            act_c = ((ec >= lo) & (ec < n))[None, :, None, :]
            Alow = jnp.where(low_el & act_r & act_c, tl, 0)
            Aslow = jnp.where(slow_el & act_r & act_c, tl, 0)
            P1 = jnp.einsum("ijab,jbv->iav", Alow, W_cols)
            P2 = jnp.einsum("ijab,iav->jbv", conj(Aslow), W_rows)
            P_nat = (
                jnp.zeros((layout.P, mb, mb), P1.dtype)
                .at[gi].add(P1)
                .at[gj].add(P2)
            )
            P_nat = lax.psum(lax.psum(P_nat, COL_AXIS), ROW_AXIS)
            P2d = P_nat.reshape(m_pad, mb)

            # -- 6. Q2 = Tk^H (V^H P), replicated ------------------------
            Q2 = conj(Tk).T @ (conj(V2d).T @ P2d)

            # -- 7. two-sided trailing update on the stored triangle -----
            P_rows = P_nat[gi]
            P_cols = P_nat[gj]
            t1 = jnp.einsum("iav,jbv->ijab", V_rows, conj(P_cols))
            t2 = jnp.einsum("iav,jbv->ijab", P_rows, conj(V_cols))
            t3 = jnp.einsum("iav,vw,jbw->ijab", V_rows, Q2, conj(V_cols))
            upd = t1 + t2 - t3
            tl = tl - jnp.where(low_el & act_r & act_c, upd, 0)

            # -- 8. stash V on its owner column --------------------------
            V_st = V_nat[row_gather]
            vmine = lax.dynamic_slice_in_dim(V_st, r * mtl, mtl, axis=0)
            cur_v = lax.dynamic_slice_in_dim(Vs, k // q, 1, axis=1)[:, 0]
            new_v = jnp.where(sel, vmine, cur_v)
            Vs = lax.dynamic_update_slice_in_dim(
                Vs, new_v[:, None], k // q, axis=1
            )
            return tl, Vs, Ts

        Vs0 = jnp.zeros_like(tl)
        Ts0 = jnp.zeros((max(nsteps, 1), mb, mb), tl.dtype)
        return lax.fori_loop(0, nsteps, step, (tl, Vs0, Ts0))

    spec = P(ROW_AXIS, COL_AXIS)
    fn = shard_map(
        local, mesh=grid.mesh, in_specs=(spec,), out_specs=(spec, spec, P())
    )
    return fn(T)


@instrumented("spmd.unmtr_he2hb_left")
def spmd_unmtr_he2hb_left(
    grid: ProcessGrid,
    V_tiles: jnp.ndarray,
    Tstack: jnp.ndarray,
    C_tiles: jnp.ndarray,
    v_layout: TileLayout,
    c_layout: TileLayout,
    trans: bool,
) -> jnp.ndarray:
    """C <- Q C (trans=False) or Q^H C (True) with Q from spmd_he2hb
    (reference: src/unmtr_he2hb.cc, Side::Left).

    Q = H_0 H_1 ... H_{np-1}, H_k = I - V_k T_k V_k^H with V_k gathered
    from tile column k of V_tiles.  One fori_loop over panels; per panel
    the same panel-gather + distributed compact-WY apply as spmd_qr's
    trailing update: W = V^H C is a local contraction + psum over 'p',
    then C -= V (T W) locally.
    """
    p, q = grid.p, grid.q
    mb = v_layout.mb
    assert mb == v_layout.nb and mb == c_layout.mb
    n = v_layout.n
    nsteps = Tstack.shape[0]
    mtl, ntl = v_layout.mtl, v_layout.ntl
    ntl_c = c_layout.ntl
    m_pad = v_layout.P * mb
    row_scatter = jnp.asarray(v_layout.row_scatter)
    complex_t = jnp.issubdtype(C_tiles.dtype, jnp.complexfloating)

    def conj(x):
        return jnp.conj(x) if complex_t else x

    # forward (k ascending) applies H_{np-1} ... H_0; Q C needs k
    # descending (apply H_{np-1} first), Q^H C ascending.
    ascending = trans

    def local(vt, Ts, ct):
        r = lax.axis_index(ROW_AXIS)
        c = lax.axis_index(COL_AXIS)
        gi = jnp.arange(mtl) * p + r
        g_rows = jnp.arange(m_pad, dtype=jnp.int32)

        def step(i, ct):
            k = i if ascending else nsteps - 1 - i
            lo = (k + 1) * mb
            # gather V panel column k
            pan_loc = lax.dynamic_slice_in_dim(vt, k // q, 1, axis=1)[:, 0]
            pan_q = lax.all_gather(pan_loc, COL_AXIS)
            pan_rows = lax.dynamic_index_in_dim(pan_q, k % q, 0, keepdims=False)
            pan_full = lax.all_gather(pan_rows, ROW_AXIS).reshape(p * mtl, mb, mb)
            V2d = pan_full[row_scatter].reshape(m_pad, mb)
            V2d = jnp.where((g_rows >= lo)[:, None] & (g_rows < n)[:, None], V2d, 0)
            V_nat = V2d.reshape(v_layout.P, mb, mb)
            V_rows = V_nat[gi]
            Tk = lax.dynamic_index_in_dim(Ts, k, 0, keepdims=False)
            Tm = conj(Tk).T if trans else Tk
            # the V^H C gram is cancellation-heavy; past ~4096 local
            # rows the chip's f64 emulation drops its compensation
            # terms on exactly this shape (an old record, not reproduced;
            # the gathered-path gram was heev's whole orthogonality
            # budget at n=4096) — chunk the tile-stack contraction at
            # <= 2048 rows and accumulate across chunks in f64
            mtl_l = V_rows.shape[0]
            tchunk = max(1, KCHUNK // mb)
            if (
                emulated_f64(ct.dtype)
                and mtl_l * mb >= 2 * KCHUNK
                and mtl_l > tchunk
            ):
                W = jnp.einsum(
                    "iav,ijab->vjb",
                    conj(V_rows[:tchunk]), ct[:tchunk],
                    precision=lax.Precision.HIGHEST,
                )
                for t0 in range(tchunk, mtl_l, tchunk):
                    W = W + jnp.einsum(
                        "iav,ijab->vjb",
                        conj(V_rows[t0 : t0 + tchunk]),
                        ct[t0 : t0 + tchunk],
                        precision=lax.Precision.HIGHEST,
                    )
            else:
                W = jnp.einsum(
                    "iav,ijab->vjb", conj(V_rows), ct,
                    precision=lax.Precision.HIGHEST,
                )
            W = lax.psum(W, ROW_AXIS)  # (nb, ntl_c, nbc)
            upd = jnp.einsum("iav,vw,wjb->ijab", V_rows, Tm, W)
            return ct - upd

        return lax.fori_loop(0, nsteps, step, ct)

    spec = P(ROW_AXIS, COL_AXIS)
    fn = shard_map(
        local,
        mesh=grid.mesh,
        in_specs=(spec, P(), spec),
        out_specs=spec,
    )
    return fn(V_tiles, Tstack, C_tiles)
