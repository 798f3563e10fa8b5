"""Fast native blocked LU with partial pivoting for TPU.

The f64 LU in ops/lu_kernels.py (blocked_getrf) runs its n/nb steps in
at most 4 loops, each at the fixed shape where it starts, so every step
still pays a masked trailing matmul of its loop's full width.  This
module rebuilds the reference's right-looking schedule (reference:
src/getrf.cc:85-214 — threaded panel with per-column pivot search,
pivot broadcast, row exchange, trsm row, trailing gemm) as a
three-level TPU schedule, the LU analogue of
ops/chol_kernels.py:

* micro level (``_lu_panel_strips``): one fori_loop over ib-wide column
  strips of an (m, nb) panel.  Per column: VPU argmax pivot search,
  two-row swap, rank-1 update restricted to the strip; per strip: a
  unit-lower strip inverse by nilpotent squaring ((I+N)^-1 =
  (I-N)(I+N^2)(I+N^4)... exact because N^ib = 0) and one rank-ib MXU
  update of the rest of the panel.  This bounds the bandwidth-bound
  per-column traffic at O(m*ib) instead of O(m*nb).
* sub-panel level (``_block_lu``): one fori_loop over the nb-wide
  panels of an (m, NB) coarse block; the active region is rolled to the
  top so every iteration keeps one static shape.  Row exchanges are one
  gather of the block per panel; the trailing-in-block update is an
  explicit nb-inverse + two MXU gemms.
* coarse level (``blocked_getrf_fast``): <= coarse_panels Python-
  unrolled panels of width NB with exact shrinking shapes, so the
  dominant trailing gemms run at full MXU rate; the panel solve uses an
  explicit unit-lower inverse (MAGMA recipe).

Pivot choice matches LAPACK partial pivoting (maximal |entry| wins) up
to tie-breaking: exact-magnitude ties resolve to the lowest ORIGINAL
row index, where LAPACK scans in swapped order — the factorization is
equally valid but perm can differ on tied (structured/integer) inputs.
Used by lu_kernels.lu_global for large square matrices on non-CPU
backends.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..internal.precision import hdot as _dot


def _unit_lower_inv(L: jnp.ndarray) -> jnp.ndarray:
    """Exact inverse of a unit-lower (b, b) matrix by nilpotent squaring:
    (I + N)^-1 = (I - N)(I + N^2)(I + N^4)...  — log2(b) small matmuls,
    no triangular-solve lowering."""
    b = L.shape[0]
    eye = jnp.eye(b, dtype=L.dtype)
    N = jnp.tril(L, -1)
    inv = eye - N
    P = N
    k = 2
    while k < b:
        P = _dot(P, P)  # N^(2^j)
        inv = _dot(inv, eye + P)
        k *= 2
    return inv


def _lu_panel_strips(
    P: jnp.ndarray, act, ib: int = 32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Partial-pivot LU of an (m, w) panel; only rows < act are eligible
    pivots (the rest is padding).  w must be a multiple of ib.

    No row is moved during elimination: pivoting is tracked with an
    eligibility mask (the Schur update is row-order independent), so the
    per-column work touches only the (m, ib) strip — the swap-based
    variant's four full-panel row updates per column dominated its
    runtime.  One ordering gather at the end produces the same row
    order (and net forward permutation) as LAPACK's swap sequence.

    Returns (P, perm): P holds unit-lower L below the diagonal and U
    on/above for the w eliminated columns, rows in LAPACK pivot order;
    P rows correspond to input rows perm."""
    m, w = P.shape
    rows = jnp.arange(m)
    colsw = jnp.arange(w)
    ibr = jnp.arange(ib)

    def strip(s, carry):
        P, unpiv, pivrows = carry
        j0 = s * ib
        S = lax.dynamic_slice(P, (0, j0), (m, ib))
        for c in range(ib):
            colc = S[:, c]
            mag = jnp.where(unpiv, jnp.abs(colc), -jnp.inf)
            piv = jnp.argmax(mag)
            pv = colc[piv]
            safe = jnp.where(pv == 0, jnp.ones_like(pv), pv)
            elig = unpiv & (rows != piv) & (pv != 0)
            l = jnp.where(elig, colc / safe, jnp.zeros((), P.dtype))
            # pivoted rows keep their U entries; the pivot row keeps pv
            S = S.at[:, c].set(jnp.where(unpiv & (rows != piv), l, colc))
            unpiv = unpiv.at[piv].set(False)
            pivrows = pivrows.at[j0 + c].set(piv.astype(jnp.int32))
            if c + 1 < ib:
                # rank-1 on the strip's remaining columns only (c is a
                # Python int, so the tail slice is static — halves the
                # bandwidth-bound micro traffic vs updating all of S)
                tail = S[:, c + 1 :]
                urow = tail[piv]
                S = S.at[:, c + 1 :].set(tail - jnp.outer(l, urow))
        P = lax.dynamic_update_slice(P, S, (0, j0))
        # rank-ib update of the rest of the panel: gather the strip's
        # pivot rows, exact unit-lower inverse by nilpotent squaring,
        # one MXU gemm.  Lss[j, c] for j > c is the column-c multiplier
        # of pivot row p_j (recorded in S before p_j was pivoted).
        stripiv = lax.dynamic_slice(pivrows, (j0,), (ib,))
        Srows = P[stripiv]  # (ib, w)
        D = lax.dynamic_slice(Srows, (0, j0), (ib, ib))
        Linv = _unit_lower_inv(D)
        U12 = _dot(Linv, Srows)
        cmask = (colsw >= j0 + ib)[None, :]
        P = P.at[stripiv].set(jnp.where(cmask, U12, Srows))
        L21 = jnp.where(unpiv[:, None], S, jnp.zeros((), P.dtype))
        return (
            P - jnp.where(cmask, _dot(L21, U12), jnp.zeros((), P.dtype)),
            unpiv,
            pivrows,
        )

    unpiv0 = rows < act
    pivrows0 = jnp.zeros((w,), jnp.int32)
    P, unpiv, pivrows = lax.fori_loop(
        0, w // ib, strip, (P, unpiv0, pivrows0)
    )

    # Reconstruct LAPACK's row order: replay the swap sequence
    # (column j swaps positions j <-> current position of pivrows[j])
    # on an index vector.  O(w) scalar steps — tiny next to the strips.
    def replay(j, carry):
        perm, pos = carry
        p = pos[pivrows[j]]
        rj = perm[j]
        rp = perm[p]
        perm = perm.at[j].set(rp).at[p].set(rj)
        pos = pos.at[rp].set(j).at[rj].set(p)
        return perm, pos

    perm0 = jnp.arange(m, dtype=jnp.int32)
    perm, _ = lax.fori_loop(0, w, replay, (perm0, perm0))
    return P[perm], perm


def _block_lu(
    B: jnp.ndarray, nb: int, ib: int = 32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Partial-pivot LU of the first W columns of an (m, W) block,
    m >= W, W a multiple of nb.  One fori_loop over the W//nb panels
    (active region rolled to the top keeps a single compiled shape).

    Returns (B, perm): L\\U packed in the first W columns, perm the net
    forward row permutation over the m rows."""
    m, W = B.shape
    rows = jnp.arange(m)
    colsW = jnp.arange(W)
    eye_nb = jnp.eye(nb, dtype=B.dtype)

    def panel(s, carry):
        B, perm = carry
        j0 = s * nb
        colblk = lax.dynamic_slice(B, (0, j0), (m, nb))
        rolled = jnp.roll(colblk, -j0, axis=0)
        act = m - j0
        rolled = jnp.where((rows < act)[:, None], rolled, jnp.zeros((), B.dtype))
        Pf, perm_loc = _lu_panel_strips(rolled, act, ib)
        # unroll the panel permutation into the block frame (identity
        # above j0) and exchange rows across the whole block
        mapped = jnp.where(
            rows >= j0,
            perm_loc[jnp.clip(rows - j0, 0, m - 1)] + j0,
            rows,
        )
        B = B[mapped]
        perm = perm[mapped]
        # write the factored panel back
        Pn = jnp.roll(Pf, j0, axis=0)
        cur = lax.dynamic_slice(B, (0, j0), (m, nb))
        neu = jnp.where((rows >= j0)[:, None], Pn, cur)
        B = lax.dynamic_update_slice(B, neu, (0, j0))
        # U rows to the right + trailing update inside the block; the
        # nb-block inverse via one small trsm (cheaper than nilpotent
        # squaring at nb=512: log2(nb) full matmuls vs one solve)
        Lnb = jnp.tril(Pf[:nb], -1) + eye_nb
        Linv = lax.linalg.triangular_solve(
            Lnb, eye_nb, left_side=True, lower=True, unit_diagonal=True
        )
        Rtop = lax.dynamic_slice(B, (j0, 0), (nb, W))
        U12 = _dot(Linv, Rtop)
        cmask = (colsW >= j0 + nb)[None, :]
        B = lax.dynamic_update_slice(B, jnp.where(cmask, U12, Rtop), (j0, 0))
        L21 = jnp.where((rows >= j0 + nb)[:, None], neu, jnp.zeros((), B.dtype))
        U12m = jnp.where(cmask, U12, jnp.zeros((), B.dtype))
        return B - _dot(L21, U12m), perm

    perm0 = jnp.arange(m, dtype=jnp.int32)
    return lax.fori_loop(0, W // nb, panel, (B, perm0))


def blocked_getrf_fast(
    G: jnp.ndarray, nb: int = 512, ib: int = 32, coarse_panels: int = 4
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked right-looking LU with partial pivoting of a square padded
    array (n a multiple of nb).  Returns (LU, perm): LU = (L\\U) of
    G[perm].  Same contract as lu_kernels.blocked_getrf; ~15x faster at
    n >= 4096 on the chip (exact-shape trailing gemms at MXU rate).
    """
    n = G.shape[0]
    assert n % nb == 0, f"blocked_getrf_fast: n={n} not a multiple of nb={nb}"
    nt = n // nb
    perm = jnp.arange(n, dtype=jnp.int32)
    if nt <= 1:
        act = jnp.int32(n)
        LU, perm = _lu_panel_strips(G, act, ib)
        return LU, perm

    NB = nb * (-(-nt // coarse_panels))
    eyes = {}
    k0 = 0
    while k0 < n:
        W = min(NB, n - k0)
        B = G[k0:, k0 : k0 + W]
        Bf, permB = _block_lu(B, nb, ib)
        step = jnp.concatenate(
            [jnp.arange(k0, dtype=jnp.int32), permB + k0]
        )
        G = G[step]
        perm = perm[step]
        G = G.at[k0:, k0 : k0 + W].set(Bf)
        rest = n - k0 - W
        if rest > 0:
            LW = jnp.tril(Bf[:W], -1) + eyes.setdefault(
                W, jnp.eye(W, dtype=G.dtype)
            )
            # one (W, W) unit-lower trsm (single shape reused by every
            # coarse panel), then MXU gemms carry the bulk work
            Linv = lax.linalg.triangular_solve(
                LW, eyes[W], left_side=True, lower=True, unit_diagonal=True
            )
            U12 = _dot(Linv, G[k0 : k0 + W, k0 + W :])
            G = G.at[k0 : k0 + W, k0 + W :].set(U12)
            L21 = Bf[W:, :W]
            G = G.at[k0 + W :, k0 + W :].add(-_dot(L21, U12))
        k0 += W
    return G, perm
