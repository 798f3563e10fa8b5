"""Fast native blocked LU with partial pivoting for TPU.

The f64 LU in ops/lu_kernels.py (blocked_getrf) runs its n/nb steps in
at most 4 loops, each at the fixed shape where it starts, so every step
still pays a masked trailing matmul of its loop's full width.  This
module rebuilds the reference's right-looking schedule (reference:
src/getrf.cc:85-214 — threaded panel with per-column pivot search,
pivot broadcast, row exchange, trsm row, trailing gemm) as a
three-level TPU schedule, the LU analogue of
ops/chol_kernels.py:

* micro level (``lu_kernels._lu_panel_strips``, shared with
  ``blocked_getrf``): partial-pivot LU of an (m, nb) panel in ib-wide
  column strips, per-column work confined to the strip, one rank-ib
  update of the panel per strip (``precision.short_dot``: the MXU for
  f32/c64, elementwise for emulated f64).
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
from .lu_kernels import _lu_panel_strips


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
    G[perm].  Same contract as lu_kernels.blocked_getrf, with exact-shape
    trailing gemms; its speed on the chip is not measured (no benchmark
    cell runs it).
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
