"""Native blocked LU kernels (no vendor LuDecomposition).

The XLA TPU backend implements lax.linalg.lu only for f32/c64; the
reference's own blocked right-looking getrf (reference: src/getrf.cc:85-214
— panel factor, pivot broadcast, row swaps, trailing update) is the model
for the f64/c128 path here.  Everything is static-shape fori_loop code:

* ``panel_lu``     — unblocked partial-pivot LU of one (M, nb) panel,
  the analogue of the reference's threaded panel kernel
  (Tile_getrf.hh:164-452) with the per-column argmax done by lax.argmax
  over the whole gathered panel instead of a thread/MPI reduction tree.
* ``blocked_getrf`` — right-looking blocked LU over the padded global
  array: per step the panel is rolled to the top (static shapes), factored
  redundantly, row swaps applied as one gather, then one triangular solve
  + one matmul for the trailing update (getrf.cc:183-214's permuteRows +
  trsm + gemm fused into three XLA ops).  The steps run in one loop at
  the full padded shape below 2048, else in at most 4 loops at the exact
  trailing shape where each starts.

Used by drivers/lu.py whenever the platform lacks a vendor LU for the
dtype, and by parallel/spmd_lu.py for the in-loop panel factor.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..internal.precision import hdot as _dot
from .chol_kernels import (
    RECURSIVE_MIN_N,
    _lat_height,
    pallas_compiles,
    split_point,
)


def panel_lu(
    panel: jnp.ndarray, pivot: bool = True, act: int | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unblocked LU of an (M, nb) panel, partial pivoting by default.

    Returns (lu, perm) with lu holding unit-lower L below the diagonal and
    U on/above, and perm the forward permutation: lu rows correspond to
    panel[perm].  Matches lax.linalg.lu's (lu, _, permutation) contract.
    Zero pivot columns produce zero L columns (flagged by the caller's
    info check), not NaNs.  pivot=False runs the no-exchange elimination
    (used after tournament pivoting has already ordered the rows).

    ``act`` (static) restricts the pivot search to rows < act: the
    recursive schedule pads panels with zero rows up to a canonical
    height so distinct compiled shapes stay O(log), and those pad rows
    must never be chosen as pivots (they stay exact fixed points of
    perm).
    """
    M, nb = panel.shape
    rows = jnp.arange(M)

    def body(j, carry):
        a, perm = carry
        col = a[:, j]
        if pivot:
            elig = rows >= j if act is None else (rows >= j) & (rows < act)
            mag = jnp.where(elig, jnp.abs(col), -jnp.inf)
            piv = jnp.argmax(mag)
        else:
            piv = j
        # swap rows j <-> piv (gather-free: two dynamic row updates)
        rj = a[j]
        rp = a[piv]
        a = a.at[j].set(rp).at[piv].set(rj)
        pj = perm[j]
        pp = perm[piv]
        perm = perm.at[j].set(pp).at[piv].set(pj)
        pv = a[j, j]
        safe = jnp.where(pv == 0, jnp.ones_like(pv), pv)
        l = jnp.where((rows > j) & (pv != 0), a[:, j] / safe, jnp.zeros(M, a.dtype))
        a = a.at[:, j].set(jnp.where(rows > j, l, a[:, j]))
        urow = jnp.where(jnp.arange(nb) > j, a[j], jnp.zeros(nb, a.dtype))
        return a - jnp.outer(l, urow), perm

    perm0 = jnp.arange(M, dtype=jnp.int32)
    lu, perm = lax.fori_loop(0, min(M, nb), body, (panel, perm0))
    return lu, perm


# the segmented blocked_getrf splits its steps into at most this many
# exact-shape loops (one compiled loop body each)
_FLAT_SEGMENTS = 4


def _flat_segments(m: int, n: int, nb: int) -> list:
    """(k0, steps) of each loop ``blocked_getrf`` runs on an (m, n)
    array: one full-shape loop below ``RECURSIVE_MIN_N``, else at most
    ``_FLAT_SEGMENTS`` loops of ceil(kt / 4) steps, the one at k0
    running on the trailing (m - k0, n - k0) array.  Shared with
    ``getrf_schedule_flops``."""
    kt = min(m, n) // nb
    if min(m, n) < RECURSIVE_MIN_N:
        return [(0, kt)]
    seg = -(-kt // _FLAT_SEGMENTS)
    return [(k * nb, min(seg, kt - k)) for k in range(0, kt, seg)]


def _getrf_steps(
    G: jnp.ndarray, nb: int, steps: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The first ``steps`` nb-wide steps of the right-looking LU of an
    (M, N) array, in one fori_loop at the full array shape: every step
    factors its panel, swaps rows across the whole array and updates
    the whole trailing part (finished rows and columns masked to zero).
    Returns (G, perm): G holds L\\U in its first steps*nb columns and
    rows and the updated trailing block, rows in the order perm."""
    M, N = G.shape
    rows = jnp.arange(M)
    cols = jnp.arange(N)

    def step(k, carry):
        G, perm = carry
        with jax.named_scope("getrf.panel"):
            # roll active rows to the top, factor
            col = lax.dynamic_slice(G, (0, k * nb), (M, nb))
            colr = jnp.roll(col, -k * nb, axis=0)
            active_len = M - k * nb
            colr = jnp.where((rows < active_len)[:, None], colr,
                             jnp.zeros_like(colr))
            lu_pan, piv = panel_lu(colr)
            # step permutation in global row space (identity above the
            # panel)
            act = rows - k * nb
            mapped = piv[jnp.clip(act, 0, M - 1)] + k * nb
            step_perm = jnp.where(act >= 0, mapped, rows)
        with jax.named_scope("getrf.swap"):
            # row exchange across the whole matrix, then the factored
            # panel written back (rows >= k*nb)
            G = G[step_perm]
            perm = perm[step_perm]
            lu_nat = jnp.roll(lu_pan, k * nb, axis=0)
            col_cur = lax.dynamic_slice(G, (0, k * nb), (M, nb))
            col_new = jnp.where((rows >= k * nb)[:, None], lu_nat, col_cur)
            G = lax.dynamic_update_slice(G, col_new, (0, k * nb))
        with jax.named_scope("getrf.trsm"):
            # U row: Lkk^-1 A(k, j>k)
            Lkk = jnp.tril(lu_pan[:nb], -1) + jnp.eye(nb, dtype=G.dtype)
            row = lax.dynamic_slice(G, (k * nb, 0), (nb, N))
            rs = lax.linalg.triangular_solve(
                Lkk, row, left_side=True, lower=True, unit_diagonal=True
            )
            row_new = jnp.where((cols >= (k + 1) * nb)[None, :], rs, row)
            G = lax.dynamic_update_slice(G, row_new, (k * nb, 0))
        with jax.named_scope("getrf.update"):
            Lpan = jnp.where((rows >= (k + 1) * nb)[:, None], col_new, 0)
            Urow = jnp.where((cols >= (k + 1) * nb)[None, :], row_new, 0)
            return G - _dot(Lpan, Urow), perm

    perm0 = jnp.arange(M, dtype=jnp.int32)
    return lax.fori_loop(0, steps, step, (G, perm0))


def blocked_getrf(
    Gp: jnp.ndarray, nb: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked right-looking LU with partial pivoting of a padded array.

    Gp: (Mp, Np) with Mp, Np multiples of nb and the padding diagonal
    spliced to 1 (layout.eye_splice semantics).  Returns (LU, perm) with
    perm the net forward row permutation: LU = (L\\U) of Gp[perm].
    Reference: src/getrf.cc:85-214.

    Below ``RECURSIVE_MIN_N`` all min(Mp, Np)/nb steps run in one loop
    at the full padded shape (one compiled body).  Above it the steps
    run in at most 4 loops (``_flat_segments``), each at the exact
    trailing shape where it starts, like ``lu_fast``'s coarse panels:
    the segment's row swaps reach the finished L columns to its left
    once, after its loop.  The masked full-segment-shape steps still
    execute ~1.6x the 2n^3/3 model at n/nb = 16 (see
    ``getrf_schedule_flops``); ``getrf_recursive`` runs near the model
    from O(log n) compile units.
    """
    (_, steps), *rest = _flat_segments(*Gp.shape, nb)
    G, perm = _getrf_steps(Gp, nb, steps)
    for k0, steps in rest:
        with jax.named_scope("getrf.swap"):
            T = G[k0:, k0:]
        T, p = _getrf_steps(T, nb, steps)
        with jax.named_scope("getrf.swap"):
            G = G.at[k0:, :k0].set(G[k0:, :k0][p])
            G = G.at[k0:, k0:].set(T)
            perm = perm.at[k0:].set(perm[k0:][p])
    return G, perm


def tournament_pivots(
    panel: jnp.ndarray, nb: int, chunk: int
) -> jnp.ndarray:
    """Tournament (CALU) pivot selection on an (M, nb) panel (reference:
    src/getrf_tntpiv.cc:1-498, internal_getrf_tntpiv.cc): every `chunk`
    rows elect nb candidate pivot rows with a local partial-pivot LU, and
    winners advance up a binary reduction tree — one communication-free
    pass per level, the LU variant built for static schedules.

    Returns the nb winning row indices (into panel), in pivot order.
    """
    M, nbp = panel.shape
    assert nbp == nb and chunk >= nb and M % chunk == 0
    K = M // chunk
    chunks = panel.reshape(K, chunk, nb)
    base = jnp.arange(K)[:, None] * chunk

    def elect(ch):
        _, perm = panel_lu(ch)
        return ch[perm[:nb]], perm[:nb]

    cands, local_idx = jax.vmap(elect)(chunks)  # (K, nb, nb), (K, nb)
    idxs = base + local_idx

    while K > 1:
        if K % 2 == 1:  # odd: last bracket gets a zero-rows bye
            cands = jnp.concatenate(
                [cands, jnp.zeros((1, nb, nb), cands.dtype)]
            )
            idxs = jnp.concatenate([idxs, jnp.full((1, nb), M, idxs.dtype)])
            K += 1
        merged = cands.reshape(K // 2, 2 * nb, nb)
        midx = idxs.reshape(K // 2, 2 * nb)

        def play(ch, ix):
            _, perm = panel_lu(ch)
            return ch[perm[:nb]], ix[perm[:nb]]

        cands, idxs = jax.vmap(play)(merged, midx)
        K //= 2
    return idxs[0]


def blocked_getrf_tntpiv(
    Gp: jnp.ndarray, nb: int, chunk: int = 0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked LU with tournament pivoting (reference: getrf_tntpiv.cc,
    MethodLU.CALU).  Same right-looking structure as blocked_getrf; the
    panel's pivot rows come from the communication-free tournament, after
    which the panel eliminates without further exchanges.
    """
    Mp, Np = Gp.shape
    kt = min(Mp, Np) // nb
    chunk = chunk or max(4 * nb, nb)
    # pad rows so every (rolled) panel splits into whole chunks
    Mc = -(-Mp // chunk) * chunk
    Gw = jnp.pad(Gp, ((0, Mc - Mp), (0, 0)))
    rows = jnp.arange(Mc)
    cols = jnp.arange(Np)

    def step(k, carry):
        G, perm = carry
        col = lax.dynamic_slice(G, (0, k * nb), (Mc, nb))
        colr = jnp.roll(col, -k * nb, axis=0)
        active_len = Mp - k * nb
        colr = jnp.where((rows < active_len)[:, None], colr, jnp.zeros_like(colr))
        # -- tournament pivot selection over the active panel ----------
        win = tournament_pivots(colr, nb, chunk)  # rows in active frame
        # step permutation: winners to the top (in order), others keep
        # their relative order behind them
        is_win = jnp.zeros((Mc,), jnp.int32).at[win].set(1, mode="drop")
        win_pos = jnp.zeros((Mc,), jnp.int32).at[win].set(
            jnp.arange(nb, dtype=jnp.int32), mode="drop"
        )
        rest_rank = jnp.cumsum(1 - is_win) - 1
        key = jnp.where(is_win == 1, win_pos, nb + rest_rank)
        step_perm_act = jnp.argsort(key)  # active-frame permutation
        mapped = jnp.where(
            rows - k * nb >= 0,
            step_perm_act[jnp.clip(rows - k * nb, 0, Mc - 1)] + k * nb,
            rows,
        )
        step_perm = jnp.where(mapped < Mc, mapped, mapped - Mc)
        G = G[step_perm]
        perm = perm[step_perm]
        # -- panel factor, no further pivoting -------------------------
        col2 = lax.dynamic_slice(G, (0, k * nb), (Mc, nb))
        colr2 = jnp.roll(col2, -k * nb, axis=0)
        colr2 = jnp.where(
            (rows < active_len)[:, None], colr2, jnp.zeros_like(colr2)
        )
        lu_pan, _ = panel_lu(colr2, pivot=False)
        lu_nat = jnp.roll(lu_pan, k * nb, axis=0)
        col_cur = lax.dynamic_slice(G, (0, k * nb), (Mc, nb))
        col_new = jnp.where((rows >= k * nb)[:, None], lu_nat, col_cur)
        G = lax.dynamic_update_slice(G, col_new, (0, k * nb))
        # -- U row + trailing update (as blocked_getrf) ----------------
        Lkk = jnp.tril(lu_pan[:nb], -1) + jnp.eye(nb, dtype=G.dtype)
        row = lax.dynamic_slice(G, (k * nb, 0), (nb, Np))
        rs = lax.linalg.triangular_solve(
            Lkk, row, left_side=True, lower=True, unit_diagonal=True
        )
        row_new = jnp.where((cols >= (k + 1) * nb)[None, :], rs, row)
        G = lax.dynamic_update_slice(G, row_new, (k * nb, 0))
        Lpan = jnp.where((rows >= (k + 1) * nb)[:, None], col_new, 0)
        Urow = jnp.where((cols >= (k + 1) * nb)[None, :], row_new, 0)
        return G - _dot(Lpan, Urow), perm

    perm0 = jnp.arange(Mc, dtype=jnp.int32)
    G, perm = lax.fori_loop(0, kt, step, (Gw, perm0))
    return G[:Mp], perm[:Mp]


# ---------------------------------------------------------------------------
# Recursive (divide & conquer) schedule: exact shapes on the halving
# lattice, pivoted, with permutation composition across the halves
# (Toledo-style recursive LU).  The flat blocked_getrf above pays
# 1.6-4x the model FLOPs for its few compiled shapes; the recursion factors
# the left column half at its exact (shrinking) height, solves/updates
# the right half at exact shapes, and composes the half permutations.
# ---------------------------------------------------------------------------


def _trsm_left_unit(L: jnp.ndarray, B: jnp.ndarray, nb: int) -> jnp.ndarray:
    """L X = B with L unit-lower (diagonal implicit — only the strict
    lower triangle of L is read), by recursive 2x2 splitting: vendor
    solves only at <= nb diagonal blocks, exact-shape MXU gemms carry
    the bulk at exactly the model FLOP count (r h^2)."""
    h = L.shape[0]
    if h <= nb:
        return lax.linalg.triangular_solve(
            L, B, left_side=True, lower=True, unit_diagonal=True
        )
    s = split_point(h)
    B1 = _trsm_left_unit(L[:s, :s], B[:s], nb)
    B2 = _trsm_left_unit(
        L[s:, s:], B[s:] - _dot(L[s:, :s], B1), nb
    )
    return jnp.concatenate([B1, B2], axis=0)


def getrf_recursive(
    G: jnp.ndarray, nb_switch: int = 256, lookahead: int = 1,
    family: str = "recursive",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Recursive blocked LU with partial pivoting of an (m, n) array,
    m >= n.  Returns (LU, perm): LU = (L\\U) of G[perm], the
    blocked_getrf contract.

    Schedule: factor the left n1 = split_point(n) columns recursively
    (exact full-height panels), permute the right half by the left
    half's pivots, solve U12 with the recursive unit-lower trsm, one
    exact-shape Schur gemm, recurse on the trailing (m-n1, n-n1) block,
    then compose the two half permutations — the pivot order matches
    LAPACK partial pivoting exactly (the base case is ``panel_lu``).

    ``lookahead`` follows the reference getrf convention (1 = baseline
    pipeline): k > 1 peels k-1 eager nb_switch-wide panels ahead of the
    halving split at the top level (Option.Lookahead wiring).

    ``family`` selects the panel base case: ``"recursive"`` (the jnp
    fori_loop ``panel_lu``) or ``"pallas"`` (the fused in-register
    pivot-search kernel — identical arithmetic, identical pivot order).
    """
    m, n = G.shape
    assert m >= n, f"getrf_recursive requires m >= n, got {(m, n)}"
    if family == "pallas":
        from .pallas import panel_kernels as pk

        _panel = pk.panel_lu
    else:
        _panel = panel_lu

    def canon(X, act):
        """Snap X's height to the canonical ``_lat_height(act)``:
        truncate (rows >= act are exact zeros by construction) or
        zero-pad.  Returns (X', restore) with restore mapping a child
        (LU, perm) over X' back to X's frame — safe because rows >= act
        are never pivoted, hence fixed points of the child perm."""
        M = X.shape[0]
        Mc = _lat_height(act)
        if Mc == M:
            return X, lambda LU, p: (LU, p)
        if Mc < M:  # drop all-zero tail rows for the child

            def restore(LU, p):
                LU = jnp.concatenate(
                    [LU, jnp.zeros((M - Mc, LU.shape[1]), LU.dtype)]
                )
                return LU, jnp.concatenate(
                    [p, jnp.arange(Mc, M, dtype=p.dtype)]
                )

            return X[:Mc], restore

        def restore(LU, p):  # Mc > M: child's pad rows are fixed points
            return LU[:M], p[:M]

        return jnp.pad(X, ((0, Mc - M), (0, 0))), restore

    def panel(X, act):
        with jax.named_scope("getrf.panel"):
            return _panel(X, act=None if act >= X.shape[0] else act)

    def rec(G, act):
        # invariant: rows >= act of G are exact zeros (never pivotable)
        M, n = G.shape
        if n <= nb_switch:
            return panel(G, act)
        s = split_point(n)
        LU1, p1 = rec(G[:, :s], act)
        with jax.named_scope("getrf.swap"):
            R = G[:, s:][p1]
        with jax.named_scope("getrf.trsm"):
            U12 = _trsm_left_unit(LU1[:s, :s], R[:s], nb_switch)
        with jax.named_scope("getrf.update"):
            S2, restore = canon(
                jnp.concatenate([LU1[s:, :s], R[s:]], axis=1), act - s
            )
            S = S2[:, s:] - _dot(S2[:, :s], U12)
        LU2, p2 = rec(S, act - s)
        with jax.named_scope("getrf.swap"):
            LU2, p2 = restore(LU2, p2)
            top = jnp.concatenate([LU1[:s], U12], axis=1)
            bot = jnp.concatenate([LU1[s:][p2], LU2], axis=1)
            perm = jnp.concatenate([p1[:s], p1[s:][p2]])
            return jnp.concatenate([top, bot], axis=0), perm

    if n <= nb_switch:
        return panel(G, m)
    peel = max(int(lookahead) - 1, 0)
    frames = []  # (top_row_block, L_below, step perm), outermost first
    T, act = G, m
    while peel > 0 and (T.shape[1]) > 2 * nb_switch:
        w = nb_switch
        LU1, p1 = panel(T[:, :w], act)
        with jax.named_scope("getrf.swap"):
            R = T[:, w:][p1]
        with jax.named_scope("getrf.trsm"):
            U12 = _trsm_left_unit(LU1[:w, :w], R[:w], nb_switch)
        with jax.named_scope("getrf.update"):
            S = R[w:] - _dot(LU1[w:, :w], U12)
        frames.append((jnp.concatenate([LU1[:w], U12], axis=1),
                       LU1[w:], p1))
        T, act = S, act - w
        peel -= 1
    LUr, pr = rec(T, act)
    # stitch the peeled frames back around the recursed trailing factor,
    # composing permutations innermost-out (each frame nests exactly
    # like a recursion half)
    bot, p = LUr, pr
    with jax.named_scope("getrf.swap"):
        for top, Lw, p1 in reversed(frames):
            w = top.shape[0]
            bot = jnp.concatenate([Lw[p], bot], axis=1)
            bot = jnp.concatenate([top, bot], axis=0)
            p = jnp.concatenate([p1[:w], p1[w:][p]])
    return bot, p


def getrf_schedule_flops(
    m: int,
    n: int,
    nb: int = 512,
    schedule: str = "recursive",
    nb_switch: int = 256,
    lookahead: int = 1,
    m_true: int | None = None,
    n_true: int | None = None,
) -> dict:
    """(model, exec, units) FLOP accounting for one pivoted LU of
    (m, n), m >= n, mirroring the executed schedule (masked full-shape
    ops counted at full shape).  model = n^2 (m - n/3), the LAPACK
    getrf count — computed from (m_true, n_true) when given, so drivers
    passing padded kernel shapes still report waste against the TRUE
    problem size (pad rows/columns are waste, the same convention as
    chol_schedule_flops)."""
    from .chol_kernels import _trsm_flops

    mt, nt_ = (m_true or m), (n_true or n)
    model = float(nt_) * nt_ * (mt - nt_ / 3.0)
    # pallas panel kernel replicates panel_lu's arithmetic exactly, so
    # the executed count is identical — only the compile unit differs
    panel_unit = "pallas_lu_panel" if schedule == "pallas" else "lu_panel"

    def panel_flops(M, b):
        # panel_lu: per eliminated column one full-height rank-1 on the
        # whole (M, b) panel
        return 2.0 * M * b * min(M, b), {(panel_unit, M, b)}

    if schedule == "vendor":
        # the vendor kernel still runs on the PADDED array
        return {"model": model,
                "exec": float(n) * n * (m - n / 3.0),
                "units": {("vendor_lu", m, n)}}
    if schedule == "flat":
        # blocked_getrf: every step of a segment at the segment's full
        # (m - k0, n - k0) shape
        ex, units = 0.0, set()
        for k0, steps in _flat_segments(m, n, nb):
            ms, ns = m - k0, n - k0
            fp, up = panel_flops(ms, nb)
            ex += steps * (fp + float(ns) * nb * nb + 2.0 * ms * ns * nb)
            units |= up | {("trsm", nb, ns), ("gemm", ms, nb, ns)}
        return {"model": model, "exec": ex, "units": units}
    if schedule == "flat_fast":
        # lu_fast.blocked_getrf_fast: <= 4 coarse panels at exact
        # shapes, _block_lu's inner loops masked at full block shape
        nbf = _lu_fast_nb(n) or max(nb, 1)
        nt = max(n // nbf, 1)
        NB = nbf * (-(-nt // 4))
        ex, units = 0.0, set()
        k0 = 0
        while k0 < n:
            W = min(NB, n - k0)
            mk = m - k0
            # _block_lu(mk, W): strips + in-block trsm/gemm, all masked
            # to the full (mk, W) block per panel
            ex += 2.0 * mk * nbf * W + 2.0 * nbf * W * W + 2.0 * mk * W * W
            units |= {("lu_block", mk, W)}
            rest = n - k0 - W
            if rest > 0:
                ex += W**3 / 2.0 + 2.0 * W * W * rest
                ex += 2.0 * (mk - W) * W * rest
                units |= {("trsm", W, W), ("gemm", W, W, rest),
                          ("gemm", mk - W, W, rest)}
            k0 += W
        return {"model": model, "exec": ex, "units": units}

    from .chol_kernels import _lat_height

    def rec(M, act, n):
        # M: physical (canonical) height, act: true rows — mirrors
        # getrf_recursive's canon() exactly
        if n <= nb_switch:
            return panel_flops(M, n)
        s = split_point(n)
        f1, u1 = rec(M, act, s)
        ft, ut = _trsm_flops(n - s, s, nb_switch)
        Mc = _lat_height(act - s)
        fg = 2.0 * Mc * s * (n - s)
        f2, u2 = rec(Mc, act - s, n - s)
        return f1 + ft + fg + f2, u1 | ut | u2 | {("gemm", Mc, s, n - s)}

    ex, units = 0.0, set()
    k0, peel = 0, max(int(lookahead) - 1, 0)
    while peel > 0 and (n - k0) > 2 * nb_switch:
        w = nb_switch
        fp, up = panel_flops(m - k0, w)
        ft, ut = _trsm_flops(n - k0 - w, w, nb_switch)
        fg = 2.0 * (m - k0 - w) * w * (n - k0 - w)
        ex += fp + ft + fg
        units |= up | ut | {("gemm", m - k0 - w, w, n - k0 - w)}
        k0 += w
        peel -= 1
    fr, ur = rec(m - k0, m - k0, n - k0)
    return {"model": model, "exec": ex + fr, "units": units | ur}


def lu_supported(dtype) -> bool:
    """Whether the vendor lax.linalg.lu compiles for this dtype on the
    current default backend (TPU: f32/c64 only)."""
    import jax

    if jax.default_backend() == "cpu":
        return True
    dt = jnp.dtype(dtype)
    return dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.complex64))


def _lu_fast_nb(n: int) -> int:
    """Block size the three-level lu_fast schedule uses, 0 when the
    shape does not admit it — shared by dispatch and accounting."""
    for nbf in (512, 256, 128):
        if n % nbf == 0:
            return nbf
    return 0


def resolve_lu_schedule(m: int, n: int, dtype, schedule: str = "auto") -> str:
    """The route ``lu_global`` will take for this shape/dtype/backend —
    shared with the drivers' FLOP accounting so the recorded
    ``factor.getrf.*`` counters describe the program actually traced.

    ``flat`` is the pre-recursion native family (same convention as the
    chol/QR flat routes, which map to the tuned coarse kernels): the
    three-level ``lu_fast`` schedule for large divisible squares
    (``flat_fast``), the single-level ``blocked_getrf`` otherwise."""
    import jax

    if schedule in ("recursive", "pallas") and m >= n:
        return schedule
    if schedule in ("flat", "recursive", "pallas"):
        if m == n and n >= 2048 and _lu_fast_nb(n):
            return "flat_fast"
        return "flat"
    if jax.default_backend() != "cpu" and m == n and n >= RECURSIVE_MIN_N:
        # the segmented blocked_getrf (at most 4 loop bodies) where the
        # Pallas panel cannot run (see chol_kernels.pallas_compiles)
        return "pallas" if pallas_compiles(dtype) else "flat"
    if lu_supported(dtype):
        return "vendor"
    return "flat"


def lu_global(
    Gp: jnp.ndarray,
    nb: int,
    schedule: str = "auto",
    nb_switch: int = 256,
    lookahead: int = 1,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Schedule-dispatched LU of the padded global array.

    Returns (LU, perm), perm over Gp's (padded) rows.  ``auto``: CPU
    keeps the vendor (LAPACK) kernel; on accelerators large square
    arrays run the recursive divide & conquer schedule where the Pallas
    panel compiles, and the segmented blocked_getrf for the other
    dtypes (at most 4 exact-shape loops; ~1.6x the model FLOPs at
    n/nb = 16, where the recursion is near the model but compiles for
    minutes under emulated f64), with blocked_getrf also the
    rectangular fallback.  Explicit ``recursive``/``flat`` are honored
    on every backend (tests exercise the native schedules on CPU).
    Dispatch and the drivers' FLOP accounting share
    ``resolve_lu_schedule``, so the recorded route is always the traced
    one.
    """
    route = resolve_lu_schedule(*Gp.shape, Gp.dtype, schedule)
    if route in ("recursive", "pallas"):
        return getrf_recursive(Gp, nb_switch, lookahead, route)
    if route == "vendor":
        lu2d, _, perm = lax.linalg.lu(Gp)
        return lu2d, perm.astype(jnp.int32)
    if route == "flat_fast":
        from .lu_fast import blocked_getrf_fast

        return blocked_getrf_fast(Gp, _lu_fast_nb(Gp.shape[1]))
    return blocked_getrf(Gp, nb)
