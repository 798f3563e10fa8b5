"""Fast native blocked Householder QR for TPU.

The vendor geqrf lowering runs at ~27 GF/s in f64 on this chip (same
schedule-bound story as the vendor cholesky/LU — see ops/chol_kernels.py
and ops/lu_fast.py).  This module rebuilds the reference's CAQR-style
blocked schedule (reference: src/geqrf.cc:150-220 — local panel factor,
compact-WY T, trailing larfb with the trailing gemms dominating) as the
same three-level TPU schedule as lu_fast:

* micro level (``_qr_panel_strips``): fori_loop over ib-wide strips of
  an (m, nb) panel; per column a larfg reflector + rank-1 update of the
  strip tail; per strip a compact-WY T (larft) and one block-reflector
  application to the rest of the panel (two MXU gemms).
* panel level (``_block_qr``): fori_loop over the nb-wide panels of an
  (m, W) coarse block (rolled active region, single compiled shape);
  per panel a (nb, nb) T and a block-reflector application to the rest
  of the block; the per-panel T factors are stacked and returned.
* coarse level (``geqrf_fast``): <= coarse_panels Python-unrolled
  blocks with exact shrinking shapes; each finished block's panels are
  applied to the remaining global columns as exact-shape gemm pairs.

Returns LAPACK geqrf layout: V unit-lower below the diagonal, R on and
above, plus taus — drop-in for the vendor kernel in ops/householder.py.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .chol_kernels import (
    RECURSIVE_MIN_N,
    _lat_height,
    pallas_compiles,
    split_point,
)
from .householder import _larfg, larft, materialize_v, apply_block_reflector

from ..internal.precision import hdot as _dot


def _conj(x):
    return jnp.conj(x) if jnp.iscomplexobj(x) else x


def _qr_panel_strips(
    P: jnp.ndarray, ib: int = 32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Householder QR of an (m, w) panel whose elimination rows coincide
    with column indices (callers roll the active region to the top).
    w must be a multiple of ib.  Returns (P, taus): V below the
    diagonal, R on/above."""
    m, w = P.shape
    rows = jnp.arange(m)
    colsw = jnp.arange(w)

    def strip(s, carry):
        P, taus = carry
        j0 = s * ib
        S = lax.dynamic_slice(P, (0, j0), (m, ib))
        staus = jnp.zeros((ib,), P.dtype)
        for c in range(ib):
            jc = j0 + c
            x = S[:, c]
            below = rows > jc
            alpha = x[jc]
            xnorm_sq = jnp.sum(jnp.where(below, jnp.abs(x) ** 2, 0.0))
            beta, tau, scale = _larfg(alpha, xnorm_sq, P.dtype)
            v = jnp.where(below, x * scale, jnp.zeros((), P.dtype)).at[jc].set(1.0)
            if c + 1 < ib:
                # apply H^H to the strip tail only (static slice).  The
                # contraction is written as a broadcast-multiply-reduce:
                # the (1, m) x (m, t) matmul form lowers to a ~3x slower
                # MXU path on this toolchain.
                tail = S[:, c + 1 :]
                wrow = (tail * _conj(v)[:, None]).sum(0)
                tail = tail - _conj(tau) * v[:, None] * wrow[None, :]
                S = S.at[:, c + 1 :].set(tail)
            S = S.at[:, c].set(jnp.where(below, v, x).at[jc].set(beta))
            staus = staus.at[c].set(tau)
        P = lax.dynamic_update_slice(P, S, (0, j0))
        taus = lax.dynamic_update_slice(taus, staus, (j0,))
        # block-reflector application to the rest of the panel: V from
        # the strip (zeros on/above each column's elimination row)
        V = jnp.where(rows[:, None] > (jnp.arange(ib)[None, :] + j0), S, 0)
        V = V + jnp.where(
            rows[:, None] == (jnp.arange(ib)[None, :] + j0),
            jnp.ones((), P.dtype),
            0,
        )
        T = larft(V, staus)
        cmask = (colsw >= j0 + ib)[None, :]
        W1 = _dot(_conj(V).T, jnp.where(cmask, P, jnp.zeros((), P.dtype)))
        upd = _dot(V, _dot(_conj(T).T, W1))
        return P - jnp.where(cmask, upd, jnp.zeros((), P.dtype)), taus

    taus0 = jnp.zeros((w,), P.dtype)
    return lax.fori_loop(0, w // ib, strip, (P, taus0))


def _block_qr(
    B: jnp.ndarray, nb: int, ib: int = 32
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Householder QR of the first W columns of an (m, W) block, m >= W.
    One fori_loop over the W//nb panels (rolled active region keeps a
    single compiled shape).

    Returns (B, taus, Tstack): B in geqrf layout, Tstack the (W//nb,
    nb, nb) compact-WY factors (reused by the coarse trailing applies).
    """
    m, W = B.shape
    rows = jnp.arange(m)
    colsW = jnp.arange(W)
    nt = W // nb

    def panel(s, carry):
        B, taus, Tstack = carry
        j0 = s * nb
        colblk = lax.dynamic_slice(B, (0, j0), (m, nb))
        rolled = jnp.roll(colblk, -j0, axis=0)
        act = m - j0
        rolled = jnp.where((rows < act)[:, None], rolled, jnp.zeros((), B.dtype))
        Pf, ptaus = _qr_panel_strips(rolled, ib)
        Pn = jnp.roll(Pf, j0, axis=0)
        cur = lax.dynamic_slice(B, (0, j0), (m, nb))
        neu = jnp.where((rows >= j0)[:, None], Pn, cur)
        B = lax.dynamic_update_slice(B, neu, (0, j0))
        taus = lax.dynamic_update_slice(taus, ptaus, (j0,))
        # panel V/T in the block frame
        V = jnp.where(rows[:, None] > (jnp.arange(nb)[None, :] + j0), neu, 0)
        V = V + jnp.where(
            rows[:, None] == (jnp.arange(nb)[None, :] + j0),
            jnp.ones((), B.dtype),
            0,
        )
        T = larft(V, ptaus)
        Tstack = lax.dynamic_update_index_in_dim(Tstack, T, s, 0)
        # apply to the rest of the block
        cmask = (colsW >= j0 + nb)[None, :]
        W1 = _dot(_conj(V).T, jnp.where(cmask, B, jnp.zeros((), B.dtype)))
        upd = _dot(V, _dot(_conj(T).T, W1))
        return B - jnp.where(cmask, upd, jnp.zeros((), B.dtype)), taus, Tstack

    taus0 = jnp.zeros((W,), B.dtype)
    Tstack0 = jnp.zeros((nt, nb, nb), B.dtype)
    return lax.fori_loop(0, nt, panel, (B, taus0, Tstack0))


def geqrf_fast(
    G: jnp.ndarray, nb: int = 512, ib: int = 128, coarse_panels: int = 4
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked Householder QR of an (m, n) array, m >= n, n a multiple
    of nb.  Returns (G_factored, taus) in LAPACK geqrf layout — the
    drop-in contract of the vendor kernel, ~15-20x its measured f64
    rate on the chip."""
    m, n = G.shape
    assert m >= n and n % nb == 0, f"geqrf_fast: bad shape {(m, n)} nb={nb}"
    # ib=128 is tuned at nb=512 (tools/profile_geqrf_ib.py, n=8192:
    # 280 -> 346 GF/s over ib=32); smaller-nb fallback panels must keep
    # at least 4 strips per panel or the strip-level compact-WY applies
    # degenerate into the slow per-column tail path
    ib = min(ib, max(nb // 4, 32))
    nt = n // nb
    taus = jnp.zeros((n,), G.dtype)
    if nt <= 1:
        Gf, taus = _qr_panel_strips(G, ib)
        return Gf, taus

    NB = nb * (-(-nt // coarse_panels))
    k0 = 0
    while k0 < n:
        W = min(NB, n - k0)
        B = G[k0:, k0 : k0 + W]
        Bf, btaus, Tstack = _block_qr(B, nb, ib)
        G = G.at[k0:, k0 : k0 + W].set(Bf)
        taus = taus.at[k0 : k0 + W].set(btaus)
        rest = n - k0 - W
        if rest > 0:
            C = G[k0:, k0 + W :]
            for p in range(W // nb):
                Vp = materialize_v(Bf[:, p * nb : (p + 1) * nb], offset=p * nb)
                C = apply_block_reflector(Vp, Tstack[p], C, trans=True)
            G = G.at[k0:, k0 + W :].set(C)
        k0 += W
    return G, taus


# ---------------------------------------------------------------------------
# Recursive (divide & conquer) schedule (Elmroth & Gustavson, "Applying
# recursion to serial and parallel QR factorization", IBM JRD 44(4),
# 2000 — see PAPERS.md): factor the left column half recursively, apply
# its nb_switch-wide compact-WY panels to the right half at exact
# shapes, recurse on the trailing (m-n1, n-n1) block.  Following E&G's
# hybrid finding, the compact-WY T factors are kept at panel width
# (nb_switch) rather than combined across halves — a combined
# half-width T costs O(n^3) extra gemm FLOPs at the top split, which is
# exactly the waste this schedule exists to remove.
# ---------------------------------------------------------------------------


def _pick_ib(w: int, ib: int) -> int:
    for d in (ib, 32, 16, 8, 4, 2, 1):
        if d <= ib and w % d == 0:
            return d
    return 1


def _geqrf_rec(G, nb_switch, ib, family="recursive"):
    """Returns (G_factored, taus, panels): panels = [(offset, w, T)]
    for each nb_switch-wide base panel, T its compact-WY factor in the
    frame of G (reflector j of the panel eliminates row offset+j).
    ``family="pallas"`` assembles T through the fused compact-WY kernel
    (ops/pallas/panel_kernels.larft) instead of the jnp assembly."""
    m, n = G.shape
    if n <= nb_switch:
        P, taus = _qr_panel_strips(G, _pick_ib(n, ib))
        if family == "pallas":
            from .pallas import panel_kernels as pk

            T = pk.larft(materialize_v(P), taus)
        else:
            T = larft(materialize_v(P), taus)
        return P, taus, [(0, n, T)]
    s = split_point(n)
    F1, t1, P1 = _geqrf_rec(G[:, :s], nb_switch, ib, family)
    # apply the left half's panels to the right half, oldest first
    # (Q^H C applies the leftmost panel's reflectors first).  V is kept
    # full height (zeros above the panel offset) so the gemm shapes stay
    # on the lattice — the zero-row waste is O(nb/n) and accounted.
    C = G[:, s:]
    for off, w, T in P1:
        V = materialize_v(F1[:, off : off + w], offset=off)
        C = apply_block_reflector(V, T, C, trans=True)
    # canonical-lattice height for the trailing block: zero row pad
    # keeps R/taus/reflectors identical and the distinct compiled
    # heights O(log) (see chol_kernels._lat_height)
    mc = _lat_height(m - s)
    C2 = C[s:]
    if mc > m - s:
        C2 = jnp.pad(C2, ((0, mc - (m - s)), (0, 0)))
    F2, t2, P2 = _geqrf_rec(C2, nb_switch, ib, family)
    F2 = F2[: m - s]
    out = jnp.concatenate(
        [F1, jnp.concatenate([C[:s], F2], axis=0)], axis=1
    )
    panels = P1 + [(off + s, w, T) for off, w, T in P2]
    return out, jnp.concatenate([t1, t2]), panels


def geqrf_recursive(
    G: jnp.ndarray, nb_switch: int = 256, ib: int = 32,
    family: str = "recursive",
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Divide & conquer blocked Householder QR of (m, n), m >= n, any n.
    Returns (G_factored, taus) in LAPACK geqrf layout — the drop-in
    contract of ``geqrf_fast`` / the vendor kernel.

    Shapes shrink statically down the halving lattice: base panels
    factor at exact (canonical-lattice) heights, trailing applies are
    exact-width gemm pairs — executed FLOPs land within ~1.4x of the
    2 n^2 (m - n/3) model (the flat ``_block_qr`` inner loop runs every
    apply at full block width), from O(log) distinct width shapes and
    O(log) canonical heights (``geqrf_schedule_flops`` accounts both).
    """
    m, n = G.shape
    assert m >= n, f"geqrf_recursive: need m >= n, got {(m, n)}"
    mc = _lat_height(m)
    if mc != m:
        # zero pad rows: QR of [A; 0] has the same R and taus, reflector
        # entries in pad rows are exact zeros (larfg of a zero tail)
        Gp = jnp.pad(G, ((0, mc - m), (0, 0)))
        F, taus, _ = _geqrf_rec(Gp, nb_switch, ib, family)
        return F[:m], taus
    F, taus, _ = _geqrf_rec(G, nb_switch, ib, family)
    return F, taus


def geqrf_pallas(
    G: jnp.ndarray, nb_switch: int = 256, ib: int = 32
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recursive lattice with the compact-WY base case on the fused
    Pallas kernel — a positional-only entry point the drivers can wrap
    in a gated jit with static (nb_switch, ib)."""
    return geqrf_recursive(G, nb_switch, ib, family="pallas")


def flat_nb(n: int) -> int:
    """The block size the flat schedule uses for width n — one picker
    shared by the kernel dispatch and the FLOP accounting (the same
    512/256/128 ladder as householder.geqrf)."""
    for nbf in (512, 256, 128):
        if n % nbf == 0:
            return nbf
    return 0  # no flat tiling exists for this width


def geqrf_flat(G: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The flat three-level schedule at its own block-size pick — the
    explicit Option.Schedule=flat entry point (honored on every
    backend, like the chol/lu flat routes)."""
    return geqrf_fast(G, flat_nb(G.shape[1]))


def resolve_qr_schedule(m: int, n: int, dtype, schedule: str = "auto") -> str:
    """The route the eager QR dispatch takes for this shape/backend —
    one resolver shared by the driver's kernel choice and its FLOP
    accounting so the recorded factor.geqrf.* counters always describe
    the program actually traced.  Explicit flat/recursive are honored
    on every backend (when the shape admits them); auto mirrors
    householder.geqrf: vendor LAPACK on CPU and at small/rectangular
    shapes, the native schedules at large n on accelerators."""
    import jax

    from .householder import _geqrf_xla

    if schedule in ("recursive", "pallas") and m >= n:
        return schedule
    tiled = m >= n and flat_nb(n) > 0
    if schedule == "flat" and tiled:
        return "flat"
    if schedule == "auto":
        if jax.default_backend() != "cpu" and m >= n and n >= RECURSIVE_MIN_N:
            return "pallas" if pallas_compiles(dtype) else "recursive"
        if jax.default_backend() != "cpu" and n >= 1024 and tiled:
            return "flat"
    if _geqrf_xla is not None:
        return "vendor"
    # no XLA geqrf primitive: householder.geqrf_blocked runs — book the
    # tiled case as flat (it is a masked blocked loop); the untiled
    # corner keeps the vendor model (unreachable on this toolchain)
    return "flat" if tiled else "vendor"


def _rec_widths(n: int, nb_switch: int):
    """Base-panel widths of the column recursion, left to right."""
    if n <= nb_switch:
        return [n]
    s = split_point(n)
    return _rec_widths(s, nb_switch) + _rec_widths(n - s, nb_switch)


def geqrf_schedule_flops(
    m: int,
    n: int,
    nb: int = 512,
    schedule: str = "recursive",
    nb_switch: int = 256,
    ib: int = 32,
    m_true: int | None = None,
    n_true: int | None = None,
) -> dict:
    """(model, exec, units) FLOP accounting for one QR of (m, n),
    m >= n, mirroring the executed schedule.  model = 2 n^2 (m - n/3),
    the LAPACK geqrf count (compact-WY T formation is schedule
    overhead, counted in exec only) — computed from (m_true, n_true)
    when given so padded kernel shapes report waste against the TRUE
    problem size."""
    mt, nt_ = (m_true or m), (n_true or n)
    model = 2.0 * float(nt_) * nt_ * (mt - nt_ / 3.0)
    if schedule == "vendor":
        # the vendor kernel still runs on the PADDED array
        return {"model": model,
                "exec": 2.0 * float(n) * n * (m - n / 3.0),
                "units": {("vendor_qr", m, n)}}

    # the pallas compact-WY kernel fuses the same Gram + assembly FLOPs
    # (vendor solve stays at <= nb both ways) — only the unit differs
    panel_unit = "pallas_qr_panel" if schedule == "pallas" else "qr_panel"

    def base_flops(M, w):
        ibb = _pick_ib(w, ib)
        strips = max(w // ibb, 1)
        # per strip: micro rank-1s + two full-panel-width masked WY gemms
        ex = strips * (2.0 * M * ibb * ibb + 4.0 * M * ibb * w)
        ex += 2.0 * M * w * w + w**3 / 3.0  # larft (VhV + solve)
        return ex, {(panel_unit, M, w)}

    if schedule == "flat":
        # geqrf_fast at the dispatch's own block-size pick (flat_nb —
        # NOT the driver's lay.nb): <= 4 coarse blocks; _block_qr
        # applies every panel at the full block width (masked), coarse
        # applies exact
        nbf = flat_nb(n) or (nb if n % nb == 0 else 128)
        nt = max(n // nbf, 1)
        NB = nbf * (-(-nt // 4))
        ex, units = 0.0, set()
        k0 = 0
        while k0 < n:
            W = min(NB, n - k0)
            M = m - k0
            for _ in range(W // nbf):
                fb, ub = base_flops(M, nbf)
                ex += fb + 4.0 * M * nbf * W  # full-width masked apply
                units |= ub
            units |= {("qr_apply", M, nbf, W)}
            rest = n - k0 - W
            if rest > 0:
                ex += (W // nbf) * 4.0 * M * nbf * rest
                units |= {("qr_apply", M, nbf, rest)}
            k0 += W
        return {"model": model, "exec": ex, "units": units}

    def rec(M, n):
        if n <= nb_switch:
            return base_flops(M, n)
        s = split_point(n)
        f1, u1 = rec(M, s)
        fa, ua = 0.0, set()
        for w in _rec_widths(s, nb_switch):
            # full-height apply: 2 gemms at (w, M, n-s) + the T multiply
            fa += 4.0 * M * w * (n - s) + 2.0 * w * w * (n - s)
            ua |= {("qr_apply", M, w, n - s)}
        Mc = _lat_height(M - s)
        f2, u2 = rec(Mc, n - s)
        return f1 + fa + f2, u1 | ua | u2

    Mc0 = _lat_height(m)
    ex, units = rec(Mc0, n)
    return {"model": model, "exec": ex, "units": units}
