"""Native blocked Cholesky kernels for TPU.

The vendor ``lax.linalg.cholesky`` lowers to a near-sequential schedule on
this TPU toolchain (measured ~1-5 GF/s at panel sizes, 52 GF/s at n=4096,
against a ~2.6 TF/s f64 matmul rate on the same chip), so the driver-level
potrf was stuck at ~3.5% of gemm speed.  These kernels rebuild the
reference's blocked right-looking schedule (reference: src/potrf.cc:84-209
— panel factor, trsm, trailing herk with the trailing gemm dominating)
out of the ops that ARE fast here:

* ``chol_unblocked``  — column-at-a-time fori_loop Cholesky of one
  nb x nb diagonal block.  The masked rank-1 update is a VPU
  elementwise op (measured ~6 us/column at nb=512), two orders faster
  than the vendor kernel's schedule.
* ``chol_fori``       — single-level blocked loop: one ``lax.fori_loop``
  over nb-wide panels with full-height masked trsm + trailing gemm.
  A compile-lean alternative (one compiled shape regardless of n; the
  default schedule below is ~20% faster but compiles one shape set per
  panel count) — kept off the default path, available to callers that
  factor many distinct sizes.
* ``blocked_potrf``   — two-level schedule for large n: at most
  ``coarse_panels`` Python-unrolled panels of width NB (exact shrinking
  shapes, so the trailing update is a full-rate gemm), each diagonal
  block factored by recursing into ``_chol_panels``/``chol_unblocked``,
  the panel solve done MAGMA-style as an explicit small triangular
  inverse + gemm so the bulk work rides the MXU instead of the slow
  vendor trsm path.

Everything is static-shape; distinct XLA shapes per n are bounded by
O(coarse_panels) to keep compile time in check (measured ~25 s per
distinct f64 trsm shape, ~10 s per gemm shape on this toolchain).

Used by drivers/chol.py for the single-chip (global-path) potrf on
non-CPU backends; the CPU backend keeps the vendor (LAPACK) kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# All matmuls in these kernels run at HIGHEST precision: the TPU default
# for the f64 emulation drops to ~f32-grade accumulation (measured 1e-8
# Cholesky residual vs 1e-12 with HIGHEST), and f32 inputs would drop to
# one bf16 pass (internal/precision.py's policy, applied here directly
# since these kernels are used inside jit where the context manager at
# call sites may not be active).
from ..internal.precision import hdot as _dot


def _conj(x):
    return jnp.conj(x) if jnp.iscomplexobj(x) else x


def chol_unblocked(a: jnp.ndarray, ib: int = 16) -> jnp.ndarray:
    """Cholesky of one (b, b) block: L L^H = a, b a multiple of ib.

    fori_loop over b//ib column strips: the ib columns of a strip are
    eliminated by an unrolled micro-loop touching only the (b, ib)
    strip, then one VPU rank-ib update fixes the trailing columns.
    This keeps the per-iteration memory traffic at O(b*ib) for the
    micro-steps and O(b^2) only once per strip — the column-at-a-time
    variant's O(b^2) *per column* made it bandwidth-bound (~80 us per
    column at b=512 on the chip).

    Non-SPD input yields NaN columns (sqrt of a negative pivot), which
    the caller's info check detects — same contract as the vendor
    kernel.
    """
    b = a.shape[0]
    if b % ib != 0:
        ib = 8 if b % 8 == 0 else 1
    idx = jnp.arange(b)
    nsteps = b // ib

    def body(i, a):
        j0 = i * ib
        P = lax.dynamic_slice(a, (0, j0), (b, ib))
        for c in range(ib):
            jc = j0 + c
            pj = jnp.sqrt(jnp.real(lax.dynamic_slice(P, (jc, c), (1, 1))[0, 0]))
            pj = pj.astype(a.dtype)
            col = jnp.where(idx > jc, P[:, c] / pj, jnp.zeros((), a.dtype))
            P = P.at[:, c].set(jnp.where(idx == jc, pj, col))
            if c + 1 < ib:
                # multipliers for the strip's remaining columns are the
                # scaled L entries at the strip's own pivot rows
                lrow = lax.dynamic_slice(P, (j0, c), (ib, 1))[:, 0]
                lrow = jnp.where(jnp.arange(ib) > c, _conj(lrow), 0)
                P = P - jnp.outer(col, lrow)
        a = lax.dynamic_update_slice(a, P, (0, j0))
        # rank-ib trailing update, restricted to columns >= j0+ib via a
        # row mask on the second operand (upper-triangle junk is dropped
        # by the final tril)
        Q = jnp.where((idx >= j0 + ib)[:, None], P, jnp.zeros((), a.dtype))
        return a - _dot(P, _conj(Q).T)

    return jnp.tril(lax.fori_loop(0, nsteps, body, a))


def chol_fori(G: jnp.ndarray, nb: int = 512) -> jnp.ndarray:
    """Single-level blocked Cholesky of (n, n), n a multiple of nb.

    One fori_loop over the n//nb panels; every step runs at full array
    shape with row masks (one compile unit).  The trailing update is a
    (n, nb) x (nb, n) gemm at EVERY step, so the executed FLOPs are
    ~2 n^3 + n^2 nb against the n^3/3 model — ~6x the model, ~3x the
    exact-shape blocked schedule (see ``chol_schedule_flops``), the
    price of the single compiled shape.  Large-n callers should prefer
    ``chol_recursive``: exact halving-lattice shapes, near-model FLOPs,
    O(log n) compile units.
    """
    n = G.shape[0]
    if n == nb:
        return chol_unblocked(G)
    assert n % nb == 0, "chol_fori requires n % nb == 0"
    rows = jnp.arange(n)

    def step(k, G):
        with jax.named_scope("potrf.panel"):
            Akk = lax.dynamic_slice(G, (k * nb, k * nb), (nb, nb))
            Lkk = chol_unblocked(Akk)
        with jax.named_scope("potrf.trsm"):
            # the panel below the diagonal block, written back with Lkk
            col = lax.dynamic_slice(G, (0, k * nb), (n, nb))
            sol = lax.linalg.triangular_solve(
                Lkk, col, left_side=False, lower=True, transpose_a=True,
                conjugate_a=jnp.iscomplexobj(G),
            )
            below = (rows >= (k + 1) * nb)[:, None]
            Lpan = jnp.where(below, sol, jnp.zeros((), G.dtype))
            diag_rows = ((rows >= k * nb) & (rows < (k + 1) * nb))[:, None]
            Lkk_tall = jnp.pad(Lkk, ((0, n - nb), (0, 0)))
            Lkk_placed = jnp.where(diag_rows,
                                   jnp.roll(Lkk_tall, k * nb, axis=0), 0)
            above = (rows < k * nb)[:, None]
            newcol = jnp.where(above, jnp.zeros((), G.dtype),
                               Lkk_placed + Lpan)
            G = lax.dynamic_update_slice(G, newcol, (0, k * nb))
        with jax.named_scope("potrf.update"):
            return G - _dot(Lpan, _conj(Lpan).T)

    return jnp.tril(lax.fori_loop(0, n // nb, step, G))


def _chol_panels(G: jnp.ndarray, nb: int) -> jnp.ndarray:
    """Python-unrolled blocked Cholesky of (n, n), n a multiple of nb,
    intended for n/nb <= ~4 panels.

    Per panel: chol_unblocked diag, ONE full-height trsm (a single XLA
    shape reused by every panel — each distinct f64 trsm shape costs
    ~15-25 s of compile on this toolchain), then exact-shape trailing
    syrk (full MXU rate where the FLOPs are)."""
    n = G.shape[0]
    cplx = jnp.iscomplexobj(G)
    cols = []
    T = G
    k0 = 0
    while k0 < n:
        w = min(nb, n - k0)
        D = chol_unblocked(T[:w, :w])
        rest = n - k0 - w
        if rest > 0:
            # explicit (w, w) inverse + MXU gemm instead of a
            # full-height vendor trsm: the vendor triangular_solve with
            # a fat RHS is schedule-bound on this toolchain (~10-25 ms
            # per panel) while the small trsm + gemm ride the MXU —
            # the same MAGMA recipe blocked_potrf uses at the coarse
            # level
            Dinv = lax.linalg.triangular_solve(
                D, jnp.eye(w, dtype=G.dtype), left_side=True, lower=True
            )
            L21 = _dot(T[w:, :w], _conj(Dinv).T)
            T = T[w:, w:] - _dot(L21, _conj(L21).T)
            colk = jnp.concatenate(
                [jnp.zeros((k0, w), G.dtype), D, L21], axis=0
            )
        else:
            colk = jnp.concatenate([jnp.zeros((k0, w), G.dtype), D], axis=0)
        cols.append(colk)
        k0 += w
    return jnp.concatenate(cols, axis=1)


def blocked_potrf(
    G: jnp.ndarray, nb: int = 512, coarse_panels: int = 4
) -> jnp.ndarray:
    """Blocked Cholesky factor L (lower) of an SPD (n, n) array.

    n must be a multiple of 128 (callers pad with a unit-diagonal
    splice).  Schedule (reference: src/potrf.cc:84-209, with the
    lookahead pipeline replaced by XLA's own overlap inside one
    compiled program):

      for each of <= coarse_panels column panels of width NB:
        D    = recursive factor of T[:NB,:NB]      # exact-shape panels
        Dinv = trsm(D, I)                          # one small trsm
        L21  = T[NB:,:NB] @ Dinv^H                 # MXU gemm
        T    = T[NB:,NB:] - L21 @ L21^H            # MXU gemm (dominant)

    Exact shrinking shapes per panel (full-rate gemms); the explicit
    panel inverse trades one (NB,NB) trsm for MXU gemms, the MAGMA
    recipe.  Distinct XLA shapes stay O(coarse_panels + recursion
    depth): the diag-block shapes repeat across panels.
    """
    n = G.shape[0]
    if n <= 256:
        return chol_unblocked(G)
    nb = min(nb, n)
    if n % nb != 0:
        nb = 256 if n % 256 == 0 else 128
    assert n % nb == 0, f"blocked_potrf: n={n} not a multiple of 128"
    nt = n // nb
    if nt <= coarse_panels:
        return _chol_panels(G, nb)

    NB = nb * (-(-nt // coarse_panels))
    cols = []
    T = G
    k0 = 0
    eyeNB = None
    while k0 < n:
        w = min(NB, n - k0)
        D = blocked_potrf(T[:w, :w], nb, coarse_panels)
        rest = n - k0 - w
        if rest > 0:
            if eyeNB is None or eyeNB.shape[0] != w:
                eyeNB = jnp.eye(w, dtype=G.dtype)
            Dinv = lax.linalg.triangular_solve(
                D, eyeNB, left_side=True, lower=True
            )
            L21 = _dot(T[w:, :w], _conj(Dinv).T)
            T = T[w:, w:] - _dot(L21, _conj(L21).T)
            colk = jnp.concatenate(
                [jnp.zeros((k0, w), G.dtype), D, L21], axis=0
            )
        else:
            colk = jnp.concatenate([jnp.zeros((k0, w), G.dtype), D], axis=0)
        cols.append(colk)
        k0 += w
    return jnp.concatenate(cols, axis=1)


def tri_inv_blocked(L: jnp.ndarray, nb: int = 512) -> jnp.ndarray:
    """Explicit inverse of a lower-triangular matrix by recursive
    2x2 blocking: inv([[A,0],[B,C]]) = [[inv(A),0],[-inv(C) B inv(A),
    inv(C)]] — two half-size inverses + two MXU gemms per level; the
    vendor triangular_solve only ever sees <= nb-sized blocks (the
    full-size vendor trsm is schedule-bound on this toolchain, the
    same finding as _chol_panels')."""
    n = L.shape[0]
    if n <= nb:
        return lax.linalg.triangular_solve(
            L, jnp.eye(n, dtype=L.dtype), left_side=True, lower=True
        )
    h = max(((n + 1) // 2 + 127) // 128 * 128, 128)
    h = min(h, n - 1)
    A = L[:h, :h]
    B = L[h:, :h]
    C = L[h:, h:]
    Ai = tri_inv_blocked(A, nb)
    Ci = tri_inv_blocked(C, nb)
    lowblk = -_dot(Ci, _dot(B, Ai))
    top = jnp.concatenate([Ai, jnp.zeros((h, n - h), L.dtype)], axis=1)
    bot = jnp.concatenate([lowblk, Ci], axis=1)
    return jnp.concatenate([top, bot], axis=0)


# ---------------------------------------------------------------------------
# Recursive (divide & conquer) schedule: exact shapes on the halving
# lattice.  The flat loops above pay for their single compiled shape in
# raw FLOPs (chol_fori: ~6x the n^3/3 model); the recursion factors the
# top-left half, solves/updates the off-diagonal block and trailing half
# at their *exact* static shapes (n, n/2, n/4, ... — at most O(log n)
# distinct compile units), with the flat kernels kept as the small-n
# base case below the ``nb_switch`` crossover.
# ---------------------------------------------------------------------------


# auto-schedule crossover: below this the flat/blocked schedules win
# (recursion overhead + compile-unit count buy nothing at small n)
RECURSIVE_MIN_N = 2048


def pallas_compiles(dtype) -> bool:
    """Whether the Pallas panel kernels compile for this dtype here:
    f32 on the TPU only (Mosaic has no f64/complex vectors).  ``auto``
    takes the ``pallas`` family above the crossover exactly then; every
    other dtype takes a loop schedule (``chol_fori``, or
    ``blocked_getrf``'s at most 4 loops), because the TPU emulates
    f64 and each op unrolled into a program costs about a second of
    compile (n=8192 f64 posv for a described v5e: recursive 515 s,
    ``chol_fori`` 11 s)."""
    from .pallas.kernels import on_tpu

    return on_tpu() and jnp.dtype(dtype) == jnp.float32


def split_point(n: int) -> int:
    """Top-half size of the recursion: ceil(n/2) rounded up to the best
    MXU alignment that still leaves a nonempty trailing half.  For the
    serve halving-lattice sizes (2^k * 64/128) this is exactly n/2, so
    every recursion shape lands back on the lattice the warmup manifest
    already covers."""
    h = (n + 1) // 2
    for a in (128, 64, 32, 16, 8):
        ha = -(-h // a) * a
        if ha < n:
            return ha
    return h


def _lat_height(M: int) -> int:
    """Round M up to the nearest 2^k or 3*2^(k-1) (1.0x or 1.5x a power
    of two — exactly two values per octave).  The tall LU/QR recursions
    produce operand heights m - k*nb — O(n/nb) distinct values;
    snapping them to this lattice keeps distinct compiled shapes
    O(log^2) at <= 33% zero-row padding, and halving-lattice sizes map
    to themselves."""
    if M <= 0:
        return 0
    k = M.bit_length() - 1
    if M == 1 << k:
        return M
    c15 = 3 << (k - 1)  # 1.5 * 2^k
    return c15 if M <= c15 else 1 << (k + 1)


def _trsm_right_lh(L: jnp.ndarray, A: jnp.ndarray, nb: int) -> jnp.ndarray:
    """X L^H = A with L lower triangular, by recursive 2x2 splitting:
    the vendor triangular_solve only ever sees <= nb diagonal blocks
    (the full-size vendor trsm is schedule-bound on this toolchain, the
    _chol_panels finding) and the bulk work rides exact-shape MXU gemms
    at exactly the model FLOP count (t h^2)."""
    h = L.shape[0]
    if h <= nb:
        return lax.linalg.triangular_solve(
            L, A, left_side=False, lower=True, transpose_a=True,
            conjugate_a=jnp.iscomplexobj(A),
        )
    s = split_point(h)
    X1 = _trsm_right_lh(L[:s, :s], A[:, :s], nb)
    X2 = _trsm_right_lh(
        L[s:, s:], A[:, s:] - _dot(X1, _conj(L[s:, :s]).T), nb
    )
    return jnp.concatenate([X1, X2], axis=1)


def _base_chol(G: jnp.ndarray, family: str) -> jnp.ndarray:
    """Base-case dispatch: the ib-strip chol_unblocked (recursive
    family) or the fused Pallas kernel (pallas family)."""
    if family == "pallas":
        from .pallas import panel_kernels as pk

        return pk.chol_base(G)
    return chol_unblocked(G)


def _syrk_lower(
    C: jnp.ndarray, A: jnp.ndarray, nb: int, family: str = "recursive"
) -> jnp.ndarray:
    """Lower triangle of C - A A^H by triangle recursion: only the
    diagonal nb-blocks pay the full-square gemm, the off-diagonal
    blocks are plain exact-shape gemms — executed FLOPs t^2 h + O(nb t h)
    against the t^2 h syrk model, killing the 2x a full-square gemm
    would cost.  Entries above the diagonal pass through untouched
    (callers only consume the lower triangle).  The pallas family fuses
    the diagonal-block triangle mask and the off-diagonal
    multiply-subtract into single kernels at identical shapes/FLOPs."""
    t = C.shape[0]
    if family == "pallas":
        from .pallas import panel_kernels as pk

        if t <= nb:
            return pk.syrk_diag(C, A)
        s = split_point(t)
        C11 = _syrk_lower(C[:s, :s], A[:s], nb, family)
        C21 = pk.gemm_sub(C[s:, :s], A[s:], A[:s])
        C22 = _syrk_lower(C[s:, s:], A[s:], nb, family)
        top = jnp.concatenate([C11, C[:s, s:]], axis=1)
        bot = jnp.concatenate([C21, C22], axis=1)
        return jnp.concatenate([top, bot], axis=0)
    if t <= nb:
        return C - _dot(A, _conj(A).T)
    s = split_point(t)
    C11 = _syrk_lower(C[:s, :s], A[:s], nb)
    C21 = C[s:, :s] - _dot(A[s:], _conj(A[:s]).T)
    C22 = _syrk_lower(C[s:, s:], A[s:], nb)
    top = jnp.concatenate([C11, C[:s, s:]], axis=1)
    bot = jnp.concatenate([C21, C22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def _chol_rec(G: jnp.ndarray, nb: int, family: str = "recursive") -> jnp.ndarray:
    n = G.shape[0]
    if n <= nb:
        with jax.named_scope("potrf.panel"):
            return _base_chol(G, family)
    s = split_point(n)
    L11 = _chol_rec(G[:s, :s], nb, family)
    with jax.named_scope("potrf.trsm"):
        L21 = _trsm_right_lh(L11, G[s:, :s], nb)
    with jax.named_scope("potrf.update"):
        S = _syrk_lower(G[s:, s:], L21, nb, family)
    L22 = _chol_rec(S, nb, family)
    top = jnp.concatenate([L11, jnp.zeros((s, n - s), G.dtype)], axis=1)
    bot = jnp.concatenate([L21, L22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def chol_recursive(
    G: jnp.ndarray, nb_switch: int = 256, lookahead: int = 1,
    family: str = "recursive",
) -> jnp.ndarray:
    """Divide & conquer Cholesky factor L (lower) of an SPD (n, n) array.

    Schedule: factor the top-left half, solve the off-diagonal block
    (recursive trsm, vendor solves only at <= nb_switch), subtract the
    exact-shape triangle-recursive syrk, recurse on the trailing half.
    Shapes shrink statically down the halving lattice (n, n/2, n/4, ...)
    so the dominant gemms run at their exact shapes — executed FLOPs stay
    within ~1.3x of the n^3/3 model at n/nb_switch >= 8 (the flat
    ``chol_fori`` runs ~6x; see ``chol_schedule_flops``) from O(log n)
    distinct compile units.

    ``lookahead`` follows the reference potrf convention (lookahead=1 is
    the baseline pipeline): k > 1 peels k-1 eager ``nb_switch``-wide
    panels ahead of the halving split at the top level, each with
    exact-shape trsm + syrk updates (Option.Lookahead wiring).

    ``family`` selects the base-case/update kernels on the same
    lattice: ``"recursive"`` (the jnp strip kernels) or ``"pallas"``
    (the fused panel kernels in ops/pallas/panel_kernels.py).
    """
    n = G.shape[0]
    if n <= nb_switch:
        with jax.named_scope("potrf.panel"):
            return jnp.tril(_base_chol(G, family))
    cols = []
    T = G
    k0 = 0
    peel = max(int(lookahead) - 1, 0)
    while peel > 0 and (n - k0) > 2 * nb_switch:
        w = nb_switch
        with jax.named_scope("potrf.panel"):
            D = _base_chol(T[:w, :w], family)
        with jax.named_scope("potrf.trsm"):
            L21 = _trsm_right_lh(D, T[w:, :w], nb_switch)
        with jax.named_scope("potrf.update"):
            T = _syrk_lower(T[w:, w:], L21, nb_switch, family)
        cols.append(
            jnp.concatenate([jnp.zeros((k0, w), G.dtype), D, L21], axis=0)
        )
        k0 += w
        peel -= 1
    Lr = _chol_rec(T, nb_switch, family)
    if not cols:
        return jnp.tril(Lr)
    Lr = jnp.concatenate(
        [jnp.zeros((k0, n - k0), G.dtype), Lr], axis=0
    )
    return jnp.tril(jnp.concatenate(cols + [Lr], axis=1))


# ---------------------------------------------------------------------------
# Rank-k Cholesky up/downdate: L' L'^H = L L^H ± U U^H in O(k n^2),
# the incremental-edit path of the serve factor cache (a rank-k change
# to A re-keys a cached factor without the O(n^3) refactor).
# ---------------------------------------------------------------------------


def chol_rank1_update(
    L: jnp.ndarray, u: jnp.ndarray, downdate: bool = False
) -> jnp.ndarray:
    """Rank-1 update (``downdate=False``: A + u u^H) or downdate
    (A - u u^H) of a lower Cholesky factor, column-at-a-time with
    full-vector masks (one fori_loop, static shapes — O(n^2) work).

    Per column k (lkk = L[k,k] real-positive, sigma = ±1):
    ``t = u[k]/lkk``, ``c = sqrt(1 + sigma |t|^2)``, then
    ``L'[j,k] = (L[j,k] + sigma conj(t) u[j]) / c`` for j > k,
    ``L'[k,k] = c lkk``, and ``u <- (u - t L[:,k]) / c`` (the OLD
    column) — the hyperbolic analogue of the Givens sweep, valid for
    complex Hermitian A since the diagonal stays real.

    A downdate past positive definiteness (1 - |t|^2 <= 0) yields NaN
    columns via the sqrt, the same breakdown contract as
    ``chol_unblocked`` — callers check finiteness and refactor.
    """
    n = L.shape[0]
    sigma = -1.0 if downdate else 1.0
    idx = jnp.arange(n)
    rdt = jnp.finfo(L.dtype).dtype  # real dtype of (possibly complex) L

    def body(k, carry):
        L, u = carry
        lkk = jnp.real(L[k, k])
        t = u[k] / lkk.astype(L.dtype)
        c = jnp.sqrt(
            jnp.asarray(1.0, rdt) + sigma * jnp.real(t * jnp.conj(t))
        )
        colk = L[:, k]
        below = idx > k
        newcol = jnp.where(
            below,
            (colk + (sigma * jnp.conj(t)) * u) / c.astype(L.dtype),
            colk,
        )
        newcol = newcol.at[k].set((c * lkk).astype(L.dtype))
        u = jnp.where(
            below, (u - t * colk) / c.astype(L.dtype),
            jnp.zeros((), L.dtype),
        )
        return L.at[:, k].set(newcol), u

    L, _ = lax.fori_loop(0, n, body, (L, u.astype(L.dtype)))
    return jnp.tril(L)


def chol_update(
    L: jnp.ndarray, U: jnp.ndarray, downdate: bool = False
) -> jnp.ndarray:
    """Rank-k Cholesky up/downdate: ``L' L'^H = L L^H ± U U^H`` with U
    of shape (n, k) or (n,) — k sequential rank-1 sweeps (each column's
    sweep transforms only L; the columns are independent updates of the
    running factor).  O(k n^2) total; ``downdate`` is static."""
    U2 = U if U.ndim == 2 else U[:, None]
    n, k = U2.shape

    def body(i, L):
        u = lax.dynamic_slice(U2, (0, i), (n, 1))[:, 0]
        return chol_rank1_update(L, u, downdate)

    return lax.fori_loop(0, k, body, L)


# ---------------------------------------------------------------------------
# FLOP accounting.  Pure-python structural mirrors of the schedules
# above: every gemm/trsm/base-case the traced program will execute is
# counted at the shape it executes at (masked full-shape ops count at
# full shape — that IS the waste being measured).  The drivers feed
# these into the ``factor.flops_model`` / ``factor.flops_exec`` metric
# counters so the waste ratio is observable per routine; the ``units``
# set of distinct (op, shape) tuples bounds the schedule's compile-unit
# count (the recursive paths stay O(log n) vs the data-dependent-free
# but FLOP-hungry flat loops' O(1)).
# ---------------------------------------------------------------------------


def _chol_unblocked_flops(b: int, ib: int = 16):
    if b % ib != 0:
        ib = 8 if b % 8 == 0 else 1
    nsteps = max(b // ib, 1)
    # per strip: one full-shape rank-ib trailing gemm + ib masked rank-1
    # micro-updates on the (b, ib) strip
    return nsteps * (2.0 * b * ib * b + 2.0 * b * ib * ib), {
        ("chol_base", b)
    }


def _chol_base_flops(b: int, family: str = "recursive"):
    if family == "pallas":
        # fused column loop: b masked rank-1 trailing updates on the
        # (b, b) block — no per-strip overhead, strictly below the
        # ib-strip count
        return 2.0 * float(b) ** 3, {("pallas_chol_base", b)}
    return _chol_unblocked_flops(b)


def _trsm_flops(t: int, h: int, nb: int):
    """Executed FLOPs of _trsm_right_lh / the unit-lower left variant in
    lu_kernels (identical split structure): exactly the t h^2 model."""
    if h <= nb:
        return float(t) * h * h, {("trsm", h, t)}
    s = split_point(h)
    f1, u1 = _trsm_flops(t, s, nb)
    f2, u2 = _trsm_flops(t, h - s, nb)
    return f1 + f2 + 2.0 * t * s * (h - s), u1 | u2 | {("gemm", t, s, h - s)}


def _syrk_flops(t: int, h: int, nb: int, family: str = "recursive"):
    diag = "pallas_syrk" if family == "pallas" else "gemm"
    offd = "pallas_gemm" if family == "pallas" else "gemm"
    if t <= nb:
        return 2.0 * t * t * h, {(diag, t, h, t)}
    s = split_point(t)
    f1, u1 = _syrk_flops(s, h, nb, family)
    f2, u2 = _syrk_flops(t - s, h, nb, family)
    return f1 + f2 + 2.0 * (t - s) * h * s, u1 | u2 | {
        (offd, t - s, h, s)
    }


def _chol_rec_flops(n: int, nb: int, family: str = "recursive"):
    if n <= nb:
        return _chol_base_flops(n, family)
    s = split_point(n)
    f1, u1 = _chol_rec_flops(s, nb, family)
    ft, ut = _trsm_flops(n - s, s, nb)
    fs, us = _syrk_flops(n - s, s, nb, family)
    f2, u2 = _chol_rec_flops(n - s, nb, family)
    return f1 + ft + fs + f2, u1 | ut | us | u2


def _chol_panels_flops(n: int, nb: int):
    """_chol_panels / blocked_potrf coarse level: exact shapes but the
    explicit panel inverse (MAGMA recipe) and full-square trailing gemm
    both cost real FLOPs."""
    fl, units = 0.0, set()
    k0 = 0
    while k0 < n:
        w = min(nb, n - k0)
        fb, ub = _chol_unblocked_flops(w)
        fl += fb
        units |= ub
        rest = n - k0 - w
        if rest > 0:
            fl += w**3 / 2.0  # Dinv trsm vs identity
            fl += 2.0 * rest * w * w  # L21 gemm
            fl += 2.0 * rest * rest * w  # full-square trailing gemm
            units |= {("trsm", w, w), ("gemm", rest, w, w),
                      ("gemm", rest, w, rest)}
        k0 += w
    return fl, units


def _blocked_potrf_flops(n: int, nb: int = 512, coarse_panels: int = 4):
    if n <= 256:
        return _chol_unblocked_flops(n)
    nb = min(nb, n)
    if n % nb != 0:
        nb = 256 if n % 256 == 0 else 128
    nt = n // nb
    if nt <= coarse_panels:
        return _chol_panels_flops(n, nb)
    NB = nb * (-(-nt // coarse_panels))
    fl, units = 0.0, set()
    k0 = 0
    while k0 < n:
        w = min(NB, n - k0)
        fd, ud = _blocked_potrf_flops(w, nb, coarse_panels)
        fl += fd
        units |= ud
        rest = n - k0 - w
        if rest > 0:
            fl += w**3 / 2.0 + 2.0 * rest * w * w + 2.0 * rest * rest * w
            units |= {("trsm", w, w), ("gemm", rest, w, w),
                      ("gemm", rest, w, rest)}
        k0 += w
    return fl, units


def _chol_fori_flops(n: int, nb: int):
    if n == nb:
        return _chol_unblocked_flops(n)
    steps = n // nb
    fb, ub = _chol_unblocked_flops(nb)
    # per step: full-height trsm + full (n, nb) x (nb, n) trailing gemm
    per = float(n) * nb * nb + 2.0 * n * nb * n
    return steps * (fb + per), ub | {("trsm", nb, n), ("gemm", n, nb, n)}


def chol_schedule_flops(
    n: int, nb: int = 512, schedule: str = "recursive",
    nb_switch: int = 256, lookahead: int = 1,
) -> dict:
    """(model, exec, units) FLOP accounting for one Cholesky of size n
    under the given schedule (after the dispatcher's pad to a multiple
    of 128).  ``model`` is the textbook n^3/3; ``exec`` counts what the
    traced program actually issues; ``units`` is the set of distinct
    (op, shape) compile units in the schedule."""
    npad = -(-n // 128) * 128
    model = n**3 / 3.0
    if schedule == "vendor":
        return {"model": model, "exec": float(model),
                "units": {("vendor_potrf", n)}}
    if schedule == "flat":
        ex, units = _blocked_potrf_flops(npad, nb)
    elif schedule == "flat_fori":
        ex, units = _chol_fori_flops(npad, nb if npad % nb == 0 else 128)
    else:
        fam = "pallas" if schedule == "pallas" else "recursive"
        ex, units = 0.0, set()
        k0, peel = 0, max(int(lookahead) - 1, 0)
        if npad <= nb_switch:
            ex, units = _chol_base_flops(npad, fam)
        else:
            while peel > 0 and (npad - k0) > 2 * nb_switch:
                w = nb_switch
                fb, ub = _chol_base_flops(w, fam)
                ft, ut = _trsm_flops(npad - k0 - w, w, nb_switch)
                fs, us = _syrk_flops(npad - k0 - w, w, nb_switch, fam)
                ex += fb + ft + fs
                units |= ub | ut | us
                k0 += w
                peel -= 1
            fr, ur = _chol_rec_flops(npad - k0, nb_switch, fam)
            ex += fr
            units |= ur
    return {"model": model, "exec": ex, "units": units}


def resolve_schedule(n: int, dtype, schedule: str = "auto") -> str:
    """Resolve an ``auto`` schedule request against the backend, size
    and dtype: vendor LAPACK on CPU; on accelerators the flat/blocked
    schedule below the crossover and above it ``pallas`` where its
    kernels compile (``pallas_compiles``), the single-loop
    ``flat_fori`` otherwise.  Explicit ``flat``/``recursive``/``pallas``
    are honored on every backend (tests exercise the native schedules
    on CPU — pallas runs its kernels in interpret mode there)."""
    if schedule in ("flat", "recursive", "pallas"):
        return schedule
    if jax.default_backend() == "cpu":
        return "vendor"
    if n < RECURSIVE_MIN_N:
        return "flat"
    return "pallas" if pallas_compiles(dtype) else "flat_fori"


def cholesky(
    G: jnp.ndarray,
    nb: int = 512,
    schedule: str = "auto",
    nb_switch: int = 256,
    lookahead: int = 1,
) -> jnp.ndarray:
    """Schedule-dispatched Cholesky: vendor kernel on CPU under ``auto``
    (LAPACK — already optimal), native blocked (``flat``), single-loop
    (``flat_fori``, auto's non-Pallas route on accelerators) or divide
    & conquer (``recursive``/``pallas``, crossover ``nb_switch``)
    schedule otherwise.

    Accepts any n: pads to a multiple of 128 with a unit-diagonal
    splice (chol of blockdiag(A, I) is blockdiag(L, I)) and slices the
    factor back out."""
    n = G.shape[0]
    route = resolve_schedule(n, G.dtype, schedule)
    if route == "vendor":
        # the lower triangle only, like every native route (the default
        # symmetrize_input averages in the upper triangle)
        return lax.linalg.cholesky(G, symmetrize_input=False)
    npad = -(-n // 128) * 128
    if npad != n:
        # pad first even at small n so chol_unblocked keeps its ib=16
        # strips (odd n would degrade it to column-at-a-time)
        Gp = jnp.pad(G, ((0, npad - n), (0, npad - n)))
        idx = jnp.arange(npad)
        splice = jnp.where(idx >= n, 1.0, 0.0).astype(G.dtype)
        Gp = Gp.at[idx, idx].add(splice)
        return _native_chol(Gp, route, nb, nb_switch, lookahead)[:n, :n]
    return _native_chol(G, route, nb, nb_switch, lookahead)


def _native_chol(G, route, nb, nb_switch, lookahead):
    if route in ("recursive", "pallas"):
        return chol_recursive(G, nb_switch, lookahead, route)
    if route == "flat_fori":
        return chol_fori(G, nb if G.shape[0] % nb == 0 else 128)
    return blocked_potrf(G, nb)
