"""Native divide & conquer symmetric tridiagonal eigensolver (stedc).

The reference implements Cuppen's D&C across ~2.5 kLoC
(reference: src/stedc.cc, stedc_deflate.cc:1-595, stedc_merge.cc:23-31
laed4 secular roots, stedc_secular.cc, stedc_solve.cc, stedc_sort.cc,
stedc_z_vector.cc).  This is the TPU-native redesign: the merge tree is
a bottom-up loop over log2(N) levels, every level's merges run as ONE
vmapped batch, the laed4 secular roots are found by vectorized
bisection+Newton (all roots of all merges in parallel — pure VPU work),
deflation is masked compaction-free arithmetic (static shapes), and the
O(n^3) back-rotation Q @ U is a batched MXU gemm — which is where the
FLOPs land, exactly as in the reference.

Key numerical devices (same as LAPACK dlaed3/dlaed4):

* secular roots are solved in pole-shifted coordinates mu = lambda -
  d_i, so lambda - d_j = (d_i - d_j) + mu stays accurate for the
  eigenvector assembly;
* the z-vector is *recomputed* from the computed roots via the Lowner
  formula (Gu-Eisenstat), which makes the assembled eigenvectors
  numerically orthogonal even for clustered poles;
* deflation: (a) tiny rho*|z_j| passes the eigenpair through directly,
  (b) near-equal pole pairs are combined by Givens rotations in
  alternating even/odd passes (vectorized; handles clusters up to
  ~2^passes wide — degenerate wider clusters still deflate via (a)
  after the rotations concentrate their weight).

The subproblem boundary adjustment (Cuppen subtracts |e_m| from both
boundary diagonals before recursing) telescopes: in a full binary tree
every interior edge is cut exactly once, so the size-1 leaves start
from d_j - |e_{j-1}| - |e_j| and each merge's rank-one term restores
its own edge.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the emulation-safe HIGHEST matmul: k-chunks cancellation-sensitive
# f64 contractions at k >= 4096 (the chip's emulation loses its
# compensation there — see internal/precision.py).  The merge
# back-rotations and the final polish are exactly such products.
from ..internal.precision import hdot as _dot

_BISECT = 18  # geometric bisection phase: localizes to ~2e-4 relative
_NEWTON = 10  # hybrid Newton/geometric phase: eps from there


_barrier_warned = False


def _opt_barrier(xs):
    """lax.optimization_barrier, degrading to identity where the
    toolchain lacks its batching rule (the merges are vmapped; some jax
    versions cannot batch the barrier).  The barrier only defends the
    chip's f64-emulated reductions against log->sum fusion — on real-f64
    backends dropping it is numerically harmless; on emulated-f64
    backends the degradation is surfaced via a one-time warning and the
    `stedc.barrier_dropped` metrics counter."""
    global _barrier_warned
    try:
        return lax.optimization_barrier(xs)
    except NotImplementedError:
        from ..aux import metrics as _metrics

        _metrics.inc("stedc.barrier_dropped")
        if not _barrier_warned and jax.default_backend() not in ("cpu", "gpu"):
            import warnings

            warnings.warn(
                "optimization_barrier unsupported under vmap on this jax; "
                "stedc's emulated-f64 fusion guard is dropped — "
                "eigenvector orthogonality may degrade",
                stacklevel=2,
            )
            _barrier_warned = True
        return xs


def _secular_roots(D, z2, rho, nondefl, nxt_idx, gap_hi):
    """Vectorized secular roots with nearest-pole shifting (the laed4
    numerics, reference: src/stedc_merge.cc:23-31 / LAPACK dlaed4).

    For each nondeflated i the root of
        f(lam) = 1 + rho * sum_j z2_j / (D_j - lam)
    in (D_i, D_i + gap_hi_i) is located as lam = D[k_i] + sgn_i * x_i,
    where k_i is the nearer bracket pole (decided by the sign of f at
    the interval midpoint) and x_i > 0 the offset from it.  x is found
    by *geometric* bisection (midpoint sqrt(lo*hi)), which delivers
    RELATIVE precision — a root can sit many orders of magnitude closer
    to its pole than the interval width (small z_i), where arithmetic
    bisection and Newton both stall — followed by a keep-best Newton
    polish.

    Returns (kshift, sgn, x): lam_i = D[kshift_i] + sgn_i * x_i.
    nxt_idx[i] = index of the next nondeflated pole (n2 if none).
    """
    n2 = D.shape[0]
    dt = D.dtype
    # NOT finfo.tiny: the TPU f64 emulation carries an f32 exponent
    # range — values below ~1e-38 flush to zero — so floors must stay
    # well above it (stedc() normalizes the problem to O(1) scale).
    tiny = jnp.asarray(np.float64(1e-30), dt)
    idx = jnp.arange(n2)

    # decide the shift side with one arithmetic-midpoint evaluation
    mid = D + 0.5 * gap_hi

    def f_at(lam):  # lam: (n2,) candidate per root -> f values
        den = D[None, :] - lam[:, None]
        safe = jnp.where(den == 0, tiny, den)
        terms = jnp.where(nondefl[None, :], z2[None, :] / safe, 0.0)
        return 1.0 + rho * terms.sum(axis=1)

    has_upper = nxt_idx < n2
    f_mid = f_at(mid)
    right = has_upper & (f_mid < 0)  # root in the upper half
    kshift = jnp.where(right, jnp.minimum(nxt_idx, n2 - 1), idx)
    sgn = jnp.where(right, -1.0, 1.0).astype(dt)
    Ds = D[kshift]
    # span of the offset variable x: distance from the shift pole to the
    # midpoint (the root is on this side of the midpoint by choice);
    # the last root (no upper pole) keeps its full interval
    span = jnp.where(right, Ds - mid, jnp.where(has_upper, mid - D, gap_hi))
    span = jnp.maximum(span, tiny)

    # f evaluated ENTIRELY in shifted coordinates: den = (D_j - D_s) -
    # sgn*x.  Reconstructing lam = D_s + sgn*x first would round away
    # sub-ulp offsets and flip the own-pole sign (z^2/+0 = +inf).
    deltaS = D[None, :] - Ds[:, None]  # (i, j) -> D_j - D_shift_i

    def fx(x):  # offset -> f values (n2,)
        den = deltaS - (sgn * x)[:, None]
        safe = jnp.where(den == 0, tiny, den)
        terms = jnp.where(nondefl[None, :], z2[None, :] / safe, 0.0)
        return 1.0 + rho * terms.sum(axis=1)

    def fpx(x):  # |df/dx| = f'(lam) (positive), for Newton in x
        den = deltaS - (sgn * x)[:, None]
        safe = jnp.where(den == 0, tiny, den)
        terms = jnp.where(nondefl[None, :], z2[None, :] / (safe * safe), 0.0)
        return rho * terms.sum(axis=1)

    # x-space: f moves away from the pole singularity; at x -> 0+ the
    # own-pole term dominates: left shift -> -inf, right shift -> +inf.
    # "root is above x" <=> f(x) has the sign it takes near the pole.
    pole_sign = jnp.where(right, 1.0, -1.0).astype(dt)

    # absolute floor 1e-34: span*1e-25 can drop below the chip's ~1e-38
    # flush-to-zero line when the pole gap is itself tiny (deflation
    # guarantees nondeflated gaps > tol ~ 8 eps, so the floor is safe)
    lo = jnp.maximum(
        span * jnp.asarray(np.float64(1e-25), dt),
        jnp.asarray(np.float64(1e-34), dt),
    )
    hi = span

    def gbisect(_, carry):
        lo, hi = carry
        x = jnp.sqrt(lo) * jnp.sqrt(hi)
        fm = fx(x)
        toward = fm * pole_sign > 0  # still on the pole side of the root
        lo = jnp.where(toward, x, lo)
        hi = jnp.where(toward, hi, x)
        return lo, hi

    lo, hi = lax.fori_loop(0, _BISECT, gbisect, (lo, hi))
    x = jnp.sqrt(lo) * jnp.sqrt(hi)

    # bracket-maintained hybrid Newton with geometric fallback and
    # keep-best answer: the short geometric phase localizes to ~1e-4
    # relative, Newton squares that to eps in a few steps, and any
    # escape from the bracket falls back to the geometric midpoint.
    # (keep-best matters: once an iterate lands on the root, the
    # bracket pins it to an endpoint and the fallback jumps away.)
    def hybrid(_, carry):
        x, lo, hi, x_best, fbest = carry
        fm = fx(x)
        toward = fm * pole_sign > 0
        lo = jnp.where(toward, x, lo)
        hi = jnp.where(toward, hi, x)
        ab = jnp.abs(fm)
        better = ab < fbest
        x_best = jnp.where(better, x, x_best)
        fbest = jnp.where(better, ab, fbest)
        xn = x - sgn * fm / jnp.maximum(fpx(x), tiny)
        bad = ~jnp.isfinite(xn) | (xn <= lo) | (xn >= hi)
        xn = jnp.where(bad, jnp.sqrt(lo) * jnp.sqrt(hi), xn)
        return xn, lo, hi, x_best, fbest

    inf0 = jnp.full_like(x, jnp.asarray(np.float64(1e30), dt))
    x, lo, hi, x_best, fbest = lax.fori_loop(
        0, _NEWTON, hybrid, (x, lo, hi, x, inf0)
    )
    fm = jnp.abs(fx(x))
    x = jnp.where(fm < fbest, x, x_best)
    return kshift, sgn, x


def _merge_setup(w1, QT1, w2, QT2, e_r, eps):
    """Phase 0 of a Cuppen merge: build (D, z, QT) for the rank-one
    coupled problem and sort the poles ascending.  Eigenvector blocks
    are carried in TRANSPOSED form across the whole tree (row i of QT
    is the eigenvector belonging to w[i]): every permutation and Givens
    pass then gathers/updates ROWS — the TPU-friendly (sublane) axis —
    instead of lanes, which is what made the n=4096 top merges
    pathologically slow on-chip."""
    s = w1.shape[0]
    n2 = 2 * s
    dt = w1.dtype

    sigma = jnp.where(e_r < 0, -1.0, 1.0).astype(dt)
    rho = jnp.abs(e_r)

    D = jnp.concatenate([w1, w2])
    # z = (sigma * last row of Q1, first row of Q2) = (sigma * last
    # column of QT1, first column of QT2): static lane slices, cheap
    z = jnp.concatenate([sigma * QT1[:, -1], QT2[:, 0]])
    QT = jnp.zeros((n2, n2), dt)
    QT = QT.at[:s, :s].set(QT1).at[s:, s:].set(QT2)

    # sort poles ascending
    order = jnp.argsort(D)
    D = D[order]
    z = z[order]
    QT = QT[order, :]

    scale = jnp.maximum(jnp.abs(D).max(), rho * (z * z).sum())
    tol = 8.0 * eps * jnp.maximum(scale, jnp.asarray(np.float64(1e-30), dt))
    return D, z, QT, rho, tol


def _deflate(D, z, QT, rho, tol):
    """Deflation phases (a) + (b): drop negligible coupling weight and
    combine near-equal pole pairs by Givens passes (vectorized; rank
    pairing halves an equal-pole run per pass).  QT rows are the
    eigenvector columns."""
    n2 = D.shape[0]
    # --- deflation (a): negligible coupling weight --------------------
    nondefl = rho * jnp.abs(z) > tol
    # --- deflation (b): near-equal poles, Givens passes ---------------
    idx = jnp.arange(n2)

    def defl_pass(carry):
        p, D, z, QT, nondefl, _, prev = carry
        # pair nondeflated entries by their rank among the nondeflated
        # (even rank leads, its next nondeflated neighbour follows) —
        # index-adjacent pairing would stall on equal-pole runs once the
        # in-between entries deflate; rank pairing halves a run per
        # pass, so log2(n2) passes clear any cluster
        rank = jnp.cumsum(nondefl.astype(jnp.int32)) - 1
        posn = jnp.where(nondefl, idx, n2)
        suf = lax.cummin(posn[::-1])[::-1]
        nxt_nd = jnp.concatenate([suf[1:], jnp.full((1,), n2, jnp.int32)])
        posp = jnp.where(nondefl, idx, -1)
        prv_nd = jnp.concatenate(
            [jnp.full((1,), -1, jnp.int32), lax.cummax(posp)[:-1]]
        )
        # alternate pairing parity: a cluster starting at odd rank would
        # otherwise never align with the even-rank leads
        is_lead = nondefl & (rank % 2 == (p % 2)) & (nxt_nd < n2)
        nxt_c = jnp.clip(nxt_nd, 0, n2 - 1)
        act_lead = is_lead & (jnp.abs(D[nxt_c] - D) <= tol)
        is_fol = nondefl & (rank % 2 != (p % 2))
        prv_c = jnp.clip(prv_nd, 0, n2 - 1)
        lead = jnp.where(is_fol, prv_c, idx)
        act = jnp.where(is_fol, act_lead[prv_c] & (prv_nd >= 0), act_lead)
        act = act & (is_lead | is_fol)
        fol = jnp.clip(nxt_nd[lead], 0, n2 - 1)
        zl = z[lead]
        zf = z[fol]
        r = jnp.sqrt(zl * zl + zf * zf)
        rsafe = jnp.where(r == 0, 1.0, r)
        c = zl / rsafe
        sn = zf / rsafe
        # z: lead <- r, follower <- 0
        z = jnp.where(act, jnp.where(is_lead, r, 0.0), z)
        # diagonal mix (the dropped off-diagonal (D_l - D_f) c s <= tol)
        Dl = D[lead]
        Df = D[fol]
        D = jnp.where(
            act,
            jnp.where(
                is_lead, c * c * Dl + sn * sn * Df, sn * sn * Dl + c * c * Df
            ),
            D,
        )
        # rotate eigenvector pairs (rows of QT):
        #   lead <- c q_l + s q_f, fol <- -s q_l + c q_f
        ql = QT[lead, :]
        qf = QT[fol, :]
        Qrot = jnp.where(
            is_lead[:, None],
            c[:, None] * ql + sn[:, None] * qf,
            -sn[:, None] * ql + c[:, None] * qf,
        )
        QT = jnp.where(act[:, None], Qrot, QT)
        nondefl = nondefl & ~(act & is_fol)
        return p + 1, D, z, QT, nondefl, jnp.any(act), carry[5]

    # early-exit after TWO consecutive quiet passes (the parities
    # alternate, and one parity being quiet says nothing about the
    # other); most merges need 0-2 passes, only degenerate clusters use
    # the full 2*log2(n2) budget (each pass halves a run)
    npass = max(4, 2 * int(np.ceil(np.log2(n2))) + 2)
    _, D, z, QT, nondefl, _, _ = lax.while_loop(
        lambda c: (c[0] < npass) & (c[5] | c[6]),
        defl_pass,
        (jnp.int32(0), D, z, QT, nondefl, jnp.bool_(True), jnp.bool_(True)),
    )
    # re-apply deflation (a) after rotations moved the weight
    nondefl = nondefl & (rho * jnp.abs(z) > tol)
    z = jnp.where(nondefl, z, 0.0)
    return D, z, QT, nondefl


def _solve_secular(D, z, rho, nondefl, tol):
    """Secular-equation phase: bracket construction + vectorized laed4
    roots.  Returns (kshift, sgn, x, lam)."""
    n2 = D.shape[0]
    dt = D.dtype
    idx = jnp.arange(n2)
    z2 = z * z
    # --- secular solve ------------------------------------------------
    # index of the next nondeflated pole above i (n2 if none)
    posn2 = jnp.where(nondefl, idx, n2).astype(jnp.int32)
    suf2 = lax.cummin(posn2[::-1])[::-1]
    nxt_idx = jnp.concatenate([suf2[1:], jnp.full((1,), n2, jnp.int32)])
    nxt_c = jnp.clip(nxt_idx, 0, n2 - 1)
    top_gap = rho * z2.sum() + tol
    gap_hi = jnp.where(nxt_idx < n2, D[nxt_c] - D, top_gap)
    gap_hi = jnp.maximum(gap_hi, jnp.asarray(np.float64(1e-30), dt))
    kshift, sgn, x = _secular_roots(D, z2, rho, nondefl, nxt_idx, gap_hi)
    kshift = jnp.where(nondefl, kshift, idx)
    sgn = jnp.where(nondefl, sgn, 1.0)
    x = jnp.where(nondefl, x, 0.0)
    lam = jnp.where(nondefl, D[kshift] + sgn * x, D)
    return kshift, sgn, x, lam


def _assemble_u(D, z, nondefl, kshift, sgn, x):
    """Lowner z-hat recomputation + eigenvector assembly.  Returns Ur
    with ROWS indexed by root i (Ur = U^T of the classical U), ready
    for the transposed back-rotation QT_out = Ur @ QT."""
    n2 = D.shape[0]
    dt = D.dtype
    # --- Lowner z-hat (Gu-Eisenstat) ----------------------------------
    # zhat_j^2 = prod_i (lam_i - D_j) / prod_{i != j} (D_i - D_j), over
    # nondeflated i, j.  lam_i - D_j = (D[kshift_i] - D_j) + sgn_i x_i
    # — the nearest-pole representation keeps this difference accurate
    # even when lam_i hugs a pole.
    delta = D[:, None] - D[None, :]  # (i, j) -> D_i - D_j
    lam_minus_d = (D[kshift][:, None] - D[None, :]) + (sgn * x)[:, None]
    both = nondefl[:, None] & nondefl[None, :]
    num = jnp.where(both, lam_minus_d, 1.0)
    offdiag = both & (jnp.arange(n2)[:, None] != jnp.arange(n2)[None, :])
    den = jnp.where(offdiag, delta, 1.0)
    logmag = jnp.where(both, jnp.log(jnp.abs(jnp.where(num == 0, 1.0, num))), 0.0)
    logden = jnp.where(offdiag, jnp.log(jnp.abs(jnp.where(den == 0, 1.0, den))), 0.0)
    # optimization_barrier: when the log producers FUSE into the column
    # sums below, the chip's f64-emulated reduction accumulates at f32
    # grade and zhat loses ~7 digits — this single fusion was the whole
    # stedc orthogonality budget at n=4096 (97 n eps jitted vs 36 with
    # the logs materialized first; round-5 bisection, the per-phase and
    # norm-sum barriers moved nothing).  Forcing materialization keeps
    # the jitted tree at eager-grade accuracy for ~16 MB of extra HBM
    # traffic per merge.
    logmag, logden = _opt_barrier((logmag, logden))
    logzhat = 0.5 * (logmag.sum(axis=0) - logden.sum(axis=0))
    zsign = jnp.where(z < 0, -1.0, 1.0).astype(dt)

    # --- eigenvector assembly (log-space, underflow-proof) ------------
    # column i (nondeflated): u_j = zhat_j / (lam_i - D_j), normalized.
    # Assembled as exp(log|zhat_j| - log|lam_i - D_j| - max_col) so that
    # tiny zhat magnitudes (exp of a large negative sum) cannot flush
    # to zero inside the chip's f32-grade f64 exponent range — a direct
    # exp(logzhat) underflow zeroes whole columns there.
    absd = jnp.abs(lam_minus_d)
    logd = jnp.log(jnp.where(absd == 0, 1.0, absd))
    logU = jnp.where(both, logzhat[None, :] - logd, -jnp.inf)  # (i, j)
    sgn_u = zsign[None, :] * jnp.where(lam_minus_d < 0, -1.0, 1.0)
    M = jnp.max(logU, axis=1, keepdims=True)
    Msafe = jnp.where(jnp.isfinite(M), M, 0.0)
    Ur = jnp.where(both, sgn_u * jnp.exp(logU - Msafe), 0.0)  # (root i, j)
    norms = jnp.sqrt((Ur * Ur).sum(axis=1))
    Ur = Ur / jnp.where(norms == 0, 1.0, norms)[:, None]
    # deflated roots: unit vectors
    eye = jnp.eye(n2, dtype=dt)
    Ur = jnp.where(nondefl[:, None], Ur, eye)
    return Ur


def _merge(w1, QT1, w2, QT2, e_r, eps):
    """One Cuppen merge: children (w1, QT1), (w2, QT2) of size s each
    (QT in row-eigenvector form), coupled by off-diagonal e_r.  Returns
    (w, QT) of size 2s, ascending.

    Composed of the phase functions above (setup/sort -> deflate ->
    secular -> assemble -> back-rotate); tools/profile_stedc.py times
    each phase separately on-chip."""
    D, z, QT, rho, tol = _merge_setup(w1, QT1, w2, QT2, e_r, eps)
    D, z, QT, nondefl = _deflate(D, z, QT, rho, tol)
    kshift, sgn, x, lam = _solve_secular(D, z, rho, nondefl, tol)
    Ur = _assemble_u(D, z, nondefl, kshift, sgn, x)

    # --- back-rotation + final sort (all in transposed form): the
    # classical Q @ U becomes QT_out = U^T @ QT, still one MXU gemm ----
    QT = _dot(Ur, QT)
    order2 = jnp.argsort(lam)
    return lam[order2], QT[order2, :]


def stedc(d: jnp.ndarray, e: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Eigendecomposition of the symmetric tridiagonal (d, e):
    returns (w ascending, Q) with T = Q diag(w) Q^T.

    Bottom-up Cuppen tree over a power-of-two padding; every level's
    merges run as one vmapped batch (reference: src/stedc.cc's recursive
    driver + stedc_merge/stedc_secular; see module docstring)."""
    n = d.shape[0]
    dt = d.dtype
    eps = float(jnp.finfo(dt).eps)
    if jax.default_backend() != "cpu":
        # the TPU f64 emulation's effective unit roundoff is ~10x the
        # IEEE one (measured ~2.5e-15 on gemm); deflation calibrated to
        # IEEE eps leaves degenerate clusters undeflated with pole
        # differences that are pure emulation noise, which destroys
        # eigenvector orthogonality.  The n-growth keeps large merges'
        # root interlacing robust.  (The ~100 n eps orthogonality this
        # calibration used to be blamed for was actually the
        # _assemble_u log->sum fusion defect, fixed round 5 by the
        # optimization_barrier there: with it, orthogonality is ~3
        # n eps at n=4096 under this same 32x sqrt(n) factor.)
        eps *= 32.0 * max(1.0, float(np.sqrt(n / 2048.0)))
    if n == 1:
        return d, jnp.ones((1, 1), dt)

    # normalize to O(1) scale (LAPACK dlaed0 does the same): keeps every
    # internal quantity inside the TPU f64 emulation's f32-grade
    # exponent range (values under ~1e-38 flush to zero on this chip)
    scale0 = jnp.maximum(
        jnp.abs(d).max(), jnp.abs(e).max() if e.shape[0] else jnp.zeros((), dt)
    )
    scale = jnp.where(scale0 > 0, scale0, 1.0)
    d = d / scale
    e = e / scale

    N = 1 << int(np.ceil(np.log2(n)))
    # pad with decoupled, well-separated poles above the spectrum
    bound = jnp.abs(d).max() + 2 * (jnp.abs(e).max() if e.shape[0] else 0.0) + 1.0
    dpad = jnp.concatenate([d, bound * (2.0 + jnp.arange(N - n, dtype=dt))])
    epad = jnp.concatenate([e, jnp.zeros((N - 1 - e.shape[0],), dt)])

    # leaf adjustment: every interior edge is cut once in the full tree
    eabs = jnp.abs(epad)
    left = jnp.concatenate([jnp.zeros((1,), dt), eabs])
    right = jnp.concatenate([eabs, jnp.zeros((1,), dt)])
    w = (dpad - left - right)[:, None]  # (N, 1) block eigenvalues
    QT = jnp.ones((N, 1, 1), dt)  # row-eigenvector (transposed) form
    w = w.reshape(N, 1)

    merge_b = jax.vmap(_merge, in_axes=(0, 0, 0, 0, 0, None))

    s = 1
    while s < N:
        nm = N // (2 * s)
        w_pairs = w.reshape(nm, 2, s)
        Q_pairs = QT.reshape(nm, 2, s, s)
        e_r = epad[s - 1 :: 2 * s][:nm]
        w, QT = merge_b(
            w_pairs[:, 0], Q_pairs[:, 0], w_pairs[:, 1], Q_pairs[:, 1],
            e_r, eps,
        )
        s *= 2
        w = w.reshape(nm, s)
        QT = QT.reshape(nm, s, s)

    w = w.reshape(N)
    QT = QT.reshape(N, N)
    QT = QT[:n, :n]
    # Orthogonality on-chip: ~3 n eps at n=4096 since the
    # optimization_barrier in _assemble_u (the log->sum fusion was the
    # whole ~100 n eps budget; round-5 bisection).  The previously
    # attempted Newton-Schulz/CholQR output polish was a symptom-level
    # workaround for that same fused-reduction defect and stays absent.
    # single transpose back to column-eigenvector convention
    return w[:n] * scale, QT.T
