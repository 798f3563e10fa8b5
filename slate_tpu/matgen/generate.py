"""Deterministic test-matrix generator (reference: matgen/ — ~30 named
kinds with condition-controlled spectra; kind grammar parsed in
generate_matrix_utils.cc:211-360; special-matrix formulas
generate_matrix_ge.cc:80-465; sigma distributions generate_sigma.hh:39-130;
svd/heev constructions generate_type_svd.hh / generate_type_heev.hh).

Kind grammar (identical to the reference):

    base[_dist][_scale][_modifier...]   tokens split on '_' or '-'

      base:     zeros ones identity ij jordan jordanT chebspec circul
                fiedler gfpp kms orthog riemann ris zielkeNS diag svd poev
                heev geev geevx minij hilb frank lehmer lotkin redheff triw
                tridiag toeppen pei parter moler cauchy chow clement gcdmat
                rand rands randn randb randr
      dist:     rand rands randn logrand arith geo cluster0 cluster1
                rarith rgeo rcluster0 rcluster1 specified
                (only for diag/svd/poev/heev/geev/geevx; default logrand)
      scale:    small large ufl ofl
      modifier: dominant, zerocol<N|fraction>

All element values come from the Philox (i, j)-keyed RNG, so any kind is
bit-reproducible for a given seed regardless of tiling or process count.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..exceptions import SlateError
from ..matrix.base import BaseMatrix, is_distributed
from ..matrix.matrix import Matrix
from ..parallel.layout import tiles_from_global
from . import philox

_RAND_KINDS = {
    "rand": "uniform",
    "rands": "uniform_signed",
    "randn": "normal",
    "randb": "binary",
    "randr": "binary_signed",
}

_DISTS = (
    "rand", "rands", "randn", "logrand", "arith", "geo", "cluster0",
    "cluster1", "rarith", "rgeo", "rcluster0", "rcluster1", "specified",
)

_SPECTRUM_KINDS = ("diag", "svd", "poev", "heev", "geev", "geevx")


def _ij_grids(m, n):
    i = jnp.arange(m, dtype=jnp.float64)[:, None]
    j = jnp.arange(n, dtype=jnp.float64)[None, :]
    return jnp.broadcast_arrays(i + 0 * j, 0 * i + j)


def _special_entry(base: str, m: int, n: int, dtype) -> jnp.ndarray:
    """Elementwise special matrices (generate_matrix_ge.cc:80-465)."""
    i, j = _ij_grids(m, n)
    mx = max(m, n)
    pi = np.pi
    one = 1.0
    if base == "zeros":
        G = jnp.zeros((m, n))
    elif base == "ones":
        G = jnp.ones((m, n))
    elif base == "identity":
        G = jnp.where(i == j, 1.0, 0.0)
    elif base == "ij":
        s = 1.0 / 10 ** math.ceil(math.log10(max(n, 2)))
        G = i + j * s
    elif base == "jordan":
        G = jnp.where((i == j) | (i + 1 == j), 1.0, 0.0)
    elif base == "jordanT":
        G = jnp.where((i == j) | (i == j + 1), 1.0, 0.0)
    elif base == "chebspec":
        x_i = jnp.cos(pi * (i + 1) / mx)
        x_j = jnp.cos(pi * (j + 1) / mx)
        c_i = jnp.where(i == mx - 1, 2.0, 1.0)
        c_j = jnp.where(j == mx - 1, 2.0, 1.0)
        sgn = jnp.where((i + j) % 2 == 0, 1.0, -1.0)
        off = sgn * c_i / (c_j * (x_j - x_i + jnp.where(i == j, 1.0, 0.0)))
        last = (2.0 * mx * mx + 1) / -6.0
        diag = jnp.where(j + 1 == mx, last, -0.5 * x_i / (one - x_i * x_i))
        G = jnp.where(i == j, diag, off)
    elif base == "circul":
        diff = j - i
        G = diff + jnp.where(diff < 0, float(mx), 0.0) + 1
    elif base == "fiedler":
        G = jnp.abs(j - i)
    elif base == "gfpp":
        G = jnp.where(
            j == n - 1, 1.0, jnp.where(i > j, -1.0, jnp.where(i == j, 0.5, 0.0))
        )
    elif base == "kms":
        G = 0.5 ** jnp.abs(j - i)
    elif base == "orthog":
        G = jnp.sqrt(2.0 / (mx + 1)) * jnp.sin((i + 1) * (j + 1) * pi / (mx + 1))
    elif base == "riemann":
        bi, bj = i + 2, j + 2
        G = jnp.where(bj % bi == 0, bj - 1.0, -1.0)
    elif base == "ris":
        G = 0.5 / (mx - j - i - 0.5)
    elif base == "zielkeNS":
        G = jnp.where(
            j < i, 1.0, jnp.where((j + 1 == mx) & (i == 0), -1.0, 0.0)
        )
    elif base == "minij":
        G = jnp.minimum(i, j) + 1
    elif base == "hilb":
        G = 1.0 / (i + j + 1)
    elif base == "frank":
        G = jnp.where(
            i - j > 1, 0.0, jnp.where(i - j == 1, mx - j - 1.0, mx - j + 0.0)
        )
    elif base == "lehmer":
        G = (jnp.minimum(i, j) + 1) / (jnp.maximum(i, j) + 1)
    elif base == "lotkin":
        G = jnp.where(i == 0, 1.0, 1.0 / (i + j + 1))
    elif base == "redheff":
        G = jnp.where(((j + 1) % (i + 1) == 0) | (j == 0), 1.0, 0.0)
    elif base == "triw":
        G = jnp.where(i == j, 1.0, jnp.where(i > j, 0.0, -1.0))
    elif base == "tridiag":
        G = jnp.where(i == j, 2.0, jnp.where(jnp.abs(i - j) == 1, -1.0, 0.0))
    elif base == "toeppen":
        G = jnp.where(
            jnp.abs(j - i) == 1,
            (j - i) * 10.0,
            jnp.where(jnp.abs(i - j) == 2, 1.0, 0.0),
        )
    elif base == "pei":
        G = jnp.where(i == j, 2.0, 1.0)
    elif base == "parter":
        G = 1.0 / (i - j + 0.5)
    elif base == "moler":
        G = jnp.where(i == j, i + 1.0, jnp.minimum(i, j) - 1.0)
    elif base == "cauchy":
        G = 1.0 / (i + j + 2)
    elif base == "chow":
        G = jnp.where(i - j < -1, 0.0, 1.0)
    elif base == "clement":
        G = jnp.where(
            i - j == 1, mx - j - 1.0, jnp.where(i - j == -1, j + 0.0, 0.0)
        )
    elif base == "gcdmat":
        ii = np.arange(1, m + 1)[:, None]
        jj = np.arange(1, n + 1)[None, :]
        G = jnp.asarray(np.gcd(ii, jj).astype(np.float64))
    else:
        raise SlateError(f"unknown matrix kind base: {base!r}")
    return G.astype(dtype)


def _sigma(dist: str, min_mn: int, cond: float, sigma_max: float, seed: int,
           real_t, specified=None) -> jnp.ndarray:
    """Singular/eigen value distribution (generate_sigma.hh:39-130)."""
    idx = jnp.arange(min_mn, dtype=jnp.float64)
    denom = max(min_mn - 1, 1)
    if dist == "arith":
        s = 1 - idx / denom * (1 - 1 / cond)
    elif dist == "rarith":
        s = 1 - (min_mn - 1 - idx) / denom * (1 - 1 / cond)
    elif dist == "geo":
        s = cond ** (-idx / denom)
    elif dist == "rgeo":
        s = cond ** (-(min_mn - 1 - idx) / denom)
    elif dist == "cluster0":
        s = jnp.where(idx == 0, 1.0, 1 / cond)
    elif dist == "rcluster0":
        s = jnp.where(idx == min_mn - 1, 1.0, 1 / cond)
    elif dist == "cluster1":
        s = jnp.where(idx == min_mn - 1, 1 / cond, 1.0)
    elif dist == "rcluster1":
        s = jnp.where(idx == 0, 1 / cond, 1.0)
    elif dist == "logrand":
        u = philox.random_jnp(
            "uniform", seed, jnp.arange(min_mn, dtype=jnp.int64), jnp.zeros(min_mn, jnp.int64),
            jnp.float64,
        )
        rng_span = math.log(1 / cond)
        s = jnp.exp(u * rng_span)
    elif dist in ("rand", "rands", "randn"):
        s = philox.random_jnp(
            {"rand": "uniform", "rands": "uniform_signed", "randn": "normal"}[dist],
            seed,
            jnp.arange(min_mn, dtype=jnp.int64),
            jnp.zeros(min_mn, jnp.int64),
            jnp.float64,
        )
    elif dist == "specified":
        if specified is None:
            raise SlateError("dist 'specified' requires sigma values")
        s = jnp.asarray(specified, jnp.float64)
    else:
        raise SlateError(f"unknown sigma distribution {dist!r}")
    return (s * sigma_max).astype(real_t)


def _random_orthogonal(m: int, k: int, seed: int, dtype) -> jnp.ndarray:
    """Random Householder-based orthogonal factor (generate_type_svd.hh:
    90-123: randn matrix -> geqrf -> Q)."""
    from ..ops.householder import geqrf as _geqrf, larft, materialize_v

    i, j = np.arange(m)[:, None], np.arange(k)[None, :]
    X = philox.random_np("normal", seed, i + 0 * j, j + 0 * i,
                         np.complex128 if jnp.dtype(dtype).kind == "c" else np.float64)
    vr, taus = _geqrf(jnp.asarray(X))
    Q = jnp.eye(m, k, dtype=vr.dtype)
    # Q = H_0 ... H_{k-1} I  via blocked application
    nb = min(32, k)
    for k0 in range(((k + nb - 1) // nb) - 1, -1, -1):
        w = min(nb, k - k0 * nb)
        Vk = materialize_v(vr[:, k0 * nb : k0 * nb + w], offset=k0 * nb)
        Tk = larft(Vk, taus[k0 * nb : k0 * nb + w])
        W = jnp.conj(Vk).T @ Q
        Q = Q - Vk @ (Tk @ W)
    return Q.astype(dtype)


def parse_kind(kind: str):
    """Kind-string parsing (generate_matrix_utils.cc:211-360)."""
    tokens = [t for t in kind.replace("-", "_").split("_")]
    if not tokens or not tokens[0]:
        raise SlateError("empty matrix kind")
    base, *mods = tokens
    dist = None
    sigma_max = 1.0
    dominant = False
    zero_col = None
    eps = np.finfo(np.float64).eps
    ufl = np.finfo(np.float64).tiny
    ofl = 1 / ufl
    for tok in mods:
        if tok in _DISTS:
            dist = tok
        elif tok == "small":
            sigma_max = math.sqrt(ufl)
        elif tok == "large":
            sigma_max = math.sqrt(ofl)
        elif tok == "ufl":
            sigma_max = ufl
        elif tok == "ofl":
            sigma_max = ofl
        elif tok == "dominant":
            dominant = True
        elif tok.startswith("zerocol"):
            v = tok[7:]
            zero_col = float(v) if "." in v else int(v)
        else:
            raise SlateError(f"in {kind!r}: unknown suffix {tok!r}")
    if dist is not None and base not in _SPECTRUM_KINDS:
        raise SlateError(f"in {kind!r}: base {base!r} doesn't support distribution")
    if dist is None:
        dist = "logrand"
    return base, dist, sigma_max, dominant, zero_col


def generate_2d(
    kind: str,
    m: int,
    n: int,
    dtype=np.float64,
    seed: int = 42,
    cond: Optional[float] = None,
    sigma_specified=None,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Generate the (m, n) global array for `kind`; returns (A, Sigma)."""
    base, dist, sigma_max, dominant, zero_col = parse_kind(kind)
    dtype = jnp.dtype(dtype)
    real_t = (
        np.float32
        if dtype in (jnp.dtype("float32"), jnp.dtype("complex64"))
        else np.float64
    )
    if cond is None:
        cond = float(1.0 / math.sqrt(np.finfo(real_t).eps))
    min_mn = min(m, n)
    Sigma = None

    if base in _RAND_KINDS:
        i, j = np.arange(m)[:, None], np.arange(n)[None, :]
        G = jnp.asarray(
            philox.random_np(
                _RAND_KINDS[base], seed, i + 0 * j, j + 0 * i, np.dtype(dtype.name)
            )
        )
        if sigma_max != 1.0:
            G = G * sigma_max
        if dominant:
            # generate_rand: diag += row-sum bound (max_mn) to dominate
            rowsum = jnp.sum(jnp.abs(G), axis=1)
            idx = jnp.arange(min_mn)
            G = G.at[idx, idx].set(rowsum[:min_mn].astype(G.dtype))
            dominant = False
    elif base == "diag":
        Sigma = _sigma(dist, min_mn, cond, sigma_max, seed, real_t, sigma_specified)
        G = jnp.zeros((m, n), dtype).at[
            jnp.arange(min_mn), jnp.arange(min_mn)
        ].set(Sigma.astype(dtype))
    elif base in ("svd", "poev", "heev", "geev", "geevx"):
        Sigma = _sigma(dist, min_mn, cond, sigma_max, seed, real_t, sigma_specified)
        if base == "heev":
            # signed spectrum (generate_heev rand_sign)
            signs = philox.random_np(
                "binary_signed", seed + 3, np.arange(min_mn), np.zeros(min_mn)
            )
            Sigma = (Sigma * jnp.asarray(signs)).astype(real_t)
        U = _random_orthogonal(m, min_mn, seed + 1, dtype)
        if base == "svd":
            V = _random_orthogonal(n, min_mn, seed + 2, dtype)
            G = (U * Sigma.astype(dtype)[None, :]) @ jnp.conj(V).T
        elif base in ("poev", "heev"):
            G = (U * Sigma.astype(dtype)[None, :]) @ jnp.conj(U).T
        else:  # geev/geevx: known spectrum, non-normal: A = U T U^H,
            # T upper triangular with Sigma diagonal (Schur-form based,
            # generate_type_geev.hh)
            i, j = np.arange(min_mn)[:, None], np.arange(min_mn)[None, :]
            N = philox.random_np(
                "normal", seed + 4, i + 0 * j, j + 0 * i, np.dtype(dtype.name)
            )
            # mild non-normality: keep the eigenproblem well-conditioned so
            # the spectrum is numerically recoverable
            noise = float(jnp.abs(Sigma).max()) / (4.0 * math.sqrt(min_mn))
            T = noise * jnp.triu(jnp.asarray(N), 1) + jnp.diag(Sigma.astype(dtype))
            G = U @ T @ jnp.conj(U).T
        G = G.astype(dtype)
    else:
        G = _special_entry(base, m, n, dtype)

    if dominant:
        rowsum = jnp.sum(jnp.abs(G), axis=1)
        idx = jnp.arange(min_mn)
        G = G.at[idx, idx].set(rowsum[:min_mn].astype(G.dtype))
    if zero_col is not None:
        col = int(zero_col * (n - 1)) if isinstance(zero_col, float) else zero_col
        if not (0 <= col < n):
            raise SlateError(f"zerocol {col} outside [0, {n})")
        G = G.at[:, col].set(0)
    return G, Sigma


def generate_tiles(
    kind: str, layout, dtype, seed: int = 42, sharding=None
) -> Optional[jnp.ndarray]:
    """Device-side generation of the (P, Q, mb, nb) storage-order tile
    array for the plain rand kinds: every element draws from the Philox
    counter RNG keyed by its *global* (i, j), so the result is invariant
    to tiling and process count (reference: matgen/random.cc:43-100) —
    and with the grid's tile ``sharding`` each device generates only its
    local tiles, with no host round-trip.  Returns None for kinds that need global
    structure (spectra, special matrices, dominant/zerocol suffixes);
    callers fall back to the host path."""

    base, dist, sigma_max, dominant, zero_col = parse_kind(kind)
    if base not in _RAND_KINDS or dominant or zero_col is not None:
        return None
    return _rand_tiles(
        jnp.asarray(layout.global_rows_np.astype(np.int64)),  # (P, mb)
        jnp.asarray(layout.global_cols_np.astype(np.int64)),  # (Q, nb)
        jnp.asarray(layout.row_mask_np),
        jnp.asarray(layout.col_mask_np),
        dist=_RAND_KINDS[base], seed=int(seed), dtype=jnp.dtype(dtype),
        scale=float(sigma_max), sharding=sharding,
    )


@functools.partial(
    jax.jit, static_argnames=("dist", "seed", "dtype", "scale", "sharding")
)
def _rand_tiles(gr, gc, row_mask, col_mask, dist, seed, dtype, scale,
                sharding):
    """One compiled program per tile shape: the Philox chain fuses into
    one elementwise pass (eagerly it is ~30 ops, each compiled per new
    shape and each materializing a full-size intermediate), partitioned
    by ``sharding`` when given (a whole n=16384 f64 chain does not fit
    one v5e)."""
    shape = (gr.shape[0], gc.shape[0], gr.shape[1], gc.shape[1])

    def place(x):
        if sharding is None:
            return x
        return jax.lax.with_sharding_constraint(x, sharding)

    i = place(jnp.broadcast_to(gr[:, None, :, None], shape))
    j = place(jnp.broadcast_to(gc[None, :, None, :], shape))
    T = philox.random_jnp(dist, seed, i, j, dtype)
    if scale != 1.0:
        T = T * scale
    mask = row_mask[:, None, :, None] & col_mask[None, :, None, :]
    return place(jnp.where(mask, T, 0))


def generate_matrix(
    kind: str,
    A: BaseMatrix,
    seed: int = 42,
    cond: Optional[float] = None,
    sigma_specified=None,
) -> Tuple[BaseMatrix, Optional[jnp.ndarray]]:
    """Fill an existing matrix's shape/layout with `kind` (reference:
    slate::generate_matrix, include/slate/generate_matrix.hh:29-60).

    Plain rand kinds generate directly on-device per tile
    (generate_tiles); structured kinds assemble on the host."""
    Ar = A.resolved()
    sharding = Ar.grid.tile_sharding() if is_distributed(Ar) else None
    T = generate_tiles(kind, Ar.layout, A.dtype, seed, sharding)
    if T is not None:
        return A._with(data=T).shard(), None
    G, Sigma = generate_2d(
        kind, A.m, A.n, A.dtype, seed=seed, cond=cond,
        sigma_specified=sigma_specified,
    )
    out = A._with(data=tiles_from_global(G, lay))
    return out.shard(), Sigma


def generate(
    kind: str,
    m: int,
    n: int,
    mb: int,
    nb: Optional[int] = None,
    dtype=np.float64,
    grid=None,
    seed: int = 42,
    cond: Optional[float] = None,
) -> Matrix:
    """Convenience constructor: generate a fresh distributed Matrix
    (plain rand kinds on the device, tile by tile; see
    ``generate_matrix``)."""
    A = Matrix.zeros(m, n, mb, nb, dtype=dtype, grid=grid)
    return generate_matrix(kind, A, seed=seed, cond=cond)[0]


def cond_matrix(
    n: int,
    cond: float,
    dtype=np.float64,
    seed: int = 42,
    spd: bool = False,
) -> np.ndarray:
    """Deterministic n x n matrix with **specified 2-norm condition
    number** via scaled-singular-value construction: A = U diag(s) V^H
    with s geometrically spaced from 1 down to 1/cond (``geo``
    distribution, generate_sigma.hh:39-130) and Philox-seeded random
    orthogonal factors — so sigma_max = 1, sigma_min = 1/cond and
    cond_2(A) = cond *exactly by construction*, bit-reproducible for a
    given seed.

    ``spd=True`` uses one orthogonal factor (A = U diag(s) U^H, the
    ``poev`` construction): symmetric/Hermitian positive definite with
    the same 2-norm condition number.

    The knob the refine/ tests are built on: iterative-refinement
    convergence (cond such that cond * eps_factor << 1), stall
    (~1/eps_factor — where GMRES-IR still converges), and divergence +
    fallback (>> 1/eps_factor) become deterministic properties of the
    requested cond instead of luck-of-the-draw spectra."""
    if cond < 1:
        raise SlateError(f"cond must be >= 1, got {cond}")
    kind = "poev_geo" if spd else "svd_geo"
    G, _ = generate_2d(kind, n, n, dtype, seed=seed, cond=float(cond))
    return np.asarray(G)
