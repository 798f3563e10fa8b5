"""Native blocked LU kernels (ops/lu_kernels.py) — the f64-on-TPU path
(reference: src/getrf.cc:85-214 blocked right-looking factorization).

On CPU the vendor path is taken by default, so these tests call the
native kernels directly to validate them against numpy on every platform.
"""

import numpy as np
import pytest

from slate_tpu.ops import lu_kernels


@pytest.mark.parametrize("M,nb", [(64, 16), (64, 8), (48, 16), (16, 16)])
def test_panel_lu(rng, M, nb):
    panel = rng.standard_normal((M, nb))
    lu, perm = lu_kernels.panel_lu(np.asarray(panel))
    lu = np.asarray(lu)
    perm = np.asarray(perm)
    L = np.tril(lu, -1)[:, :nb] + np.eye(M, nb)
    U = np.triu(lu[:nb])
    np.testing.assert_allclose(panel[perm], L @ U, atol=1e-12)
    # partial pivoting: multipliers bounded by 1
    assert np.abs(np.tril(lu, -1)).max() <= 1.0 + 1e-12


def test_panel_lu_complex(rng):
    M, nb = 40, 8
    panel = rng.standard_normal((M, nb)) + 1j * rng.standard_normal((M, nb))
    lu, perm = lu_kernels.panel_lu(panel.astype(np.complex128))
    lu = np.asarray(lu)
    L = np.tril(lu, -1)[:, :nb] + np.eye(M, nb)
    U = np.triu(lu[:nb])
    np.testing.assert_allclose(panel[np.asarray(perm)], L @ U, atol=1e-12)


@pytest.mark.parametrize("n,nb", [(64, 16), (96, 32), (32, 32)])
def test_blocked_getrf(rng, n, nb):
    A = rng.standard_normal((n, n))
    LU, perm = lu_kernels.blocked_getrf(np.asarray(A), nb)
    LU = np.asarray(LU)
    perm = np.asarray(perm)
    L = np.tril(LU, -1) + np.eye(n)
    U = np.triu(LU)
    err = np.abs(A[perm] - L @ U).max() / np.abs(A).max()
    assert err < 1e-13, err
    assert np.abs(np.tril(LU, -1)).max() <= 1.0 + 1e-12


def test_blocked_getrf_matches_vendor(rng):
    """Same pivot choices as LAPACK on a generic matrix."""
    from jax import lax

    n, nb = 64, 16
    A = rng.standard_normal((n, n))
    LU, perm = lu_kernels.blocked_getrf(np.asarray(A), nb)
    lu_ref, _, perm_ref = lax.linalg.lu(np.asarray(A))
    np.testing.assert_allclose(np.asarray(LU), np.asarray(lu_ref), atol=1e-10)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm_ref))


def test_blocked_getrf_singular(rng):
    """Zero column: no NaNs, zero U diagonal for the info check."""
    n, nb = 32, 16
    A = rng.standard_normal((n, n))
    A[:, 5] = 0.0
    LU, perm = lu_kernels.blocked_getrf(np.asarray(A), nb)
    LU = np.asarray(LU)
    assert np.isfinite(LU).all()


def test_getrf_forced_native(rng, monkeypatch):
    """Drive the full getrf driver through the native path."""
    from slate_tpu.drivers import lu as lu_driver
    from slate_tpu.matrix.matrix import Matrix
    from slate_tpu.testing import checks

    monkeypatch.setattr(lu_kernels, "lu_supported", lambda dt: False)
    n, nb = 50, 16
    A0 = rng.standard_normal((n, n))
    B0 = rng.standard_normal((n, 4))
    X, LU, piv, info = lu_driver.gesv(
        Matrix.from_global(A0, nb), Matrix.from_global(B0, nb)
    )
    assert int(info) == 0
    err = checks.solve_residual(A0, np.asarray(X.to_global()), B0)
    assert checks.passed(err, np.float64, factor=30), err


# Above RECURSIVE_MIN_N blocked_getrf runs its steps in at most four loops
# at exact trailing shapes; each must reproduce the single full-shape loop.
@pytest.mark.parametrize(
    "m,n,nb",
    [
        (2560, 2560, 256),  # kt=10: segments of 3, 3, 3, 1 steps
        (2560, 2048, 256),  # tall
        (2048, 2560, 256),  # wide
    ],
)
def test_segmented_getrf_matches_single_loop(rng, m, n, nb):
    A = rng.standard_normal((m, n))
    kt = min(m, n) // nb
    assert len(lu_kernels._flat_segments(m, n, nb)) == 4
    LU, perm = lu_kernels.blocked_getrf(np.asarray(A), nb)
    LU0, perm0 = lu_kernels._getrf_steps(np.asarray(A), nb, kt)
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm0))
    np.testing.assert_allclose(np.asarray(LU), np.asarray(LU0), atol=1e-12)


def test_segmented_getrf_singular(rng):
    """Zero column inside a later segment: finite output, zero U
    diagonal there, as the single loop gives."""
    n, nb = 2048, 256
    A = rng.standard_normal((n, n))
    A[:, 1100] = 0.0
    LU, perm = lu_kernels.blocked_getrf(np.asarray(A), nb)
    LU0, perm0 = lu_kernels._getrf_steps(np.asarray(A), nb, n // nb)
    LU = np.asarray(LU)
    assert np.isfinite(LU).all()
    assert LU[1100, 1100] == 0.0
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(perm0))
    np.testing.assert_allclose(LU, np.asarray(LU0), atol=1e-12)


def test_blocked_getrf_small_is_single_loop():
    """Below RECURSIVE_MIN_N blocked_getrf lowers to exactly the single
    full-shape loop."""
    import jax
    import jax.numpy as jnp

    n, nb = 1024, 256
    G = jax.ShapeDtypeStruct((n, n), jnp.float64)
    seg = jax.jit(lambda G: lu_kernels.blocked_getrf(G, nb)).lower(G)
    one = jax.jit(lambda G: lu_kernels._getrf_steps(G, nb, n // nb)).lower(G)
    assert seg.as_text() == one.as_text()


# The strip panel (``_lu_panel_strips``) factors blocked_getrf's panels
# from nb = 2 * PANEL_IB up; it must reproduce panel_lu's pivots and
# factor on tie-free panels, in the rolled zero-padded form too.
@pytest.mark.parametrize(
    "M,w,act,dtype",
    [
        (256, 64, 256, np.float64),
        (256, 64, 150, np.float64),
        (384, 128, 384, np.float64),
        (384, 128, 200, np.float64),
        (512, 256, 512, np.float64),
        (512, 256, 300, np.float64),
        (256, 128, 256, np.complex128),
        (256, 128, 180, np.complex128),
        # lu_fast's f32/c64 route shares the panel (rank-32 update by hdot)
        (256, 128, 256, np.float32),
        (256, 128, 180, np.complex64),
    ],
)
def test_strip_panel_matches_panel_lu(rng, M, w, act, dtype):
    import jax
    import jax.numpy as jnp

    P = rng.standard_normal((M, w))
    if np.issubdtype(dtype, np.complexfloating):
        P = P + 1j * rng.standard_normal((M, w))
    P = P.astype(dtype)
    P[act:] = 0
    lu0, p0 = lu_kernels.panel_lu(P, act=None if act == M else act)
    lu1, p1 = jax.jit(lu_kernels._lu_panel_strips)(P, jnp.int32(act))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p0))
    tol = 1e-12 if np.finfo(dtype).bits == 64 else 1e-5
    np.testing.assert_allclose(np.asarray(lu1), np.asarray(lu0), rtol=0,
                               atol=tol * np.abs(np.asarray(lu0)).max())
    # pad rows are fixed points of perm
    assert (np.asarray(p1)[act:] == np.arange(act, M)).all()


@pytest.mark.parametrize("zcol", [5, 32 + 7])
def test_strip_panel_zero_column(rng, zcol):
    """A zero column inside a strip: finite output, a zero U diagonal
    there, no NaN, and every later column still eliminated."""
    import jax.numpy as jnp

    M, w = 256, 64
    P = rng.standard_normal((M, w))
    P[:, zcol] = 0.0
    lu, perm = lu_kernels._lu_panel_strips(P, jnp.int32(M))
    lu, perm = np.asarray(lu), np.asarray(perm)
    assert np.isfinite(lu).all()
    assert lu[zcol, zcol] == 0.0
    assert sorted(perm) == list(range(M))
    L = np.tril(lu, -1)[:, :w] + np.eye(M, w)
    U = np.triu(lu[:w])
    np.testing.assert_allclose(P[perm], L @ U, atol=1e-12)


def test_blocked_getrf_strip_panel_vs_scipy(rng):
    """nb = 256 (strip panels) over four segments: LAPACK's pivots and a
    backward-stable factor."""
    import scipy.linalg as sla

    n, nb = 2048, 256
    A = rng.standard_normal((n, n))
    LU, perm = lu_kernels.blocked_getrf(np.asarray(A), nb)
    LU, perm = np.asarray(LU), np.asarray(perm)
    _, piv = sla.lu_factor(A)
    pref = np.arange(n)
    for i, p in enumerate(piv):
        pref[[i, p]] = pref[[p, i]]
    np.testing.assert_array_equal(perm, pref)
    L = np.tril(LU, -1) + np.eye(n)
    U = np.triu(LU)
    err = np.abs(A[perm] - L @ U).max() / np.abs(A).max()
    assert err < 1e-12, err


@pytest.mark.parametrize("nb", [16, 32, 48, 64, 96, 128])
def test_strip_panel_engagement(monkeypatch, nb):
    """_getrf_steps takes the strip panel exactly when nb is a multiple
    of PANEL_IB and at least two strips wide; below that, panel_lu."""
    import jax
    import jax.numpy as jnp

    used = []
    strips, plain = lu_kernels._lu_panel_strips, lu_kernels.panel_lu
    monkeypatch.setattr(lu_kernels, "_lu_panel_strips",
                        lambda *a, **k: used.append("strips") or strips(*a, **k))
    monkeypatch.setattr(lu_kernels, "panel_lu",
                        lambda *a, **k: used.append("panel_lu") or plain(*a, **k))
    n = 2 * nb
    jax.eval_shape(lambda G: lu_kernels.blocked_getrf(G, nb),
                   jax.ShapeDtypeStruct((n, n), jnp.float64))
    ib = lu_kernels.PANEL_IB
    want = "strips" if nb % ib == 0 and nb >= 2 * ib else "panel_lu"
    assert used == [want]


@pytest.mark.parametrize(
    "dtype,backend,dot",
    [
        (np.float64, "tpu", False),
        (np.complex128, "tpu", False),
        (np.float32, "tpu", True),
        (np.float64, "cpu", True),
    ],
)
def test_short_dot_route(rng, monkeypatch, dtype, backend, dot):
    """The strip panel's rank-32 product: elementwise only where f64 is
    emulated (the TPU), a HIGHEST dot (the MXU for f32) elsewhere; the
    same product either way."""
    import jax

    from slate_tpu.internal import precision

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    a = rng.standard_normal((48, 32)).astype(dtype)
    b = rng.standard_normal((32, 80)).astype(dtype)
    # a fresh function: a traced jaxpr is cached without the backend
    jaxpr = str(jax.make_jaxpr(lambda x, y: precision.short_dot(x, y))(a, b))
    assert ("dot_general" in jaxpr) == dot
    tol = 1e-12 if np.finfo(dtype).bits == 64 else 1e-5
    np.testing.assert_allclose(np.asarray(precision.short_dot(a, b)), a @ b,
                               rtol=0, atol=tol * np.abs(a @ b).max())
