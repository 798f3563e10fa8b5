"""Recursive (divide & conquer) factorization schedules — parity against
the flat loops and scipy/LAPACK, compile-count guards, and the
FLOP-accounting acceptance bounds.

The recursive kernels (ops/chol_kernels.chol_recursive,
ops/lu_kernels.getrf_recursive, ops/qr_fast.geqrf_recursive) factor
exact halving-lattice shapes; tests use a small nb_switch so a few
hundred rows already exercise several recursion levels.  Heavy (n=2048)
end-to-end cases are marked slow (tier-1 budget)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.ops.chol_kernels import (
    blocked_potrf,
    chol_recursive,
    chol_schedule_flops,
    cholesky,
)
from slate_tpu.ops.lu_kernels import (
    blocked_getrf,
    getrf_recursive,
    getrf_schedule_flops,
)
from slate_tpu.ops.qr_fast import (
    geqrf_fast,
    geqrf_recursive,
    geqrf_schedule_flops,
)

# full dtype sweep; only f64 rides tier-1 (each parametrization costs a
# distinct XLA compile of the whole recursion graph, and the seed
# tier-1 gate has ~160 s of headroom on the 2-core box — ISSUE 3 asks
# for exactly this split: heavy cases go slow)
DTYPES = [
    pytest.param(jnp.float32, marks=pytest.mark.slow),
    jnp.float64,
    pytest.param(jnp.complex64, marks=pytest.mark.slow),
    pytest.param(jnp.complex128, marks=pytest.mark.slow),
]


def _tol(dtype, n):
    eps = float(jnp.finfo(jnp.zeros((), dtype).real.dtype).eps)
    return 50 * n * eps


def _rand(m, n, dtype, seed=0):
    key = jax.random.PRNGKey(seed)
    rt = jnp.zeros((), dtype).real.dtype
    a = jax.random.normal(key, (m, n), rt)
    if jnp.issubdtype(dtype, jnp.complexfloating):
        a = a + 1j * jax.random.normal(jax.random.PRNGKey(seed + 1), (m, n), rt)
    return a.astype(dtype)


def _spd(n, dtype, seed=0):
    a = _rand(n, n, dtype, seed)
    return a @ jnp.conj(a).T + n * jnp.eye(n, dtype=dtype)


# ---------------------------------------------------------------------------
# parity: recursive vs flat vs scipy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
def test_chol_recursive_parity(dtype):
    n = 192  # 192 -> split 128/64: two levels at nb_switch=64
    S = _spd(n, dtype)
    Lr = np.asarray(chol_recursive(S, nb_switch=64))
    Lf = np.asarray(blocked_potrf(S, 64))
    ref = np.linalg.cholesky(np.asarray(S))
    tol = _tol(dtype, n) * float(np.abs(ref).max())
    assert np.allclose(Lr, ref, atol=tol)
    assert np.allclose(np.tril(Lf), ref, atol=tol)


@pytest.mark.parametrize("dtype", DTYPES)
def test_getrf_recursive_parity(dtype):
    n = 192
    A = _rand(n, n, dtype, seed=2)
    LUr, pr = getrf_recursive(A, nb_switch=64)
    LUf, pf = blocked_getrf(A, 64)
    # same pivot sequence as the flat kernel on random (tie-free) input
    assert np.array_equal(np.asarray(pr), np.asarray(pf))
    assert np.allclose(
        np.asarray(LUr), np.asarray(LUf), atol=_tol(dtype, n)
    )
    # reconstruction against scipy: L U = A[perm]
    LU = np.asarray(LUr)
    perm = np.asarray(pr)
    L = np.tril(LU, -1) + np.eye(n)
    U = np.triu(LU)
    An = np.asarray(A)
    assert sorted(perm) == list(range(n))
    assert np.allclose(
        L @ U, An[perm], atol=_tol(dtype, n) * float(np.abs(An).max())
    )


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.float64, jnp.complex64])
def test_getrf_recursive_tall(dtype):
    m, n = 320, 192  # tall + canonical-height padding inside (320->lat)
    A = _rand(m, n, dtype, seed=3)
    LU, perm = getrf_recursive(A, nb_switch=64)
    LU = np.asarray(LU)
    perm = np.asarray(perm)
    L = np.tril(LU[:, :n], -1) + np.eye(m, n)
    U = np.triu(LU[:n])
    An = np.asarray(A)
    assert sorted(perm) == list(range(m))
    assert np.allclose(
        L @ U, An[perm], atol=_tol(dtype, n) * float(np.abs(An).max())
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.float64, jnp.complex64, jnp.complex128]
)
def test_geqrf_recursive_parity(dtype):
    m = n = 192
    A = _rand(m, n, dtype, seed=4)
    Fr, taur = geqrf_recursive(A, nb_switch=64)
    Ff, tauf = geqrf_fast(A, nb=64, ib=16)
    # R matches the flat kernel up to column sign conventions — both use
    # the same larfg, so it matches exactly (tie-free random input)
    Rr = np.triu(np.asarray(Fr)[:n])
    Rf = np.triu(np.asarray(Ff)[:n])
    tol = _tol(dtype, n) * float(np.abs(Rr).max())
    assert np.allclose(Rr, Rf, atol=tol)
    # |R| parity vs scipy
    import scipy.linalg as sla

    Rs = sla.qr(np.asarray(A), mode="r")[0][:n]
    assert np.allclose(np.abs(Rr), np.abs(Rs), atol=tol)


@pytest.mark.slow
def test_geqrf_recursive_q_reconstruction():
    m, n = 320, 256
    A = _rand(m, n, jnp.float64, seed=5)
    F, taus = geqrf_recursive(A, nb_switch=64)
    F = np.asarray(F)
    R = np.triu(F[:n])
    # apply reflectors in reverse to [R; 0] to rebuild A
    C = np.vstack([R, np.zeros((m - n, n))])
    taus = np.asarray(taus)
    for j in range(n - 1, -1, -1):
        v = np.concatenate([np.zeros(j), [1.0], F[j + 1 :, j]])
        C = C - taus[j] * np.outer(v, v @ C)
    assert np.allclose(C, np.asarray(A), atol=1e-10 * n)


@pytest.mark.slow
def test_non_power_of_two_via_bucket_pad():
    # the cholesky dispatcher pads any n to the 128 lattice with a
    # unit-diagonal splice; 200 -> 256 exercises pad + crop around the
    # recursion
    n = 200
    S = _spd(n, jnp.float64, seed=6)
    L = cholesky(S, 64, schedule="recursive")
    ref = np.linalg.cholesky(np.asarray(S))
    assert np.allclose(np.asarray(L), ref, atol=1e-10 * n)


@pytest.mark.slow
def test_chol_recursive_lookahead_peel():
    # lookahead=3 peels two eager panels ahead of the halving split
    n = 512
    S = _spd(n, jnp.float64, seed=7)
    L = chol_recursive(S, nb_switch=64, lookahead=3)
    ref = np.linalg.cholesky(np.asarray(S))
    assert np.allclose(np.asarray(L), ref, atol=1e-10 * n)


@pytest.mark.slow
def test_getrf_recursive_lookahead_peel():
    n = 512
    A = _rand(n, n, jnp.float64, seed=8)
    LU, perm = getrf_recursive(A, nb_switch=64, lookahead=3)
    LU0, perm0 = getrf_recursive(A, nb_switch=64, lookahead=1)
    # peeling changes the schedule, not the factorization
    assert np.array_equal(np.asarray(perm), np.asarray(perm0))
    assert np.allclose(np.asarray(LU), np.asarray(LU0), atol=1e-10)


# ---------------------------------------------------------------------------
# FLOP accounting: the acceptance bounds at the flagship point
# ---------------------------------------------------------------------------


def test_flops_ratio_acceptance_n2048():
    """Recursive dpotrf/dgetrf at n=2048, nb=256 must execute <= 1.35x
    the model FLOP count (the flat loops run ~2-6x)."""
    ch = chol_schedule_flops(2048, 512, "recursive", nb_switch=256)
    assert ch["exec"] / ch["model"] <= 1.35, ch
    lu = getrf_schedule_flops(2048, 2048, 512, "recursive", nb_switch=256)
    assert lu["exec"] / lu["model"] <= 1.35, lu
    # and the flat loops really are the waste the recursion removes:
    # the segmented flat getrf at n/nb = 4 runs one step per segment
    chf = chol_schedule_flops(2048, 512, "flat_fori")
    luf = getrf_schedule_flops(2048, 2048, 512, "flat")
    assert chf["exec"] / chf["model"] > 2.0
    assert luf["exec"] / luf["model"] == pytest.approx(2.109375)
    assert lu["exec"] < luf["exec"]


def test_compile_units_bound_n2048():
    """Distinct compiled shapes for one recursive factor stay bounded:
    chol <= 2 log2(n/nb) + 5, lu/qr <= 2 log2(n/nb) + 14 (tall
    operand heights snap to the 2-leading-bits lattice, <= 2 per
    octave)."""
    L = 2 * math.log2(2048 / 256)
    ch = chol_schedule_flops(2048, 512, "recursive", nb_switch=256)
    assert len(ch["units"]) <= L + 5, sorted(ch["units"])
    lu = getrf_schedule_flops(2048, 2048, 512, "recursive", nb_switch=256)
    assert len(lu["units"]) <= L + 14, sorted(lu["units"])
    qr = geqrf_schedule_flops(2048, 2048, 512, "recursive", nb_switch=256)
    assert len(qr["units"]) <= L + 14, sorted(qr["units"])


def test_recursive_beats_flat_at_scale():
    for n in (2048, 4096, 8192):
        ch_r = chol_schedule_flops(n, 512, "recursive", nb_switch=256)
        ch_f = chol_schedule_flops(n, 512, "flat_fori")
        assert ch_r["exec"] < ch_f["exec"] / 2
        lu_r = getrf_schedule_flops(n, n, 512, "recursive", nb_switch=256)
        lu_f = getrf_schedule_flops(n, n, 512, "flat")
        assert lu_r["exec"] < lu_f["exec"]
        qr_r = geqrf_schedule_flops(n, n, 512, "recursive", nb_switch=256)
        qr_f = geqrf_schedule_flops(n, n, 512, "flat")
        assert qr_r["exec"] < qr_f["exec"]


def test_flat_getrf_segments_n8192():
    """The flat getrf at n=8192, nb=512 runs four exact-shape loops of
    four steps (8192, 6144, 4096, 2048): 1.58x the model FLOPs, where
    one full-shape loop ran 3.28x."""
    lu = getrf_schedule_flops(8192, 8192, 512, "flat")
    assert lu["exec"] / lu["model"] == pytest.approx(1.58203125)
    gemms = sorted(u for u in lu["units"] if u[0] == "gemm")
    assert gemms == [("gemm", s, 512, s) for s in (2048, 4096, 6144, 8192)]


# ---------------------------------------------------------------------------
# compile-count guard + driver metrics integration
# ---------------------------------------------------------------------------


def test_compile_count_guard_recursive_driver():
    """One recursive factor = ONE top-level jit compilation per distinct
    driver shape (the recursion inlines into a single executable), and a
    repeat call at the same shape compiles nothing."""
    import slate_tpu as st
    from slate_tpu.aux import metrics
    from slate_tpu.enums import Option

    n = 256
    S = _spd(n, jnp.float64, seed=9)
    A = st.HermitianMatrix.from_global(S, 64, uplo=st.Uplo.Lower)
    opts = {Option.Schedule: "recursive", Option.BlockSize: 64}
    metrics.on()
    try:
        metrics.reset()
        L1, info1 = st.potrf(A, opts)
        first = metrics.counters().get("jit.compilations", 0)
        # the recursive path is one compile unit at the jit layer
        # (schedule shapes inline into one executable)
        assert first <= 2, metrics.counters()
        L2, info2 = st.potrf(A, opts)
        again = metrics.counters().get("jit.compilations", 0) - first
        assert again == 0, metrics.counters()
    finally:
        metrics.off()
    assert np.allclose(
        np.asarray(L1.to_global()), np.asarray(L2.to_global())
    )


def test_driver_flops_counters_match_accounting():
    """The factor.* counters recorded by the drivers equal the pure
    accounting functions for the traced shape."""
    import slate_tpu as st
    from slate_tpu.aux import metrics
    from slate_tpu.enums import Option
    from slate_tpu.ops.chol_kernels import resolve_schedule

    n = 256
    S = _spd(n, jnp.float64, seed=10)
    A = st.HermitianMatrix.from_global(S, 64, uplo=st.Uplo.Lower)
    opts = {Option.Schedule: "recursive", Option.BlockSize: 64}
    metrics.on()
    try:
        metrics.reset()
        st.potrf(A, opts)
        c = metrics.counters()
        fl = chol_schedule_flops(n, 256, "recursive", nb_switch=64)
        assert c["factor.potrf.flops_model"] == pytest.approx(fl["model"])
        assert c["factor.potrf.flops_exec"] == pytest.approx(fl["exec"])
        assert c["factor.flops_exec"] == pytest.approx(fl["exec"])
        units = metrics.gauges()["factor.potrf.compile_units"]
        assert units == len(fl["units"])
    finally:
        metrics.off()


def test_serve_bucket_key_schedule_roundtrip():
    """schedule is a first-class BucketKey component: distinct cache
    identity, manifest JSON round-trip, and back-compat default for old
    manifests."""
    from slate_tpu.serve import buckets as bk

    k_auto = bk.bucket_for("posv", 100, 100, 4, np.float64)
    k_rec = bk.bucket_for(
        "posv", 100, 100, 4, np.float64, schedule="recursive"
    )
    assert k_auto != k_rec and k_rec.schedule == "recursive"
    text = bk.manifest_dumps([(k_rec, 1), (k_auto, 8)])
    back = dict(bk.manifest_loads(text))
    assert back[k_rec] == 1 and back[k_auto] == 8
    # pre-schedule manifests parse with schedule="auto"
    legacy = {"routine": "posv", "m": 128, "n": 128, "nrhs": 8,
              "dtype": "float64", "nb": 64, "batch": 1}
    key = bk.BucketKey.from_json(legacy)
    assert key.schedule == "auto"


@pytest.mark.slow
def test_serve_recursive_schedule_end_to_end():
    """A recursive-schedule service serves correct solutions through
    the padded/batched path."""
    from slate_tpu.serve.cache import ExecutableCache
    from slate_tpu.serve.service import SolverService

    svc = SolverService(
        cache=ExecutableCache(manifest_path=None),
        batch_window_s=0.01,
        schedule="recursive",
        start=True,
    )
    try:
        rng = np.random.default_rng(11)
        a = rng.standard_normal((40, 40))
        S = a @ a.T + 40 * np.eye(40)
        B = rng.standard_normal((40, 3))
        X = svc.submit("posv", S, B).result(timeout=600)
        assert np.allclose(S @ X, B, atol=1e-8)
    finally:
        svc.stop()


# ---------------------------------------------------------------------------
# heavy end-to-end acceptance (slow): n=2048 through the real driver
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_recursive_driver_n2048_metrics_acceptance():
    import slate_tpu as st
    from slate_tpu.aux import metrics
    from slate_tpu.enums import Option

    n = 2048
    S = _spd(n, jnp.float64, seed=12)
    A = st.HermitianMatrix.from_global(S, 256, uplo=st.Uplo.Lower)
    opts = {Option.Schedule: "recursive", Option.BlockSize: 256}
    metrics.on()
    try:
        metrics.reset()
        L, info = st.potrf(A, opts)
        c = metrics.counters()
        assert int(info) == 0
        ratio = c["factor.potrf.flops_exec"] / c["factor.potrf.flops_model"]
        assert ratio <= 1.35, ratio
        ref = np.linalg.cholesky(np.asarray(S))
        assert np.allclose(
            np.asarray(L.to_global()), ref, atol=1e-8 * n
        )
    finally:
        metrics.off()
