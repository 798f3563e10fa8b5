"""Pallas panel-kernel schedule family — per-kernel parity against the
jnp reference twins (interpret mode on CPU), exact pivot-order equality
with ``lax.linalg.lu``, driver parity of ``schedule="pallas"`` with the
recursive family, the FLOP-accounting acceptance (pallas exec <=
recursive exec at the flagship point), and the serve round-trip: a
``schedule="pallas"`` bucket warms, persists to artifacts, and restores
compile-free.

All kernels run with ``interpret=True`` here: that lowers the fused
bodies to plain XLA ops, which is exactly how the pallas family reaches
CPU parity and how its serve executables export custom-call-free.
Only f64 rides tier-1 (each dtype costs a distinct compile of the whole
graph on the 2-core box); f32/c64/c128 are marked slow."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from slate_tpu.ops.chol_kernels import (
    chol_recursive,
    chol_schedule_flops,
    cholesky,
)
from slate_tpu.ops.lu_kernels import getrf_recursive, getrf_schedule_flops
from slate_tpu.ops.pallas import panel_kernels as pk
from slate_tpu.ops.qr_fast import (
    geqrf_pallas,
    geqrf_recursive,
    geqrf_schedule_flops,
)

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


def _tri(n, dtype, lower, unit, seed=0):
    # scale the strict triangle so the substitution stays conditioned
    # (a N(0,1) strict triangle amplifies error exponentially in n)
    a = _rand(n, n, dtype, seed) * 0.3
    d = 2.0 + jnp.abs(_rand(n, 1, dtype, seed + 7).real).astype(dtype)
    t = jnp.tril(a, -1) if lower else jnp.triu(a, 1)
    diag = jnp.ones((n,), dtype) if unit else d[:, 0]
    return t + jnp.diag(diag)


# ---------------------------------------------------------------------------
# kernel parity: pallas (interpret) vs the jnp reference twins
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
def test_chol_base_kernel_parity(dtype):
    n = 64
    G = _spd(n, dtype)
    got = np.tril(np.asarray(pk.chol_base_pallas(G, interpret=True)))
    ref = np.tril(np.asarray(pk.chol_base_reference(G)))
    tol = _tol(dtype, n) * float(np.abs(ref).max())
    assert np.allclose(got, ref, atol=tol)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(96, 32, None), (96, 32, 80), (160, 24, None)])
def test_panel_lu_kernel_parity(dtype, shape):
    # tall, act-masked, and non-power-of-two panel widths; the fused
    # kernel replicates panel_lu's arithmetic verbatim, so floats and
    # pivots are EXACTLY equal, not merely close
    m, nb, act = shape
    P = _rand(m, nb, dtype, seed=2)
    lu_p, perm_p = pk.panel_lu_pallas(P, act=act, interpret=True)
    lu_r, perm_r = pk.panel_lu_reference(P, act=act)
    assert np.array_equal(np.asarray(perm_p), np.asarray(perm_r))
    assert np.array_equal(np.asarray(lu_p), np.asarray(lu_r))


@pytest.mark.parametrize("dtype", DTYPES)
def test_larft_kernel_parity(dtype):
    # consistent compact-WY data: unit-diagonal V with a small strict
    # lower part and tau = 2/||v||^2 (an exactly unitary reflector), so
    # T^-1 stays well-conditioned — arbitrary (V, tau) pairs make the
    # triangular solve blow up and compare garbage against garbage
    m, nb = 96, 32
    V = jnp.tril(_rand(m, nb, dtype, seed=3), -1) * 0.1 + jnp.eye(
        m, nb, dtype=dtype
    )
    taus = (2.0 / jnp.sum(jnp.abs(V) ** 2, axis=0)).astype(dtype)
    T_p = np.asarray(pk.larft_pallas(V, taus, interpret=True))
    T_r = np.asarray(pk.larft_reference(V, taus))
    tol = _tol(dtype, m) * max(float(np.abs(T_r).max()), 1.0)
    assert np.allclose(T_p, T_r, atol=tol)


@pytest.mark.parametrize("dtype", DTYPES)
def test_syrk_and_gemm_kernel_parity(dtype):
    nb, k = 64, 32
    C = _spd(nb, dtype, seed=5)
    A = _rand(nb, k, dtype, seed=6)
    got = np.asarray(pk.syrk_diag_pallas(C, A, interpret=True))
    ref = np.asarray(pk.syrk_diag_reference(C, A))
    tol = _tol(dtype, nb) * float(np.abs(ref).max())
    assert np.allclose(got, ref, atol=tol)

    B = _rand(nb, k, dtype, seed=7)
    got = np.asarray(pk.gemm_sub_pallas(C, A, B, interpret=True))
    ref = np.asarray(pk.gemm_sub_reference(C, A, B))
    assert np.allclose(got, ref, atol=tol)


@pytest.mark.parametrize("dtype", DTYPES)
def test_trsm_kernel_parity(dtype):
    n, nrhs = 96, 8
    B = _rand(n, nrhs, dtype, seed=8)
    for lower, unit in ((True, False), (True, True), (False, False)):
        T = _tri(n, dtype, lower=lower, unit=unit, seed=9)
        if lower:
            X = pk.trsm_lower_pallas(T, B, unit=unit, interpret=True)
            ref = pk.trsm_lower_reference(T, B, unit=unit)
        else:
            X = pk.trsm_upper_pallas(T, B, interpret=True)
            ref = pk.trsm_upper_reference(T, B)
        ref = np.asarray(ref)
        err = np.abs(np.asarray(X) - ref).max()
        assert err <= _tol(dtype, n) * max(float(np.abs(ref).max()), 1.0)


def test_trsm_reads_only_its_triangle():
    # packed-LU storage: the other triangle holds factor data, and the
    # substitution must never touch it
    n, nrhs = 64, 4
    L = _tri(n, jnp.float64, lower=True, unit=True, seed=10)
    U = jnp.triu(_rand(n, n, jnp.float64, seed=11))  # garbage upper
    packed = jnp.tril(L, -1) + U
    B = _rand(n, nrhs, jnp.float64, seed=12)
    X = pk.trsm_lower_pallas(packed, B, unit=True, interpret=True)
    ref = pk.trsm_lower_reference(L, B, unit=True)
    assert np.allclose(np.asarray(X), np.asarray(ref), atol=1e-12 * n)


# ---------------------------------------------------------------------------
# schedule-family parity: family="pallas" vs family="recursive"
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", DTYPES)
def test_chol_family_parity(dtype):
    n = 192
    S = _spd(n, dtype, seed=13)
    Lp = np.asarray(chol_recursive(S, nb_switch=64, family="pallas"))
    ref = np.linalg.cholesky(np.asarray(S))
    tol = _tol(dtype, n) * float(np.abs(ref).max())
    assert np.allclose(Lp, ref, atol=tol)


@pytest.mark.parametrize("dtype", DTYPES)
def test_getrf_family_parity_exact(dtype):
    # the pallas panel replicates panel_lu's arithmetic, so the whole
    # recursion is bitwise-equal to the recursive family
    n = 192
    A = _rand(n, n, dtype, seed=14)
    LUp, pp = getrf_recursive(A, nb_switch=64, family="pallas")
    LUr, pr = getrf_recursive(A, nb_switch=64, family="recursive")
    assert np.array_equal(np.asarray(pp), np.asarray(pr))
    assert np.array_equal(np.asarray(LUp), np.asarray(LUr))


def test_getrf_pallas_pivot_order_matches_vendor():
    """EXACT pivot-order equality with lax.linalg.lu on tie-free random
    input — the fused in-register pivot search picks the same rows as
    the vendor partial-pivot sweep."""
    n = 192
    A = _rand(n, n, jnp.float64, seed=15)
    _, perm = getrf_recursive(A, nb_switch=64, family="pallas")
    _, _, vendor_perm = lax.linalg.lu(A)
    assert np.array_equal(np.asarray(perm), np.asarray(vendor_perm))


@pytest.mark.parametrize("dtype", DTYPES)
def test_geqrf_family_parity_exact(dtype):
    n = 192
    A = _rand(n, n, dtype, seed=16)
    Fp, taup = geqrf_pallas(A, 64)
    Fr, taur = geqrf_recursive(A, nb_switch=64)
    assert np.array_equal(np.asarray(Fp), np.asarray(Fr))
    assert np.array_equal(np.asarray(taup), np.asarray(taur))


@pytest.mark.slow
def test_getrf_family_parity_tall():
    m, n = 320, 192
    A = _rand(m, n, jnp.float64, seed=17)
    LUp, pp = getrf_recursive(A, nb_switch=64, family="pallas")
    LUr, pr = getrf_recursive(A, nb_switch=64, family="recursive")
    assert np.array_equal(np.asarray(pp), np.asarray(pr))
    assert np.array_equal(np.asarray(LUp), np.asarray(LUr))


@pytest.mark.slow
def test_non_power_of_two_via_bucket_pad():
    # the cholesky dispatcher pads any n to the 128 lattice; 200 -> 256
    # exercises pad + crop around the pallas recursion
    n = 200
    S = _spd(n, jnp.float64, seed=18)
    L = cholesky(S, 64, schedule="pallas")
    ref = np.linalg.cholesky(np.asarray(S))
    assert np.allclose(np.asarray(L), ref, atol=1e-10 * n)


# ---------------------------------------------------------------------------
# solve-phase trsm routing through the drivers
# ---------------------------------------------------------------------------


def test_potrs_pallas_route_matches_vendor():
    from slate_tpu.drivers.chol import potrs_from_global

    n, nrhs = 64, 4
    S = _spd(n, jnp.float64, seed=19)
    L = jnp.linalg.cholesky(S)
    B = _rand(n, nrhs, jnp.float64, seed=20)
    Xp = np.asarray(potrs_from_global(L, B, schedule="pallas"))
    Xv = np.asarray(potrs_from_global(L, B, schedule="auto"))
    assert np.allclose(Xp, Xv, atol=1e-10 * n)


def test_getrs_pallas_route_matches_vendor():
    from slate_tpu.drivers.lu import getrs_from_global

    n, nrhs = 64, 4
    A = _rand(n, n, jnp.float64, seed=21) + n * jnp.eye(n)
    LU, _piv, perm = lax.linalg.lu(A)
    B = _rand(n, nrhs, jnp.float64, seed=22)
    Bp = B[perm]
    Xp = np.asarray(getrs_from_global(LU, Bp, schedule="pallas"))
    Xv = np.asarray(getrs_from_global(LU, Bp, schedule="auto"))
    assert np.allclose(Xp, Xv, atol=1e-10 * n)
    # and the route actually solves: A X = B
    assert np.allclose(
        np.asarray(A) @ Xp, np.asarray(B), atol=1e-9 * n
    )


# ---------------------------------------------------------------------------
# FLOP accounting: pallas exec <= recursive exec at the flagship point
# ---------------------------------------------------------------------------


def test_pallas_flops_ratio_not_worse_than_recursive():
    """Acceptance: flops_exec/flops_model for the pallas family <= the
    recursive family at n=2048, nb=256 for all three routines (the
    fused base cases remove the strip-mined panel overhead, they never
    add work)."""
    for fn, shape in (
        (chol_schedule_flops, (2048, 512)),
        (getrf_schedule_flops, (2048, 2048, 512)),
        (geqrf_schedule_flops, (2048, 2048, 512)),
    ):
        fp = fn(*shape, "pallas", nb_switch=256)
        fr = fn(*shape, "recursive", nb_switch=256)
        assert fp["model"] == fr["model"]
        assert fp["exec"] / fp["model"] <= fr["exec"] / fr["model"], (
            fn.__name__, fp, fr,
        )


def test_pallas_compile_units_bound_n2048():
    """Per-octave compile-unit bounds for the pallas family.  chol gets
    +3 over the recursive bound: the triangle-aware syrk splits each
    trailing update into a diagonal unit (pallas_syrk) plus an
    off-diagonal gemm unit, one extra distinct shape per octave."""
    L = 2 * math.log2(2048 / 256)
    ch = chol_schedule_flops(2048, 512, "pallas", nb_switch=256)
    assert len(ch["units"]) <= L + 8, sorted(ch["units"])
    assert any(str(u[0]).startswith("pallas_") for u in ch["units"])
    lu = getrf_schedule_flops(2048, 2048, 512, "pallas", nb_switch=256)
    assert len(lu["units"]) <= L + 14, sorted(lu["units"])
    assert any(str(u[0]).startswith("pallas_") for u in lu["units"])
    qr = geqrf_schedule_flops(2048, 2048, 512, "pallas", nb_switch=256)
    assert len(qr["units"]) <= L + 14, sorted(qr["units"])
    assert any(str(u[0]).startswith("pallas_") for u in qr["units"])


# ---------------------------------------------------------------------------
# driver integration: Option.Schedule "pallas" + metrics mirrors
# ---------------------------------------------------------------------------


def test_driver_pallas_compile_guard_and_flops_counters():
    import slate_tpu as st
    from slate_tpu.aux import metrics
    from slate_tpu.enums import Option

    n = 256
    S = _spd(n, jnp.float64, seed=23)
    A = st.HermitianMatrix.from_global(S, 64, uplo=st.Uplo.Lower)
    opts = {Option.Schedule: "pallas", Option.BlockSize: 64}
    metrics.on()
    try:
        metrics.reset()
        L1, info1 = st.potrf(A, opts)
        c = metrics.counters()
        first = c.get("jit.compilations", 0)
        assert first <= 2, c
        fl = chol_schedule_flops(n, 256, "pallas", nb_switch=64)
        assert c["factor.potrf.flops_model"] == pytest.approx(fl["model"])
        assert c["factor.potrf.flops_exec"] == pytest.approx(fl["exec"])
        units = metrics.gauges()["factor.potrf.compile_units"]
        assert units == len(fl["units"])
        L2, info2 = st.potrf(A, opts)
        again = metrics.counters().get("jit.compilations", 0) - first
        assert again == 0, metrics.counters()
    finally:
        metrics.off()
    assert int(info1) == 0
    ref = np.linalg.cholesky(np.asarray(S))
    assert np.allclose(np.asarray(L1.to_global()), ref, atol=1e-9 * n)
    assert np.allclose(
        np.asarray(L1.to_global()), np.asarray(L2.to_global())
    )


def test_schedule_enum_and_bucket_roundtrip():
    from slate_tpu.enums import Schedule
    from slate_tpu.serve import buckets as bk

    assert Schedule.from_string("pallas") is Schedule.Pallas
    assert Schedule.from_string("panel") is Schedule.Pallas  # alias
    k_auto = bk.bucket_for("posv", 100, 100, 4, np.float64)
    k_pal = bk.bucket_for(
        "posv", 100, 100, 4, np.float64, schedule="pallas"
    )
    assert k_auto != k_pal and k_pal.schedule == "pallas"
    text = bk.manifest_dumps([(k_pal, 2)])
    back = dict(bk.manifest_loads(text))
    assert back[k_pal] == 2


# ---------------------------------------------------------------------------
# serve round-trip: a pallas bucket warms, persists, restores compile-free
# ---------------------------------------------------------------------------


def test_serve_pallas_bucket_warm_persist_restore(tmp_path):
    """A schedule="pallas" bucket traces custom-call-free (interpret
    mode lowers to plain XLA ops), so jax.export persists it and a
    FRESH cache restores the executable without compiling."""
    import os

    from slate_tpu.aux import metrics
    from slate_tpu.serve import buckets as bk
    from slate_tpu.serve.cache import ExecutableCache, direct_call

    key = bk.bucket_for(
        "gesv", 10, 10, 2, np.float64, floor=16, nrhs_floor=4,
        schedule="pallas",
    )
    man = str(tmp_path / "warmup.json")
    art = str(tmp_path / "store")
    metrics.off()
    metrics.reset()
    metrics.on()
    try:
        cache = ExecutableCache(manifest_path=man, artifact_dir=art)
        cache.ensure_manifest(key, (1,))
        assert cache.warmup(batch_max=1) >= 1
        assert [
            f for f in os.listdir(art) if f.endswith(".slate_exe")
        ], "pallas warmup must persist artifacts"

        # a fresh cache restores from the export artifact (the ladder
        # counts it restored, not compiled — the re-jit of the
        # deserialized module is served by the store-seeded XLA cache)
        fresh = ExecutableCache(manifest_path=man, artifact_dir=art)
        with metrics.deltas() as d:
            out = fresh.restore(batch_max=1)
        assert out["restored"] >= 1 and out["compiled"] == 0, out
        assert d.get("serve.artifact_hit") >= 1

        # steady state on the restored executable: real data through
        # the padded bucket, zero further compiles
        rng = np.random.default_rng(24)
        A = rng.standard_normal((10, 10)) + 10 * np.eye(10)
        B = rng.standard_normal((10, 2))
        Ap = np.eye(16)
        Ap[:10, :10] = A
        Bp = np.zeros((16, 4))
        Bp[:10, :2] = B
        with metrics.deltas() as d:
            X, info = fresh.run(key, Ap[None], Bp[None])
        assert d.get("jit.compilations") == 0
        assert int(info[0]) == 0
        ref = direct_call("gesv", A, B)
        err = np.abs(X[0][:10, :2] - ref).max()
        assert err < 1e-9 * max(np.abs(ref).max(), 1.0)
    finally:
        metrics.off()
        metrics.reset()


# ---------------------------------------------------------------------------
# auto routing on a TPU-reporting backend (the platform is patched here;
# nothing runs on it)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype,factor,trsm",
    [
        (jnp.float32, ("pallas", "pallas", "pallas"), "pallas"),
        (jnp.float64, ("flat_fori", "flat", "recursive"), "blocked"),
        (jnp.complex128, ("flat_fori", "flat", "recursive"), "blocked"),
    ],
)
def test_auto_routes_by_dtype_on_tpu(monkeypatch, dtype, factor, trsm):
    """auto sends only f32 to the Pallas family on the TPU: the kernels
    compile for nothing else, and the other dtypes take the loop
    schedules (QR: the jnp recursion) above the crossover."""
    from slate_tpu.drivers.chol import _solve_trsm_route
    from slate_tpu.ops.chol_kernels import resolve_schedule
    from slate_tpu.ops.lu_kernels import resolve_lu_schedule
    from slate_tpu.ops.qr_fast import resolve_qr_schedule

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 8192
    assert (
        resolve_schedule(n, dtype),
        resolve_lu_schedule(n, n, dtype),
        resolve_qr_schedule(n, n, dtype),
    ) == factor
    assert _solve_trsm_route(n, dtype, "auto") == trsm
    # below the crossover: the flat schedules and the vendor solve
    assert resolve_schedule(1024, dtype) == "flat"
    assert _solve_trsm_route(1024, dtype, "auto") == "vendor"


def test_explicit_pallas_on_tpu_takes_counted_twin_not_interpreter(
    monkeypatch,
):
    """On the TPU an operand Mosaic cannot take runs the jnp twin by a
    counted branch — never interpret mode."""
    from slate_tpu.aux import metrics
    from slate_tpu.ops.pallas import kernels as tk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert tk.on_tpu()
    G = _spd(128, jnp.float64, seed=30)
    metrics.off()
    metrics.reset()
    metrics.on()
    try:
        got = pk.chol_base(G)
        assert metrics.counters().get("pallas.reference.chol_base") == 1
    finally:
        metrics.off()
        metrics.reset()
    ref = pk.chol_base_reference(G)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_trsm_blocked_matches_vendor():
    """The blocked jnp trsm (auto's non-Pallas route above the
    crossover, and every explicit native schedule's solve) against the
    vendor solve, both sweeps and the unit-lower packed-LU case."""
    n, nrhs = 256, 5
    B = _rand(n, nrhs, jnp.float64, seed=31)
    for lower, unit in ((True, False), (True, True), (False, False)):
        T = _tri(n, jnp.float64, lower, unit, seed=32)
        got = pk.trsm_blocked(T, B, lower=lower, unit=unit)
        ref = lax.linalg.triangular_solve(
            T, B, left_side=True, lower=lower, unit_diagonal=unit
        )
        assert np.allclose(np.asarray(got), np.asarray(ref),
                           atol=1e-12 * n), (lower, unit)
