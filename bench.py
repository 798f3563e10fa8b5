#!/usr/bin/env python
"""Headline benchmark sweep over the driver stack on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"}.

Headline metric: sgemm GFLOP/s per chip in the single-pass MXU mode
(SLATE_TPU_FAST_F32).  Baseline: the
reference's only published figure, dgemm 0.70 TFLOP/s per GPU (reference
docs/usage.md:40-42; see BASELINE.md).  vs_baseline = GFLOP/s / 700.

"extra" carries the north-star routine entries (BASELINE.json asks for
gemm/potrf/getrf/geqrf/heev): dgemm + f64 factorizations + the two-stage
heev values path, each with GFLOP/s and seconds.  f32 accurate-mode gemm
(the product default after the precision policy) is reported alongside
the fast mode.

Time budget (a sweep cut by its caller's timeout prints nothing): every
entry runs under a deadline (--budget seconds, default 780 — inside the
driver's typical 900 s timeout).  When the remaining budget dips below
the reserve, the remaining entries are recorded as {"skipped": "time
budget"} and the final JSON line still prints, so a partial sweep is a
diagnosable artifact instead of a dead log.  --quick shrinks sizes and
trial counts for smoke runs.

Per-entry observability: metrics (slate_tpu.aux.metrics) are ON for the
whole sweep; each entry runs inside metrics.context(label) and reports
its jit compilation delta + wall seconds in extra[label]["metrics"].
Set SLATE_TPU_METRICS=/path/out.jsonl to keep the full event stream.
"""

import argparse
import json
import os
import time

import numpy as np

# Persistent XLA compilation cache: the native blocked factorization
# kernels compile in minutes the first time; cached executables load in
# seconds on every later run.  An operator's JAX_COMPILATION_CACHE_DIR
# wins; otherwise one fixed path inside the checkout (the path is part
# of the cache key, so it must not move between runs).
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"),
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1.0")


def _gflops(name, hand_flops, best_s):
    """GFLOP/s with the numerator from the build-time registry record
    when one exists (metrics.costs(), populated by _bench's devmon
    capture — measured program, not a derived
    formula), keeping the hand formula as a cross-check.  XLA reports
    -1 for unknowable costs (e.g. CPU while loops): that is "no data",
    never zero, so the model numerator is used and the source is
    labeled.  The registry's memory_analysis fields ride along so the
    trajectory is bench_diff-able on peak memory, not just rates."""
    from slate_tpu.aux import metrics

    out = {"gflops_model": round(hand_flops / best_s / 1e9, 1)}
    rec = metrics.costs().get(name, {})
    xla = rec.get("flops", -1.0)
    if xla is not None and xla > 0:
        out["gflops"] = round(xla / best_s / 1e9, 1)
        out["flops_source"] = "xla_cost_analysis"
    else:
        out["gflops"] = out["gflops_model"]
        out["flops_source"] = "model"
    if rec.get("bytes_accessed"):
        out["bytes_accessed"] = int(rec["bytes_accessed"])
    if rec.get("peak_bytes"):
        out["peak_bytes"] = int(rec["peak_bytes"])
    return out


def _bench(step_fn, warm_args, trials, name=None):
    """Best-of wall time with host readback as the barrier.  With a
    name, the step is AOT-compiled ONCE via the devmon capture path
    (lower -> compile -> cost_analysis + memory_analysis), so the one
    compile every entry pays anyway is also the flops/bytes/peak-
    memory evidence — on every backend, with no AOT second compile
    (the per-call capture this replaces defaulted OFF on accelerators
    and left flops_source "no data" there); the compiled executable is
    then metrics-instrumented for the compile/run timer split.
    Deliberately NOT metrics.measure_best: the steps here carry the
    trial perturbation IN the jitted signature (t) and chain K
    dependent ops — re-wrapping them in measure_best's scalarizer
    would change the measured program."""
    if name is not None:
        from slate_tpu.aux import devmon, metrics

        t0 = time.perf_counter()
        compiled, _cost = devmon.capture_jitted(
            step_fn, (*warm_args, 0.0), name=name
        )
        if compiled is not None:
            # the AOT capture WAS the entry's backend compile: record
            # it under the compile timer/counters ourselves; the
            # wrapper below is told the executable is precompiled so
            # every dispatch (first warm call included) logs as a run
            metrics.observe(f"{name}.compile", time.perf_counter() - t0)
            metrics.inc("jit.compilations")
            metrics.inc(f"{name}.compilations")
            step_fn = compiled  # reuse the capture compile as the build
        step_fn = metrics.instrument_jit(
            step_fn, name, precompiled=compiled is not None
        )
    float(step_fn(*warm_args, 0.0))  # compile + warmup
    best = float("inf")
    for trial in range(trials):
        t0 = time.perf_counter()
        s = float(step_fn(*warm_args, 1.0 + trial))
        best = min(best, time.perf_counter() - t0)
        assert np.isfinite(s)
    return best


def bench_gemm(jax, jnp, n, nb, dtype, K, trials):
    from slate_tpu.drivers import blas3
    from slate_tpu.matrix.matrix import Matrix

    key = jax.random.PRNGKey(0)
    ka, kb = jax.random.split(key)
    A = Matrix.from_global(jax.random.normal(ka, (n, n), dtype), nb)
    B = Matrix.from_global(jax.random.normal(kb, (n, n), dtype) * (1.0 / n), nb)

    @jax.jit
    def step(A, B, t):
        # t varies per trial so no layer can serve a cached result; the
        # K-chain amortizes per-dispatch latency
        C = A._with(data=A.data + t)
        for _ in range(K):
            C = blas3.gemm(1.0, C, B, 0.0, C)
        return C.data.sum()

    # the name carries mode + K: fast-f32 and accurate-f32 run different
    # programs of different chain lengths and must not share timers/costs
    mode = "fast" if os.environ.get("SLATE_TPU_FAST_F32") == "1" else "hi"
    name = f"bench.gemm_{jnp.dtype(dtype).name}_{mode}_n{n}_K{K}"
    best = _bench(step, (A, B), trials, name=name)
    # hand model 2n^3 per gemm x K chained; the xla numerator covers the
    # same whole step (K gemms + the reduction), so both rate the step
    return _gflops(name, 2.0 * n**3 * K, best), best / K


def bench_potrf(jax, jnp, n, nb, trials, schedule="auto"):
    import slate_tpu as st
    from slate_tpu.enums import Option

    key = jax.random.PRNGKey(1)
    G = jax.random.normal(key, (n, n), jnp.float64) / np.sqrt(n)
    S = G @ G.T + 2.0 * jnp.eye(n, dtype=jnp.float64)
    A = st.HermitianMatrix.from_global(S, nb, uplo=st.Uplo.Lower)
    opts = {Option.Schedule: schedule}

    @jax.jit
    def step(A, t):
        L, info = st.potrf(A._with(data=A.data + t * 1e-14), opts)
        return L.data.sum() + info

    name = f"bench.potrf_n{n}_{schedule}"
    best = _bench(step, (A,), trials, name=name)
    return _gflops(name, n**3 / 3.0, best), best


def bench_getrf(jax, jnp, n, nb, trials, schedule="auto"):
    import slate_tpu as st
    from slate_tpu.enums import Option

    key = jax.random.PRNGKey(2)
    G = jax.random.normal(key, (n, n), jnp.float64)
    A = st.Matrix.from_global(G + n * jnp.eye(n, dtype=jnp.float64), nb)
    opts = {Option.Schedule: schedule}

    @jax.jit
    def step(A, t):
        LU, piv, info = st.getrf(A._with(data=A.data + t * 1e-14), opts)
        return LU.data.sum() + info

    name = f"bench.getrf_n{n}_{schedule}"
    best = _bench(step, (A,), trials, name=name)
    return _gflops(name, 2.0 * n**3 / 3.0, best), best


def bench_geqrf(jax, jnp, n, nb, trials, schedule="auto"):
    import slate_tpu as st
    from slate_tpu.enums import Option

    key = jax.random.PRNGKey(3)
    A = st.Matrix.from_global(jax.random.normal(key, (n, n), jnp.float64), nb)
    opts = {Option.Schedule: schedule}

    @jax.jit
    def step(A, t):
        fac, T = st.geqrf(A._with(data=A.data + t * 1e-14), opts)
        return fac.data.sum()

    name = f"bench.geqrf_n{n}_{schedule}"
    best = _bench(step, (A,), trials, name=name)
    return _gflops(name, 4.0 * n**3 / 3.0, best), best


def bench_trsm(jax, jnp, routine, n, nrhs, trials, schedule="auto"):
    """The solve-phase trsm pair behind the serve ``phase="solve"``
    buckets — the factor cache's top-traffic hit path.  ``posv`` times
    potrs_from_global (lower + transposed-lower sweep against a clean
    Cholesky factor), ``gesv`` times getrs_from_global (unit-lower +
    upper sweep against a packed LU) — both triangles covered between
    the two.  ``schedule="pallas"`` routes both sweeps through the
    fused Pallas trsm kernels (interpret mode off-TPU)."""
    from jax import lax

    from slate_tpu.drivers.chol import potrs_from_global
    from slate_tpu.drivers.lu import getrs_from_global

    key = jax.random.PRNGKey(5)
    kf, kr = jax.random.split(key)
    G = jax.random.normal(kf, (n, n), jnp.float64) / np.sqrt(n)
    B = jax.random.normal(kr, (n, nrhs), jnp.float64)
    if routine == "posv":
        S = G @ G.T + 2.0 * jnp.eye(n, dtype=jnp.float64)
        F = jnp.linalg.cholesky(S)
        solve = potrs_from_global
    else:
        F, _piv, _perm = lax.linalg.lu(G + jnp.eye(n, dtype=jnp.float64))
        solve = getrs_from_global

    @jax.jit
    def step(F, B, t):
        return solve(F, B + t * 1e-12, schedule).sum()

    name = f"bench.trsm_{routine}_n{n}_{schedule}"
    best = _bench(step, (F, B), trials, name=name)
    # two O(n^2 nrhs) triangular sweeps per solve
    return _gflops(name, 2.0 * n * n * nrhs, best), best


def bench_solve_mixed(jax, jnp, routine, n, nb, trials):
    """Mixed-precision solve vs the plain f64 direct driver: wall
    seconds for both (eager best-of — the mixed drivers run the host
    fallback branch, so they are timed as the user calls them),
    refinement iteration count, and the speedup ratio.  Well-
    conditioned operands so the refine path never falls back (a
    fallback would time factor+direct and report speedup < 1 — which
    is exactly what the ratio is for)."""
    import slate_tpu as st

    key = jax.random.PRNGKey(6)
    G = jax.random.normal(key, (n, n), jnp.float64)
    B = jax.random.normal(jax.random.PRNGKey(7), (n, 8), jnp.float64)
    Bm = st.Matrix.from_global(B, nb)

    if routine == "posv":
        S = G @ G.T / n + 2.0 * jnp.eye(n, dtype=jnp.float64)

        def make_A(t):
            return st.HermitianMatrix.from_global(
                S + t * 1e-12 * jnp.eye(n, dtype=jnp.float64), nb,
                uplo=st.Uplo.Lower,
            )

        def plain(A):
            X, _L, info = st.posv(A, Bm)
            return X, int(info)

        def mixed(A):
            X, info, iters = st.posv_mixed(A, Bm)
            return X, iters
    else:
        Ad = G + n * jnp.eye(n, dtype=jnp.float64)

        def make_A(t):
            return st.Matrix.from_global(
                Ad + t * 1e-12 * jnp.eye(n, dtype=jnp.float64), nb
            )

        def plain(A):
            X, _LU, _piv, info = st.gesv(A, Bm)
            return X, int(info)

        def mixed(A):
            X, info, iters = st.gesv_mixed(A, Bm)
            return X, iters

    def best_of(fn):
        fn(make_A(0.0))  # compile + warm
        best, aux = float("inf"), None
        for t in range(trials):
            A = make_A(1.0 + t)
            jax.block_until_ready(A.data)
            t0 = time.perf_counter()
            X, a = fn(A)
            float(np.asarray(X.data.ravel()[-1]))  # host readback barrier
            best = min(best, time.perf_counter() - t0)
            aux = a
        return best, aux

    sec_plain, _ = best_of(plain)
    sec_mixed, iters = best_of(mixed)
    return {
        "n": n,
        "seconds": round(sec_mixed, 3),
        "seconds_plain": round(sec_plain, 3),
        "speedup_vs_plain": round(sec_plain / sec_mixed, 3),
        "iterations": int(iters),
    }


def bench_heev_vectors(jax, jnp, n, nb, trials):
    """Two-stage heev WITH eigenvectors: he2hb + hb2st wavefront +
    native stedc divide & conquer + both back-transforms — no vendor
    eigensolver anywhere on the path (the vendor f64 eigh is a compile
    bomb past n~512 on this toolchain)."""
    import slate_tpu as st

    key = jax.random.PRNGKey(4)
    G = jax.random.normal(key, (n, n), jnp.float64)
    S = (G + G.T) / 2
    A = st.HermitianMatrix.from_global(S, nb, uplo=st.Uplo.Lower)

    @jax.jit
    def step(A, t):
        w, Z = st.heev(A._with(data=A.data + t * 1e-14), vectors=True)
        return w.sum() + Z.data.ravel()[-1]

    name = f"bench.heev_vectors_n{n}"
    best = _bench(step, (A,), trials, name=name)
    # flop model for the WITH-vectors path: 4n^3/3 reduction + ~4n^3/3
    # D&C vector assembly + 2n^3 hb2st back-transform + 2n^3 he2hb
    # back-transform ~= 20n^3/3 (LAPACK dsyevd-style accounting), so the
    # rate is comparable across entries (ADVICE r3)
    return _gflops(name, 20.0 * n**3 / 3.0, best), best


def bench_heev_values(jax, jnp, n, nb, trials):
    """Two-stage heev, eigenvalues only: he2hb + hb2st wavefront +
    Sturm bisection — no vendor eigensolver anywhere on this path."""
    import slate_tpu as st

    key = jax.random.PRNGKey(4)
    G = jax.random.normal(key, (n, n), jnp.float64)
    S = (G + G.T) / 2
    A = st.HermitianMatrix.from_global(S, nb, uplo=st.Uplo.Lower)

    @jax.jit
    def step(A, t):
        w, _ = st.heev(A._with(data=A.data + t * 1e-14), vectors=False)
        return w.sum()

    name = f"bench.heev_values_n{n}"
    best = _bench(step, (A,), trials, name=name)
    return _gflops(name, 4.0 * n**3 / 3.0, best), best


def _progress(msg):
    """Stage marker on stderr (the JSON contract owns stdout): makes a
    wedged remote compile attributable from the driver's log."""
    import sys

    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bench")
    ap.add_argument("--budget", type=float, default=780.0,
                    help="sweep deadline in seconds (0 = unlimited); "
                         "entries past it are recorded as skipped")
    ap.add_argument("--reserve", type=float, default=45.0,
                    help="stop starting entries when less than this many "
                         "seconds of budget remain")
    ap.add_argument("--quick", action="store_true",
                    help="CPU-scale sizes + minimal trials (smoke run)")
    ap.add_argument("--full", action="store_true",
                    help="historical flagship sizes (n=8192 factorizations, "
                         "staged heev up to 8192) — needs a raised --budget; "
                         "the default list is sized to fit the default "
                         "budget and exit 0")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from slate_tpu.aux import metrics

    metrics.on()
    # flops/bytes/peak-memory come from _bench's build-time devmon
    # capture (the AOT compile IS the entry's one build — no second
    # compile, so the numerators exist on accelerators too, where the
    # old per-call capture defaulted OFF and reported "no data")
    on_tpu = any(d.platform != "cpu" for d in jax.devices()) and not args.quick
    trials = 5 if on_tpu else 2
    extra = {}
    start = time.monotonic()
    deadline = start + args.budget if args.budget > 0 else None

    def run_entry(label, fn):
        """Run one bench entry under the budget: skipped entries are
        recorded (a partial sweep stays diagnosable),
        each entry carries its wall seconds + jit compilation delta."""
        if deadline is not None and time.monotonic() > deadline - args.reserve:
            _progress(f"{label}: SKIPPED (time budget)")
            extra[label] = {"skipped": "time budget"}
            return None
        _progress(label)
        c0 = metrics.counters().get("jit.compilations", 0)
        t0 = time.monotonic()
        with metrics.context(label):
            try:
                entry = fn()
            except Exception as e:  # noqa: BLE001 — the JSON line must print
                entry = {"error": str(e)[:120]}
        entry["metrics"] = {
            "wall_s": round(time.monotonic() - t0, 2),
            "compilations": metrics.counters().get("jit.compilations", 0) - c0,
        }
        extra[label] = entry
        return entry

    # -- headline: fast-f32 sgemm ------------------------------------------
    n = 8192 if on_tpu else 512

    def entry_sgemm_fast():
        os.environ["SLATE_TPU_FAST_F32"] = "1"
        rep, sec = bench_gemm(jax, jnp, n, 1024 if on_tpu else 128,
                              jnp.float32, 8 if on_tpu else 2, trials)
        return {"n": n, **rep}

    e = run_entry("sgemm_fast_f32", entry_sgemm_fast)
    gf_fast = e.get("gflops", 0.0) if e else 0.0

    # -- accurate-mode f32 gemm (product default) -------------------------
    def entry_sgemm_accurate():
        os.environ["SLATE_TPU_FAST_F32"] = "0"
        rep, _ = bench_gemm(jax, jnp, n, 1024 if on_tpu else 128,
                            jnp.float32, 4 if on_tpu else 2, trials)
        return {"n": n, **rep}

    run_entry("sgemm_accurate", entry_sgemm_accurate)

    # -- dgemm (the north-star dtype) at n=4096; tools/profile_factor.py
    # measures the n=8192 denominator out-of-band
    def entry_dgemm():
        nd = 4096 if on_tpu else 256
        rep, _ = bench_gemm(jax, jnp, nd, 512 if on_tpu else 128,
                            jnp.float64, 4 if on_tpu else 2, trials)
        return {"n": nd, **rep}

    run_entry("dgemm", entry_dgemm)

    # -- f64 factorizations, schedule=flat|recursive variants --------------
    # default sizes fit the default --budget (the 8192 flagships push a
    # sweep past its caller's timeout); --full
    # restores them.  The recursive variants measure the exact-shape
    # divide & conquer schedules; extra[label]["flops_waste_ratio"]
    # carries the per-entry exec/model ratio from the factor.* counters.
    nfac = (8192 if args.full else 4096) if on_tpu else 128

    def factor_entry(label, fn, nsize, nb, schedule):
        def run():
            from slate_tpu.aux import metrics as _m

            c0 = _m.counters()
            rep, sec = fn(nsize, nb, schedule)
            c1 = _m.counters()
            dm = c1.get("factor.flops_model", 0) - c0.get(
                "factor.flops_model", 0
            )
            dx = c1.get("factor.flops_exec", 0) - c0.get(
                "factor.flops_exec", 0
            )
            entry = {"n": nsize, "schedule": schedule, **rep,
                     "seconds": round(sec, 3)}
            if dm > 0:
                entry["flops_waste_ratio"] = round(dx / dm, 3)
            return entry

        return run_entry(label, run)

    nbfac = 512 if on_tpu else 32
    npo = nfac if on_tpu else 256
    nbpo = nbfac if on_tpu else 64

    def _potrf(nn, nb, s):
        return bench_potrf(jax, jnp, nn, nb, trials, s)

    def _getrf(nn, nb, s):
        return bench_getrf(jax, jnp, nn, nb, trials, s)

    def _geqrf(nn, nb, s):
        return bench_geqrf(jax, jnp, nn, nb, trials, s)

    factor_entry("dpotrf", _potrf, npo, nbpo, "flat")
    factor_entry("dpotrf_recursive", _potrf, npo, nbpo, "recursive")
    factor_entry("dgetrf", _getrf, nfac, nbfac, "flat")
    factor_entry("dgetrf_recursive", _getrf, nfac, nbfac, "recursive")
    factor_entry("dgeqrf", _geqrf, nfac, nbfac, "flat")
    factor_entry("dgeqrf_recursive", _geqrf, nfac, nbfac, "recursive")

    # -- solve-phase trsm pair (the serve factor cache's top-traffic
    # hit path — phase="solve" buckets).  Both triangles between the
    # two routines, vendor vs fused-Pallas schedule variants -----------
    ntr = (8192 if args.full else 4096) if on_tpu else 256
    nrhs_tr = 512 if on_tpu else 64

    def trsm_entry(label, routine, schedule):
        def run():
            rep, sec = bench_trsm(
                jax, jnp, routine, ntr, nrhs_tr, trials, schedule
            )
            return {"n": ntr, "nrhs": nrhs_tr, "schedule": schedule,
                    **rep, "seconds": round(sec, 4)}

        return run_entry(label, run)

    trsm_entry("dtrsm_posv", "posv", "auto")
    trsm_entry("dtrsm_posv_pallas", "posv", "pallas")
    trsm_entry("dtrsm_gesv", "gesv", "auto")
    trsm_entry("dtrsm_gesv_pallas", "gesv", "pallas")

    # -- mixed-precision solves (refine/): f32-factor IR vs plain f64.
    # speedup_vs_plain is the headline the subsystem exists for: on the
    # MXU the f32 factor runs several times faster than the emulated-
    # f64 one, and the O(n^2) refinement is noise at these sizes -------
    nmix = (4096 if args.full else 2048) if on_tpu else 256

    def entry_mixed(routine):
        def run():
            return bench_solve_mixed(
                jax, jnp, routine, nmix, 512 if on_tpu else 32, trials
            )

        return run

    run_entry("dgesv_mixed", entry_mixed("gesv"))
    run_entry("dposv_mixed", entry_mixed("posv"))

    # -- serving scale-out: the same warmed request stream at
    # replicas=1 vs replicas=N (fake CPU devices here, real chips when
    # available — on one physical CPU the replicas share cores, so the
    # honest headline is the dispatch spread + requests/s pair, not a
    # speedup claim) ---------------------------------------------------
    def entry_serve_scaling():
        from slate_tpu.aux import metrics as _m
        from slate_tpu.serve import buckets as _bk
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.placement import PlacementPolicy
        from slate_tpu.serve.service import SolverService

        ndev = len(jax.devices())
        nrep = max(2, min(4, ndev))
        nserve = 512 if on_tpu else 64
        reqs = 48
        rng = np.random.default_rng(0)
        probs = [
            (rng.standard_normal((nserve, nserve)) + nserve * np.eye(nserve),
             rng.standard_normal((nserve, 4)))
            for _ in range(8)
        ]
        out = {"n": nserve, "requests": reqs, "devices": ndev}
        rates = {}
        for nrep_i in (1, nrep):
            # factor_cache=False: this entry measures dispatch spread,
            # and an env-armed cache would detour the repeated-A probs
            # onto unwarmed solve buckets (cold compiles mid-stream)
            svc = SolverService(
                cache=ExecutableCache(manifest_path=None), batch_max=8,
                batch_window_s=0.001,
                placement=PlacementPolicy(replicas=nrep_i),
                factor_cache=False,
            )
            key = _bk.bucket_for("gesv", nserve, nserve, 4, np.float64)
            svc.cache.ensure_manifest(key, (1, 8))
            svc.warmup()  # compile-free stream: rates measure dispatch
            c0 = _m.counters().get("serve.replicated_dispatch", 0)
            t0 = time.perf_counter()
            with _m.deltas() as d:
                futs = [
                    svc.submit("gesv", *probs[i % len(probs)])
                    for i in range(reqs)
                ]
                for f in futs:
                    assert np.all(np.isfinite(f.result(timeout=600)))
            dt = time.perf_counter() - t0
            svc.stop()
            rates[nrep_i] = reqs / dt
            rep = {
                "requests_per_s": round(reqs / dt, 1),
                "seconds": round(dt, 3),
                "replicated_dispatch": int(
                    _m.counters().get("serve.replicated_dispatch", 0) - c0
                ),
            }
            # tail latency alongside throughput (tracks the
            # p99 curve, not just requests/s): the serve.latency
            # histograms windowed to this config's stream
            lat = d.hist(f"serve.latency.{key.label}.total")
            if lat:
                rep.update(
                    p50_ms=round(lat["p50"] * 1e3, 2),
                    p95_ms=round(lat["p95"] * 1e3, 2),
                    p99_ms=round(lat["p99"] * 1e3, 2),
                )
            out[f"replicas_{nrep_i}"] = rep
        out["scaling_x"] = round(rates[nrep] / max(rates[1], 1e-9), 2)
        return out

    run_entry("serve_scaling", entry_serve_scaling)

    # -- serving tail latency: one warmed replica, a mixed small/large
    # stream, and the queued/execute/total percentile split per bucket
    # (the SLO surface; tools/latency_report.py renders the same table
    # from a SLATE_TPU_METRICS JSONL) --------------------------------
    def entry_serve_latency():
        from slate_tpu.aux import metrics as _m
        from slate_tpu.serve import buckets as _bk
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.service import SolverService

        nsm = 256 if on_tpu else 24
        nlg = 512 if on_tpu else 48
        reqs = 64

        def prob(n, seed):
            r = np.random.default_rng(seed)
            return (r.standard_normal((n, n)) + n * np.eye(n),
                    r.standard_normal((n, 4)))

        probs = [prob(nsm, i) for i in range(6)] + [
            prob(nlg, 100 + i) for i in range(2)
        ]
        svc = SolverService(
            cache=ExecutableCache(manifest_path=None), batch_max=8,
            batch_window_s=0.001, dim_floor=16, nrhs_floor=4,
            factor_cache=False,  # tail latency of the DIRECT bucket path
        )
        keys = {
            n: _bk.bucket_for("gesv", n, n, 4, np.float64,
                              floor=16, nrhs_floor=4)
            for n in (nsm, nlg)
        }
        for k in keys.values():
            svc.cache.ensure_manifest(k, (1, 8))
        svc.warmup()
        t0 = time.perf_counter()
        with _m.deltas() as d:
            futs = [
                # 3:1 small:large mix, interleaved so buckets contend
                svc.submit("gesv", *probs[i % len(probs)])
                for i in range(reqs)
            ]
            for f in futs:
                assert np.all(np.isfinite(f.result(timeout=600)))
        dt = time.perf_counter() - t0
        svc.stop()
        out = {"requests": reqs,
               "requests_per_s": round(reqs / dt, 1),
               "seconds": round(dt, 3)}
        for n, k in keys.items():
            row = {}
            for split in ("queued", "execute", "total"):
                h = d.hist(f"serve.latency.{k.label}.{split}")
                if h:
                    row[split] = {
                        "p50_ms": round(h["p50"] * 1e3, 2),
                        "p95_ms": round(h["p95"] * 1e3, 2),
                        "p99_ms": round(h["p99"] * 1e3, 2),
                    }
            row["count"] = (d.hist(f"serve.latency.{k.label}.total")
                            or {}).get("count", 0)
            out[f"n{n}"] = row
        return out

    run_entry("serve_latency", entry_serve_latency)

    # -- factor-once solve-many: a warmed repeated-A stream (1 factor +
    # N right-hand sides) through the factor cache's trsm-only solve
    # buckets vs the same stream refactoring every request.  The
    # headline is speedup_vs_refactor: steady-state O(n^2) vs O(n^3)
    # per request (the hit/miss deltas prove which path served) -------
    def entry_factor_solve_many():
        from slate_tpu.aux import metrics as _m
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.factor_cache import FactorCache
        from slate_tpu.serve.service import SolverService

        nfc = 1024 if on_tpu else 128
        reqs = 32
        rng = np.random.default_rng(0)
        A = rng.standard_normal((nfc, nfc)) + nfc * np.eye(nfc)
        Bs = [rng.standard_normal((nfc, 4)) for _ in range(8)]
        out = {"n": nfc, "requests": reqs}
        rates = {}
        for mode in ("refactor", "factor_cache"):
            # False = explicitly off (None would re-resolve the
            # SLATE_TPU_FACTOR_CACHE env and poison the baseline)
            fc = FactorCache(max_entries=8) if mode == "factor_cache" \
                else False
            svc = SolverService(
                cache=ExecutableCache(manifest_path=None), batch_max=8,
                batch_window_s=0.001, factor_cache=fc,
            )
            # warm: one solve registers (and, with the cache, factors);
            # warmup() then precompiles the registered buckets so the
            # measured stream is compile-free on both paths
            svc.submit("gesv", A, Bs[0]).result(timeout=600)
            svc.warmup()
            t0 = time.perf_counter()
            with _m.deltas() as d:
                futs = [
                    svc.submit("gesv", A, Bs[i % len(Bs)])
                    for i in range(reqs)
                ]
                for f in futs:
                    assert np.all(np.isfinite(f.result(timeout=600)))
                hits = int(d.get("serve.factor_cache.hit"))
                misses = int(d.get("serve.factor_cache.miss"))
            dt = time.perf_counter() - t0
            svc.stop()
            rates[mode] = reqs / dt
            out[mode] = {
                "requests_per_s": round(reqs / dt, 1),
                "seconds": round(dt, 3),
                "hits": hits,
                "misses": misses,
            }
        out["speedup_vs_refactor"] = round(
            rates["factor_cache"] / max(rates["refactor"], 1e-9), 2
        )
        return out

    run_entry("factor_solve_many", entry_factor_solve_many)

    # -- gels factor reuse (fabric/): a warmed repeated-A least-squares
    # stream through the QR-pack solve buckets + device arena vs the
    # same stream refactoring every request.  speedup_vs_refactor is
    # the tentpole headline (steady-state O(m n nrhs) vs O(m n^2) per
    # request); top-level requests_per_s carries the floor ------------
    def entry_gels_factor_reuse():
        from slate_tpu.aux import metrics as _m
        from slate_tpu.fabric.arena import FactorArena
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.factor_cache import FactorCache
        from slate_tpu.serve.service import SolverService

        ng = 512 if on_tpu else 96
        mg = 2 * ng
        reqs = 24
        rng = np.random.default_rng(0)
        A = rng.standard_normal((mg, ng))
        Bs = [rng.standard_normal((mg, 4)) for _ in range(8)]
        out = {"m": mg, "n": ng, "requests": reqs}
        rates = {}
        for mode in ("refactor", "fabric"):
            # False = explicitly off (None would re-resolve the env
            # and poison the refactor baseline)
            fabric = mode == "fabric"
            svc = SolverService(
                cache=ExecutableCache(manifest_path=None), batch_max=8,
                batch_window_s=0.001,
                factor_cache=FactorCache(max_entries=8) if fabric
                else False,
                factor_arena=FactorArena() if fabric else False,
            )
            svc.submit("gels", A, Bs[0]).result(timeout=600)
            svc.warmup()  # precompile the registered buckets
            t0 = time.perf_counter()
            with _m.deltas() as d:
                futs = [
                    svc.submit("gels", A, Bs[i % len(Bs)])
                    for i in range(reqs)
                ]
                for f in futs:
                    assert np.all(np.isfinite(f.result(timeout=600)))
                hits = int(d.get("serve.factor_cache.hit") or 0)
                avoided = int(
                    d.get("serve.arena.upload_avoided_bytes") or 0
                )
            dt = time.perf_counter() - t0
            svc.stop()
            rates[mode] = reqs / dt
            out[mode] = {
                "requests_per_s": round(reqs / dt, 1),
                "seconds": round(dt, 3),
                "hits": hits,
            }
            if fabric:
                out[mode]["upload_avoided_bytes"] = avoided
        out["requests_per_s"] = round(rates["fabric"], 1)
        out["speedup_vs_refactor"] = round(
            rates["fabric"] / max(rates["refactor"], 1e-9), 2
        )
        return out

    run_entry("gels_factor_reuse", entry_gels_factor_reuse)

    # -- streaming session updates (fabric/session.py): append k rows,
    # O(k n^2) Householder fold into R, fenced CSNE solve — vs a full
    # refactor (lstsq) per step on the grown A.  requests_per_s counts
    # streamed solves (the floored headline); speedup_vs_refactor is
    # informational — at the tiny CPU shapes the python-loop update is
    # slower than LAPACK's refactor, the asymptotics only win at real
    # sizes.  Parity is asserted every step ---------------------------
    def entry_session_stream_update():
        from slate_tpu.fabric.session import FactorSession
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.service import SolverService

        ns = 256 if on_tpu else 64
        m0 = 2 * ns
        steps, k = 8, 4
        rng = np.random.default_rng(0)
        A0 = rng.standard_normal((m0, ns))
        Cs = [rng.standard_normal((k, ns)) for _ in range(steps)]
        bs = [
            rng.standard_normal((m0 + (i + 1) * k, 2))
            for i in range(steps)
        ]
        svc = SolverService(
            cache=ExecutableCache(manifest_path=None), batch_max=4,
            batch_window_s=0.001, factor_cache=False,
        )
        sess = FactorSession(svc, A0)
        Xs = []
        t0 = time.perf_counter()
        for C, b in zip(Cs, bs):
            sess.append(C)
            Xs.append(sess.solve(b))
        dt_s = time.perf_counter() - t0
        svc.stop()
        A_cur = A0
        t0 = time.perf_counter()
        refs = []
        for C, b in zip(Cs, bs):
            A_cur = np.vstack([A_cur, C])
            refs.append(np.linalg.lstsq(A_cur, b, rcond=None)[0])
        dt_r = time.perf_counter() - t0
        err = max(
            float(np.abs(x - r).max()) for x, r in zip(Xs, refs)
        )
        assert err < 1e-8, f"streamed update drifted: {err}"
        return {
            "m0": m0, "n": ns, "steps": steps, "rows_per_step": k,
            "requests_per_s": round(steps / dt_s, 1),
            "seconds": round(dt_s, 3),
            "refactor_seconds": round(dt_r, 3),
            "speedup_vs_refactor": round(dt_r / max(dt_s, 1e-9), 2),
            "max_err": err,
        }

    run_entry("session_stream_update", entry_session_stream_update)

    # -- multi-tenant fairness: the SAME burst trace (one abusive
    # flood, then a well-behaved tenant's small stream) through a
    # static config vs the admission plane (tenant quotas + WFQ +
    # adaptive window).  The headline is the well-behaved tenant's p99
    # under each config plus the abuser's shed/rejected counts — on
    # CPU the queueing deltas are modest (one worker, fast solves);
    # the curve is for real chips, the fairness direction holds
    # everywhere ------------------------------------------------------
    def entry_serve_multitenant():
        from slate_tpu.aux import metrics as _m
        from slate_tpu.serve import buckets as _bk
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.service import SolverService
        from slate_tpu.exceptions import SlateError

        n_ab = 1024 if on_tpu else 192
        n_good = 512 if on_tpu else 96
        flood, nice = 24, 8
        rng = np.random.default_rng(0)
        A_a = rng.standard_normal((n_ab, n_ab)) + n_ab * np.eye(n_ab)
        B_a = rng.standard_normal((n_ab, 4))
        good_probs = [
            (rng.standard_normal((n_good, n_good))
             + n_good * np.eye(n_good),
             rng.standard_normal((n_good, 4)))
            for _ in range(nice)
        ]
        k_ab = _bk.bucket_for("gesv", n_ab, n_ab, 4, np.float64)
        k_good = _bk.bucket_for("gesv", n_good, n_good, 4, np.float64)
        out = {"n_abuser": n_ab, "n_good": n_good,
               "flood": flood, "good_requests": nice}
        for mode in ("static", "adaptive"):
            # tenants=""/adaptive=False: explicitly OFF for the static
            # baseline (None would re-resolve SLATE_TPU_TENANTS/
            # SLATE_TPU_ADAPTIVE and poison the comparison — the same
            # trap factor_cache=False guards against above)
            kw = dict(
                cache=ExecutableCache(manifest_path=None), batch_max=4,
                batch_window_s=0.002, factor_cache=False,
                tenants="", adaptive=False,
            )
            if mode == "adaptive":
                kw.update(
                    tenants=(
                        "good:weight=4;"
                        "abuser:rate=10,burst=4,share=0.25"
                    ),
                    adaptive=True, latency_budget_s=0.25,
                )
            svc = SolverService(**kw)
            svc.cache.ensure_manifest(k_ab, (1, 4))
            svc.cache.ensure_manifest(k_good, (1, 4))
            svc.warmup()  # the burst measures queueing, not compiles
            refused = 0
            t0 = time.perf_counter()
            with _m.deltas() as d:
                futs = []
                for _ in range(flood):
                    try:
                        futs.append(svc.submit(
                            "gesv", A_a, B_a, tenant="abuser",
                            priority="low",
                        ))
                    except SlateError:
                        refused += 1  # quota/share Rejected or Shed
                for A, B in good_probs:
                    futs.append(svc.submit(
                        "gesv", A, B, tenant="good", priority="high",
                    ))
                for f in futs:
                    assert np.all(np.isfinite(f.result(timeout=600)))
            dt = time.perf_counter() - t0
            svc.stop()
            # the victim's p99: per-tenant histogram when the plane is
            # on, the good bucket's histogram for the static baseline
            # (same requests — the abuser rides a different bucket)
            h = d.hist(
                "serve.latency.tenant.good.total" if mode == "adaptive"
                else f"serve.latency.{k_good.label}.total"
            )
            out[mode] = {
                "seconds": round(dt, 3),
                "good_p99_ms": (
                    round(h["p99"] * 1e3, 2) if h else None
                ),
                "abuser_refused": refused,
                "shed": int(d.get("serve.shed")),
                "rejected_quota": int(d.get("serve.rejected_quota")),
            }
        return out

    run_entry("serve_multitenant", entry_serve_multitenant)

    # -- sustained soak throughput: the soak fabric's open-loop replay
    # (multitenant + repeated-A mix, all serve planes armed) through a
    # warm service.  The headline is delivered req/s at the offered
    # rate's ceiling plus the client-observed p99 — the number the
    # --soak gate budgets against, tracked here so regressions show up
    # in bench_diff before they show up as a red gate ------------------
    def entry_soak_sustained():
        from slate_tpu.aux import metrics as _m
        from slate_tpu.serve import buckets as _bk
        from slate_tpu.serve.cache import ExecutableCache
        from slate_tpu.serve.factor_cache import FactorCache
        from slate_tpu.serve.service import SolverService
        from slate_tpu.soak import replay as _rp

        reqs = 4000 if on_tpu else 1200
        svc = SolverService(
            cache=ExecutableCache(manifest_path=None), batch_max=8,
            batch_window_s=0.001, dim_floor=16, nrhs_floor=4,
            factor_cache=FactorCache(max_entries=32),
            tenants="gold:weight=4;good:weight=2;free:rate=400,share=0.5",
            adaptive=True, latency_budget_s=0.5,
        )
        try:
            for routine, n in (("gesv", 12), ("posv", 12), ("gesv", 24)):
                k = _bk.bucket_for(routine, n, n, 2, np.float64,
                                   floor=16, nrhs_floor=4)
                svc.cache.ensure_manifest(k, (1, 8))
                svc.cache.ensure_manifest(k.solve_sibling(), (1, 8))
            svc.warmup()
            spec = _rp.merge_specs(
                _rp.gen_multitenant(reqs // 2, seed=1, rate_rps=500.0),
                _rp.gen_repeated_a(reqs // 2, seed=2, rate_rps=500.0,
                                   distinct=8),
            )
            # factor the pools before measuring: steady-state numbers,
            # not cold-cache numbers (the --soak gate does the same)
            _rp.replay(svc, _rp.warm_spec(spec, gap_s=0.01), speed=1.0,
                       seed=0, check_results=False)
            with _m.deltas() as d:
                res = _rp.replay(svc, spec, speed=4.0, seed=0,
                                 check_results=False)
                compiles = int(d.get("jit.compilations"))
        finally:
            svc.stop()
        return {
            "requests": res["submitted"],
            "delivered": res["delivered"],
            "refused": res["refused"],
            "requests_per_s": round(res["requests_per_s"], 1),
            "p50_s": res["p50_s"], "p99_s": res["p99_s"],
            "seconds": round(res["wall_s"], 3),
            "steady_compiles": compiles,
        }

    run_entry("soak_sustained", entry_soak_sustained)

    # -- two-stage heev values (he2hb + bulge chase + bisection) ----------
    nh = 1024 if on_tpu else 96

    def entry_heev_values():
        rep, sec = bench_heev_values(jax, jnp, nh, 64 if on_tpu else 8,
                                     max(2, trials - 3))
        return {"n": nh, **rep, "seconds": round(sec, 3)}

    run_entry("dheev_values_two_stage", entry_heev_values)

    # -- two-stage heev with vectors (+ native stedc D&C) -----------------
    def entry_heev_vectors():
        rep, sec = bench_heev_vectors(jax, jnp, nh, 64 if on_tpu else 8,
                                      max(2, trials - 3))
        return {"n": nh, **rep, "seconds": round(sec, 3)}

    run_entry("dheev_vectors_two_stage", entry_heev_vectors)

    # -- large-n heev with vectors, stage-split (the flagship path;
    # machine-readable stage seconds — verdict r4 weak #5) ---------------
    if on_tpu:
        import slate_tpu as st
        from slate_tpu.drivers.eig import heev_staged

        def entry_heev_staged(nbig):
            key = jax.random.PRNGKey(5)
            G = jax.random.normal(key, (nbig, nbig), jnp.float64)
            S = (G + G.T) / 2
            Ah = st.HermitianMatrix.from_global(S, 128, uplo=st.Uplo.Lower)
            heev_staged(Ah, vectors=True)  # compile + warm
            Ah2 = Ah._with(data=Ah.data + 1e-14)
            t0 = time.perf_counter()
            w, Z, stage_t = heev_staged(Ah2, vectors=True)
            sec = time.perf_counter() - t0
            return {
                "n": nbig, "seconds": round(sec, 2),
                # staged path compiles per stage — no single cost record
                # covers the chain, so this one stays on the hand model
                "gflops": round(20.0 * nbig**3 / 3.0 / sec / 1e9, 1),
                "flops_source": "model",
                "stages": stage_t,
            }

        for nbig in (2048, 4096, 8192) if args.full else (2048, 4096):
            run_entry(f"dheev_vectors_staged_n{nbig}",
                      lambda nbig=nbig: entry_heev_staged(nbig))

    _progress("metrics summary\n" + metrics.report())
    if os.environ.get("SLATE_TPU_METRICS"):
        metrics.dump()

    baseline_gflops = 700.0  # reference dgemm per GPU (docs/usage.md:40-42)
    # sweep-wide waste ratio from the new factor.* counter pair: executed
    # vs model FLOPs across every factorization the sweep dispatched
    # (None when no factorization entry ran — the field always prints)
    fmodel = metrics.counters().get("factor.flops_model", 0.0)
    fexec = metrics.counters().get("factor.flops_exec", 0.0)
    waste = round(fexec / fmodel, 3) if fmodel > 0 else None
    print(
        json.dumps(
            {
                "metric": f"sgemm_n{n}_gflops_per_chip",
                "value": round(gf_fast, 1),
                "unit": "GFLOP/s",
                "vs_baseline": round(gf_fast / baseline_gflops, 3),
                "flops_waste_ratio": waste,
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    main()
