#!/usr/bin/env python3
"""Chip smoke test: drive slate_tpu's default solve and serve paths once
on a TPU, through the public API, and check every answer at the
reference tester's bound (testing/checks.py: backward error
||B - A X|| / (||A|| ||X|| n) <= 3 eps).

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # the 2x2 SPMD solves + their 1x1 twin

Phases on one chip:
  (a) f64 st.posv / st.gesv at n=8192, nrhs=16, Option.Schedule auto on
      the default 1x1 grid (512 MiB per operand);
  (b) f32 st.posv / st.gesv at n=4096 under auto and under "pallas" —
      the pallas program must hold compiled Mosaic (tpu_custom_call) —
      plus st.norm on an f32 matrix whose tiles the norm kernel takes;
  (c) the served path: serve.configure() defaults, serve.warmup() of
      the buckets used, 16 f64 gesv/posv requests (4 reuse one A), each
      checked against numpy.linalg.solve; the window counts 0 compiles.

The printed seconds are smoke readings, not benchmark numbers.  The last
stdout line is the JSON verdict; a failure exits non-zero without it.
Needs a TPU: on any other platform it exits non-zero before any work.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# an operator's cache directory wins; otherwise one fixed path in the
# checkout (the path is part of the cache key)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
)

SEED = 2026
#: problem sizes per phase (the CPU rehearsal shrinks these)
SIZES = {"a": 8192, "b": 4096, "four": 16384, "twin": 2048,
         "serve": (1024, 1000, 2048, 1900)}


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def spd(st, jnp, n, nb, dtype, seed):
    """The tester's SPD construction ((G + G^T)/2 + n I) from a
    matgen rand matrix, made on the device."""
    G = st.matgen.generate("rand", n, n, nb, dtype=dtype,
                           seed=seed).to_global()
    return (G + G.T) / 2 + n * jnp.eye(n, dtype=dtype)


def general(st, jnp, n, nb, dtype, seed):
    return st.matgen.generate("randn", n, n, nb, dtype=dtype,
                              seed=seed).to_global()


def backward_error(A, X, B):
    import numpy as np

    from slate_tpu.testing.checks import solve_residual

    f64 = np.float64
    return solve_residual(
        np.asarray(A, f64), np.asarray(X, f64), np.asarray(B, f64)
    )


def timed_solve(jax, label, fn, args):
    """Compile ``fn`` for ``args``, run it once, and return
    (X, compiled_text, compile_s, run_s)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    X = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    say(f"{label}: compile_s={t1 - t0} run_s={t2 - t1}")
    return X, compiled.as_text(), t1 - t0, t2 - t1


def solvers(st, nb, opts):
    """The two public solves as functions of global arrays."""

    def posv(S, B):
        A = st.HermitianMatrix.from_global(S, nb, uplo=st.Uplo.Lower)
        X, _L, _info = st.posv(A, st.Matrix.from_global(B, nb), opts)
        return X.to_global()

    def gesv(A, B):
        X, _LU, _piv, _info = st.gesv(
            st.Matrix.from_global(A, nb), st.Matrix.from_global(B, nb), opts
        )
        return X.to_global()

    return {"posv": posv, "gesv": gesv}


def routes(n, dtype, schedule):
    from slate_tpu.drivers.chol import _solve_trsm_route
    from slate_tpu.ops import chol_kernels, lu_kernels

    return (
        f"potrf={chol_kernels.resolve_schedule(n, dtype, schedule)} "
        f"getrf={lu_kernels.resolve_lu_schedule(n, n, dtype, schedule)} "
        f"trsm={_solve_trsm_route(n, dtype, schedule)}"
    )


def phase_a(jax, jnp, np, st):
    n, nrhs, nb = SIZES["a"], 16, 512
    dt = jnp.float64
    say(f"(a) f64 n={n} nrhs={nrhs} schedule=auto -> {routes(n, dt, 'auto')}")
    B = st.matgen.generate("rand", n, nrhs, nb, dtype=dt,
                           seed=SEED + 1).to_global()
    fns = solvers(st, nb, {st.Option.Schedule: "auto"})
    for name, A in (("posv", spd(st, jnp, n, nb, dt, SEED)),
                    ("gesv", general(st, jnp, n, nb, dt, SEED + 2))):
        X, _, _, _ = timed_solve(jax, f"(a) {name} f64 {A.shape}",
                                 fns[name], (A, B))
        err = backward_error(A, X, B)
        say(f"(a) {name} backward_error={err}")
        check(st.testing.checks.passed(err, np.float64),
              f"(a) {name} backward error {err} above 3 eps")


def lu_residual(st, np, A, nb, sched):
    """||P A - L U|| / (||A|| n) of the schedule's getrf: tells a bad
    factor from a bad solve when a gesv misses its bound."""
    LU, piv, _info = st.getrf(st.Matrix.from_global(A, nb),
                              {st.Option.Schedule: sched})
    G = np.asarray(LU.to_global(), np.float64)
    L = np.tril(G, -1) + np.eye(G.shape[0])
    Ah = np.asarray(A, np.float64)
    PA = Ah[np.asarray(piv.perm)[: Ah.shape[0]]]
    return (np.abs(PA - L @ np.triu(G)).sum(axis=0).max()
            / (np.abs(Ah).sum(axis=0).max() * Ah.shape[0]))


def phase_b(jax, jnp, np, st):
    from slate_tpu.aux import metrics

    n, nrhs, nb = SIZES["b"], 16, 512
    dt = jnp.float32
    B = st.matgen.generate("rand", n, nrhs, nb, dtype=dt,
                           seed=SEED + 4).to_global()
    mats = {"posv": spd(st, jnp, n, nb, dt, SEED + 3),
            "gesv": general(st, jnp, n, nb, dt, SEED + 5)}
    for sched in ("auto", "pallas"):
        say(f"(b) f32 n={n} nrhs={nrhs} schedule={sched} -> "
            f"{routes(n, dt, sched)}")
        fns = solvers(st, nb, {st.Option.Schedule: sched})
        for name, A in mats.items():
            with metrics.deltas() as d:
                X, text, _, _ = timed_solve(
                    jax, f"(b) {name} f32 {sched}", fns[name], (A, B)
                )
            kernels = text.count("tpu_custom_call")
            err = backward_error(A, X, B)
            say(f"(b) {name} {sched}: backward_error={err} "
                f"mosaic_calls={kernels} "
                f"reference_twins={d.get('pallas.reference')}")
            if name == "gesv" and not st.testing.checks.passed(
                err, np.float32
            ):
                for s in (sched, "recursive"):
                    say(f"(b) gesv: {s} factor residual "
                        f"{lu_residual(st, np, A, nb, s)}")
            check(st.testing.checks.passed(err, np.float32),
                  f"(b) {name} {sched} backward error {err} above 3 eps")
            if sched == "pallas":
                check(kernels > 0,
                      f"(b) {name} pallas program holds no Mosaic kernel")
                check(d.get("pallas.reference") == 0,
                      f"(b) {name} pallas fell back to a jnp twin")
    # st.norm: (512, 512) f32 tiles are eligible for tile_norms_pallas
    G = mats["gesv"]

    def max_norm(G):
        return st.norm(st.Norm.Max, st.Matrix.from_global(G, nb))

    got, text, _, _ = timed_solve(jax, "(b) norm max f32", max_norm, (G,))
    ref = float(np.abs(np.asarray(G)).max())
    say(f"(b) norm max={float(got)} numpy={ref} "
        f"mosaic_calls={text.count('tpu_custom_call')}")
    check(float(got) == ref, "(b) st.norm disagrees with numpy")
    check("tpu_custom_call" in text, "(b) st.norm ran no Mosaic kernel")


def phase_c(jax, jnp, np, st):
    from slate_tpu import serve
    from slate_tpu.aux import metrics
    from slate_tpu.serve import buckets as bk

    rng = np.random.default_rng(SEED)
    dt = np.float64
    # (routine, n, nrhs): gesv on the 1024 and posv on the 2048 bucket
    # lattice point, nrhs 1-16 (two nrhs buckets each); the first four
    # gesv requests reuse one A
    n0, n1, n2, n3 = SIZES["serve"]
    plan = ([("gesv", n0, r) for r in (1, 4, 9, 16)]
            + [("gesv", n1, r) for r in (2, 7, 12, 16)]
            + [("posv", n2, r) for r in (1, 5, 8, 12)]
            + [("posv", n3, r) for r in (3, 10, 14, 16)])
    def general_np(n):
        return rng.standard_normal((n, n))

    def spd_np(n):
        G = rng.random((n, n))
        return (G + G.T) / 2 + n * np.eye(n)

    # served data is made on the host, where requests come from
    shared = general_np(n0)
    reqs = []
    for i, (routine, n, nrhs) in enumerate(plan):
        if i < 4:
            A = shared
        else:
            A = general_np(n) if routine == "gesv" else spd_np(n)
        reqs.append((routine, A, rng.standard_normal((n, nrhs))))

    svc = serve.configure()
    keys = {
        bk.bucket_for(routine, A.shape[0], A.shape[0], B.shape[1], dt,
                      floor=svc.dim_floor, nrhs_floor=svc.nrhs_floor,
                      schedule=svc.schedule)
        for routine, A, B in reqs
    }
    for key in keys:
        svc.cache.ensure_manifest(key, (1,))
    say(f"(c) buckets: {sorted(k.label for k in keys)}")
    t0 = time.perf_counter()
    compiled = serve.warmup()
    say(f"(c) warmup compiled={compiled} seconds={time.perf_counter() - t0}")
    worst = 0.0
    with metrics.deltas() as d:
        t0 = time.perf_counter()
        outs = [getattr(serve, routine)(A, B) for routine, A, B in reqs]
        wall = time.perf_counter() - t0
    for (routine, A, B), X in zip(reqs, outs):
        err = backward_error(A, X, B)
        ref = np.linalg.solve(A, B)
        diff = float(np.abs(X - ref).max() / np.abs(ref).max())
        worst = max(worst, err)
        check(st.testing.checks.passed(err, dt),
              f"(c) {routine} n={A.shape[0]} backward error {err}")
        check(diff <= 1e-9,
              f"(c) {routine} n={A.shape[0]} differs from numpy by {diff}")
    compiles = d.get("jit.compilations")
    say(f"(c) {len(reqs)} requests wall_s={wall} worst_backward_error="
        f"{worst} window_compilations={compiles}")
    check(compiles == 0, f"(c) served window compiled {compiles} times")
    serve.shutdown()


def grid_matrices(st, jnp, np, n, nb, grid, seed):
    """posv/gesv operands as matgen matrices on the grid, kept in their
    sharded tile storage (a global n=16384 f64 array and its copies do
    not fit beside each other on one chip), with host copies of the
    full matrices for the residual check.  posv's Hermitian view reads
    the lower triangle of rand + n I, which is diagonally dominant,
    hence SPD."""
    import jax

    from slate_tpu.parallel.layout import tiles_to_global

    dt = jnp.float64
    G = st.matgen.generate("rand", n, n, nb, dtype=dt, grid=grid, seed=seed)
    lay = G.layout
    diag = ((lay.global_rows_np[:, None, :, None]
             == lay.global_cols_np[None, :, None, :])
            & lay.row_mask_np[:, None, :, None]
            & lay.col_mask_np[None, :, None, :])
    S = st.HermitianMatrix(
        G.data + n * jax.device_put(diag, grid.tile_sharding()), lay,
        grid=grid, uplo=st.Uplo.Lower,
    )
    A = st.matgen.generate("randn", n, n, nb, dtype=dt, grid=grid,
                           seed=seed + 1)
    B = st.matgen.generate("rand", n, 16, nb, dtype=dt, grid=grid,
                           seed=seed + 2)

    def host(M):
        return tiles_to_global(np.asarray(M.data), M.layout)

    Sh = host(S)
    return {
        "posv": (S, np.tril(Sh) + np.tril(Sh, -1).T),
        "gesv": (A, host(A)),
    }, B, host(B)


def phase_four_chips(jax, jnp, np, st):
    """posv/gesv in f64 on a 2x2 grid under RequireSpmd, the drivers
    called eagerly on grid-resident matrices as a user would: at
    n=16384 (2 GiB per operand, 512 MiB per chip) against the tester
    bound, and at n=2048 against the same solve on one chip — the
    single-chip route cannot hold n=16384 (its f64 program needs
    29.3 GiB of the v5e's 15.75 GiB, by the chip compiler)."""
    from slate_tpu.internal import fallbacks

    nb = 512
    grid = st.ProcessGrid.from_devices(jax.devices()[:4], p=2, q=2)
    one_chip = solvers(st, nb, {})
    fallbacks.reset()
    for n in (SIZES["four"], SIZES["twin"]):
        mats, Bd, Bh = grid_matrices(st, jnp, np, n, nb, grid, SEED + 20)
        for name, (Ad, Ah) in mats.items():
            shards = Ad.data.addressable_shards
            devs = {s.device.id for s in shards}
            sizes = {s.data.size for s in shards}
            say(f"(4) {name} n={n} tile shards on devices {sorted(devs)} "
                f"sizes {sorted(sizes)}")
            check(len(devs) == 4 and len(sizes) == 1,
                  f"(4) {name} tiles not spread evenly over 4 chips")
            t0 = time.perf_counter()
            out = getattr(st, name)(Ad, Bd, {st.Option.RequireSpmd: True})
            X4 = np.asarray(out[0].to_global())
            say(f"(4) {name} f64 n={n} grid=2x2 first_call_s="
                f"{time.perf_counter() - t0} (compile included)")
            err = backward_error(Ah, X4, Bh)
            say(f"(4) {name} n={n} grid=2x2 backward_error={err}")
            check(st.testing.checks.passed(err, np.float64),
                  f"(4) {name} 2x2 backward error {err} above 3 eps")
            if n == SIZES["four"]:
                continue
            X1, _, _, _ = timed_solve(
                jax, f"(4) {name} f64 n={n} grid=1x1", one_chip[name],
                (jnp.asarray(Ah), jnp.asarray(Bh)),
            )
            err = backward_error(Ah, X1, Bh)
            diff = float(np.abs(X4 - np.asarray(X1)).max()
                         / np.abs(np.asarray(X1)).max())
            say(f"(4) {name} n={n} grid=1x1 backward_error={err}; "
                f"2x2 vs 1x1 max relative difference={diff}")
            check(st.testing.checks.passed(err, np.float64),
                  f"(4) {name} 1x1 backward error {err} above 3 eps")
        del mats, Bd
    check(not fallbacks.counters(),
          f"(4) gather fallbacks: {fallbacks.counters()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    import slate_tpu as st
    import slate_tpu.testing.checks  # noqa: F401  (st.testing.checks)
    from slate_tpu.aux import metrics

    metrics.on()

    say(f"device {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}")
    phases = ([phase_four_chips] if args.chips == 4
              else [phase_a, phase_b, phase_c])
    try:
        for phase in phases:
            t0 = time.perf_counter()
            phase(jax, jnp, np, st)
            say(f"{phase.__name__} ok in {time.perf_counter() - t0} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
