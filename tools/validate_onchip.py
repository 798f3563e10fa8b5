"""On-chip correctness validation of the large-n native kernel paths.

The pytest suite runs on the virtual CPU mesh and caps n <= 384, so the
n >= 1024 dispatch gates (ops/chol_kernels.py, ops/lu_fast.py,
ops/qr_fast.py, the stedc-backed heev vectors path) never execute there
on the real device.  This script residual-checks each of them ON THE
CHIP at production sizes and prints one summary line per check
(one line per check).

Run: python tools/validate_onchip.py [--quick]
"""

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_ROOT, ".jax_cache")
)

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sizes")
    ap.add_argument("--heev-only", action="store_true")
    ap.add_argument("--n-eig", type=int, default=0,
                    help="override heev size (default 2048, quick: 1024)")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"device: {dev}", flush=True)
    rng = np.random.default_rng(42)
    eps = float(np.finfo(np.float64).eps)
    results = {}

    def report(name, err, bound, secs):
        ok = bool(err <= bound)
        results[name] = {"err": float(err), "bound": float(bound),
                         "seconds": round(secs, 2), "pass": ok}
        print(f"{name:28s} err={err:9.3e} bound={bound:9.3e} "
              f"{'PASS' if ok else 'FAIL'} ({secs:.1f}s)", flush=True)
        return ok

    ok = True

    if not args.heev_only:
        # -- dpotrf: ops/chol_kernels.cholesky --------------------------
        n = 1024 if args.quick else 2048
        A0 = rng.standard_normal((n, n))
        A0 = A0 @ A0.T + n * np.eye(n)
        from slate_tpu.ops.chol_kernels import cholesky

        t0 = time.time()
        L = np.asarray(jax.block_until_ready(cholesky(jnp.asarray(A0), 512)))
        t1 = time.time()
        L = np.tril(L)
        err = np.abs(L @ L.T - A0).max() / (np.abs(A0).max() * n * eps)
        ok &= report("dpotrf_native(n=%d)" % n, err, 100, t1 - t0)

        # -- dgetrf: ops/lu_fast ----------------------------------------
        from slate_tpu.ops.lu_fast import blocked_getrf_fast

        M0 = rng.standard_normal((n, n))
        t0 = time.time()
        lu2d, perm = jax.block_until_ready(
            blocked_getrf_fast(jnp.asarray(M0), 512)
        )
        t1 = time.time()
        lu2d = np.asarray(lu2d)
        perm = np.asarray(perm)
        Lm = np.tril(lu2d, -1) + np.eye(n)
        Um = np.triu(lu2d)
        err = np.abs(Lm @ Um - M0[perm]).max() / (np.abs(M0).max() * n * eps)
        ok &= report("dgetrf_native(n=%d)" % n, err, 100, t1 - t0)

        # -- dgeqrf: ops/qr_fast ----------------------------------------
        from slate_tpu.ops.qr_fast import geqrf_fast
        from slate_tpu.ops.householder import larft, materialize_v

        t0 = time.time()
        fac, taus = jax.block_until_ready(geqrf_fast(jnp.asarray(M0), 512))
        t1 = time.time()
        # reconstruct Q^T A and compare to R (apply the panels)
        Afac = np.asarray(fac)
        R = np.triu(Afac)
        C = jnp.asarray(M0)
        nbp = 512
        for k0 in range(0, n, nbp):
            V = materialize_v(fac[:, k0:k0 + nbp], offset=k0)
            T = larft(V, taus[k0:k0 + nbp])
            W = V.conj().T @ C
            C = C - V @ (T.conj().T @ W)
        QtA = np.asarray(C)
        err = np.abs(QtA - R).max() / (np.abs(M0).max() * n * eps)
        ok &= report("dgeqrf_native(n=%d)" % n, err, 100, t1 - t0)

    # -- heev with vectors through the driver (he2hb + hb2st + stedc +
    #    back-transforms), the full flagship path ------------------------
    n_eig = args.n_eig or (1024 if args.quick else 2048)
    from slate_tpu.drivers import eig
    from slate_tpu.enums import Uplo
    from slate_tpu.matrix.matrix import HermitianMatrix

    H0 = rng.standard_normal((n_eig, n_eig))
    H0 = (H0 + H0.T) / 2
    A = HermitianMatrix.from_global(
        jnp.asarray(H0), 128, uplo=Uplo.Lower
    )

    # The product stage-split path (drivers/eig.py heev_staged): the
    # driver compiles the four stages separately at n >= 2048, with the
    # native host chaser for stage 2 when available.
    from slate_tpu import native as native_mod
    from slate_tpu.drivers.eig import heev_staged

    print(f"native hb2st: {native_mod.hb2st_available()}", flush=True)
    print("compiling heev stages...", flush=True)
    tc0 = time.time()
    heev_staged(A, vectors=True)
    print(f"heev stages compile+first run: {time.time() - tc0:.1f}s",
          flush=True)
    # perturb the input so no layer can serve a cached result
    A = A._with(data=A.data + jnp.float64(1e-14))
    H0 = H0 + 1e-14
    t0 = time.time()
    w, Zm, stage_t = heev_staged(A, vectors=True)
    t1 = time.time() - t0
    t0 = 0.0
    w = np.asarray(w)
    Zg = np.asarray(Zm.to_global())
    print(f"stage breakdown: {stage_t}", flush=True)
    results["heev_stages"] = dict(stage_t)
    err = np.abs(H0 @ Zg - Zg * w[None, :]).max() / (
        np.abs(H0).max() * n_eig * eps
    )
    orth = np.abs(Zg.T @ Zg - np.eye(n_eig)).max() / (n_eig * eps)
    ok &= report("dheev_vectors(n=%d)" % n_eig, err, 100, t1 - t0)
    ok &= report("dheev_orth(n=%d)" % n_eig, orth, 100, 0.0)
    werr = np.abs(np.sort(w) - np.linalg.eigvalsh(H0)).max() / (
        np.abs(w).max() * n_eig * eps
    )
    ok &= report("dheev_values(n=%d)" % n_eig, werr, 100, 0.0)

    print(json.dumps({"onchip_validation": results, "all_pass": bool(ok)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
