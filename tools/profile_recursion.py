"""On-chip sweep of the factorization recursion shape (verdict r4 #5).

The r4 ceiling analysis: the rank-512 trailing update runs at 481 GF/s
(25% of square-gemm), so fattening the coarse updates is the remaining
schedule lever.  This sweeps (nb, coarse_panels) for the native dpotrf
and dgetrf at n=8192 and prints GF/s per configuration — either the
better recipe or the measured refutation.

Run: python tools/profile_recursion.py [--n 8192]
"""

import argparse
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
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--skip-lu", action="store_true")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from slate_tpu.ops.chol_kernels import blocked_potrf
    from slate_tpu.ops.lu_fast import blocked_getrf_fast

    n = args.n
    print(f"device: {jax.devices()[0]}  n={n}", flush=True)
    rng = np.random.default_rng(0)
    A0 = rng.standard_normal((n, n))
    S = jnp.asarray(A0 @ A0.T / n + 2 * np.eye(n))
    M = jnp.asarray(A0)

    def timed(fn, x, tries=2):
        """Host-readback barrier: one element read back per call."""

        def run(arg):
            out = fn(arg)
            return float(np.asarray(jax.tree.leaves(out)[0].ravel()[-1]))

        last = None
        for attempt in range(4):
            try:
                run(x)
                break
            except Exception as e:
                last = e
                print(f"  [retry {attempt+1}: {type(e).__name__}]", flush=True)
                time.sleep(10.0 * (attempt + 1))
        else:
            raise last
        best = 1e9
        for t in range(tries):
            t0 = time.time()
            run(x + (t + 1) * 1e-13)
            best = min(best, time.time() - t0)
        return best

    print("--- dpotrf sweep ---", flush=True)
    for nb, cp in [(512, 4), (512, 2), (1024, 4), (1024, 2), (2048, 4),
                   (512, 8), (256, 4)]:
        fn = jax.jit(lambda x, nb=nb, cp=cp: blocked_potrf(x, nb, cp))
        try:
            dt = timed(fn, S)
            gf = (n**3 / 3.0) / dt / 1e9
            print(f"dpotrf nb={nb:5d} coarse={cp}: {dt:6.3f}s {gf:7.1f} GF/s",
                  flush=True)
        except Exception as e:
            print(f"dpotrf nb={nb} coarse={cp}: FAIL {type(e).__name__}",
                  flush=True)

    if not args.skip_lu:
        print("--- dgetrf sweep ---", flush=True)
        for nb, cp in [(512, 4), (512, 2), (1024, 4), (1024, 2)]:
            fn = jax.jit(
                lambda x, nb=nb, cp=cp: blocked_getrf_fast(
                    x, nb, coarse_panels=cp
                )[0]
            )
            try:
                dt = timed(fn, M)
                gf = (2.0 * n**3 / 3.0) / dt / 1e9
                print(f"dgetrf nb={nb:5d} coarse={cp}: {dt:6.3f}s "
                      f"{gf:7.1f} GF/s", flush=True)
            except Exception as e:
                print(f"dgetrf nb={nb} coarse={cp}: FAIL {type(e).__name__}",
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
