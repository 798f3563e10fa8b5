"""Micro-compare of diagonal-block factor kernels on the chip: the
native ib-strip chol_unblocked vs the vendor lowering, at panel tile
sizes — the candidate lever for dpotrf's panel-bound ceiling."""

import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_ROOT, ".jax_cache")
)

import numpy as np


def main():
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from slate_tpu.ops.chol_kernels import chol_unblocked

    print(f"device: {jax.devices()[0]}", flush=True)
    key = jax.random.PRNGKey(0)

    for nb in (256, 512):
        G = jax.random.normal(key, (nb, nb), jnp.float64)
        S = G @ G.T + nb * jnp.eye(nb, dtype=jnp.float64)

        for name, fn in (
            ("chol_unblocked_ib16", lambda d: chol_unblocked(d, 16)),
            ("chol_unblocked_ib32", lambda d: chol_unblocked(d, 32)),
            ("vendor_cholesky", lambda d: jax.lax.linalg.cholesky(d)),
        ):
            sj = jax.jit(lambda d, fn=fn: fn(d).ravel()[-1] + fn(d).ravel()[0])
            try:
                float(np.asarray(sj(S)))
            except Exception as e:
                print(f"nb={nb} {name}: FAILED {type(e).__name__}", flush=True)
                continue
            best = 1e9
            for t in range(3):
                St = S + (t + 1) * 1e-13
                t0 = time.time()
                float(np.asarray(sj(St)))
                best = min(best, time.time() - t0)
            gf = (nb**3 / 3.0) / best / 1e9
            print(f"nb={nb} {name:22s} {best*1e3:8.2f} ms  {gf:7.1f} GF/s",
                  flush=True)


if __name__ == "__main__":
    main()
