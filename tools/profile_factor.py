"""Factorization throughput breakdown on the chip (round-4 ceiling
analysis): measures the f64 gemm denominator at n=8192, the three
factorization totals, their PANEL-ONLY costs, and exact-shape
trailing-gemm proxies, so the gap can be attributed between
the factorization rates and the chip's own gemm rate.

Thin wrapper over the shared measurement layer: best-of timing with the
host-readback barrier lives in slate_tpu.aux.metrics.measure_best (the
bench.py methodology); every section lands in the metrics registry, so
SLATE_TPU_METRICS=/path/out.jsonl keeps the full event stream.

Run: python tools/profile_factor.py [--n 8192]
"""

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(_ROOT, ".jax_cache")
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()
    n = args.n

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from slate_tpu.aux import metrics

    metrics.on()

    print(f"device: {jax.devices()[0]}, n={n}", flush=True)
    key = jax.random.PRNGKey(0)
    res = {}

    def put(name, seconds, flops):
        gf = flops / seconds / 1e9
        res[name] = {"seconds": round(seconds, 4), "gflops": round(gf, 1)}
        metrics.gauge(f"profile_factor.{name}.gflops", gf)
        print(f"{name:32s} {seconds:8.3f}s  {gf:9.1f} GF/s", flush=True)

    nb = 512 if n % 512 == 0 and n > 512 else max(n // 4, 1)
    pert = lambda ar, t: (ar[0] + t * 1e-13,) + tuple(ar[1:])  # noqa: E731

    def best(name, fn, fn_args):
        return metrics.measure_best(
            fn, fn_args, trials=args.trials, perturb=pert,
            name=f"profile_factor.{name}",
        )

    # -- denominator: f64 gemm at the same n ---------------------------
    A = jax.random.normal(key, (n, n), jnp.float64)
    B = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float64)
    put("dgemm", best("dgemm", lambda a, b: a @ b, (A, B)), 2.0 * n**3)

    # -- totals --------------------------------------------------------
    from slate_tpu.ops.chol_kernels import blocked_potrf, chol_unblocked
    from slate_tpu.ops.lu_fast import blocked_getrf_fast, _lu_panel_strips
    from slate_tpu.ops.qr_fast import geqrf_fast, _qr_panel_strips

    S = A @ A.T + n * jnp.eye(n, dtype=jnp.float64)
    put("dpotrf_total",
        best("dpotrf", lambda g: blocked_potrf(g, nb), (S,)), n**3 / 3.0)
    put("dgetrf_total",
        best("dgetrf", lambda g: blocked_getrf_fast(g, nb), (A,)),
        2.0 * n**3 / 3.0)
    put("dgeqrf_total",
        best("dgeqrf", lambda g: geqrf_fast(g, nb), (A,)), 4.0 * n**3 / 3.0)

    # -- panel-only costs (the sequential micro-loops) ------------------
    P = jax.random.normal(jax.random.PRNGKey(2), (n, nb), jnp.float64)
    nt = n // nb
    s = best("qr_panel", lambda p: _qr_panel_strips(p, 32), (P,))
    put("qr_panel(mxnb) x nt", s * nt, nt * (2.0 * n * nb * nb))
    s = best("lu_panel", lambda p: _lu_panel_strips(p, p.shape[0], 32), (P,))
    put("lu_panel(mxnb) x nt", s * nt, nt * (n * nb * nb))

    D = S[:nb, :nb]
    s = best("chol_diag", lambda d: chol_unblocked(d, 16), (D,))
    put("chol_diag(nbxnb) x nt", s * nt, nt * (nb**3 / 3.0))

    # -- trailing-gemm proxy: the exact update shapes, chained ----------
    # right-looking trailing updates ~ sum_k (n - k nb) x nb @ nb x (n - k nb)
    def trailing_chain(a):
        out = jnp.zeros((), jnp.float64)
        acc = a
        for k in range(nt - 1):
            h = n - (k + 1) * nb
            L = acc[:h, :nb]
            acc = acc.at[:h, :h].add(-L @ jnp.swapaxes(L, 0, 1) * 1e-20)
            out = out + acc[0, 0]
        return out

    s = best("trailing_syrk_chain", trailing_chain, (A,))
    fl = sum(2.0 * (n - (k + 1) * nb) ** 2 * nb for k in range(nt - 1))
    put("trailing_syrk_chain", s, fl)

    if os.environ.get("SLATE_TPU_METRICS"):
        metrics.dump()
    print(json.dumps(res))


if __name__ == "__main__":
    main()
