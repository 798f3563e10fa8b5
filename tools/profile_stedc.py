"""On-chip phase/level profiler for the native stedc (ops/stedc.py).

Round-4 finding: stedc+unmtr_hb2st went 7.3 s (n=2048) -> 324 s
(n=4096) on the chip — a toolchain interaction, not algorithmic
scaling.  This tool isolates it: it re-runs the bottom-up Cuppen tree
with ONE JIT PER LEVEL (timing each level at steady state), and for
the largest levels times each merge phase (setup/sort, deflation
while_loop, secular roots, Lowner assembly, back-rotation gemm)
separately.

Thin wrapper over the shared measurement layer: the steady-state
host-readback-barrier timing lives in
slate_tpu.aux.metrics.measure_steady; every level/phase lands in the
metrics registry, so SLATE_TPU_METRICS=/path/out.jsonl keeps the full
event stream.

Run: python tools/profile_stedc.py --n 2048 4096
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

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, nargs="+", default=[2048, 4096])
    ap.add_argument("--phases-from", type=int, default=1024,
                    help="per-phase timing for levels with n2 >= this")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from slate_tpu.aux import metrics
    from slate_tpu.ops import stedc as M

    metrics.on()

    print(f"device: {jax.devices()[0]}", flush=True)
    rng = np.random.default_rng(0)
    out = {}

    def timed(name, fn, *a):
        return metrics.measure_steady(fn, *a, name=f"profile_stedc.{name}")

    for n in args.n:
        print(f"\n=== n={n} ===", flush=True)
        d = jnp.asarray(rng.standard_normal(n))
        e = jnp.asarray(rng.standard_normal(n - 1))
        dt = d.dtype
        eps = float(jnp.finfo(dt).eps)
        if jax.default_backend() != "cpu":
            eps *= 32.0

        # replicate stedc()'s normalize + pad + leaves
        scale0 = jnp.maximum(jnp.abs(d).max(), jnp.abs(e).max())
        scale = jnp.where(scale0 > 0, scale0, 1.0)
        d = d / scale
        e = e / scale
        N = 1 << int(np.ceil(np.log2(n)))
        bound = jnp.abs(d).max() + 2 * jnp.abs(e).max() + 1.0
        dpad = jnp.concatenate([d, bound * (2.0 + jnp.arange(N - n, dtype=dt))])
        epad = jnp.concatenate([e, jnp.zeros((N - 1 - e.shape[0],), dt)])
        eabs = jnp.abs(epad)
        left = jnp.concatenate([jnp.zeros((1,), dt), eabs])
        right = jnp.concatenate([eabs, jnp.zeros((1,), dt)])
        w = (dpad - left - right).reshape(N, 1)
        QT = jnp.ones((N, 1, 1), dt)

        levels = {}
        merge_b = jax.jit(jax.vmap(M._merge, in_axes=(0, 0, 0, 0, 0, None)),
                          static_argnums=(5,))
        s = 1
        while s < N:
            nm = N // (2 * s)
            w_pairs = w.reshape(nm, 2, s)
            Q_pairs = QT.reshape(nm, 2, s, s)
            e_r = epad[s - 1 :: 2 * s][:nm]
            tsec, (w, QT) = timed(
                f"level_{2 * s}",
                lambda a, b, c, dd, ee: merge_b(a, b, c, dd, ee, eps),
                w_pairs[:, 0], Q_pairs[:, 0], w_pairs[:, 1], Q_pairs[:, 1],
                e_r,
            )
            n2 = 2 * s
            levels[n2] = round(tsec, 3)
            print(f"level n2={n2:5d} x{nm:4d} merges: {tsec:8.3f}s",
                  flush=True)

            # per-phase timing on this level's inputs
            if n2 >= args.phases_from:
                setup = jax.jit(
                    jax.vmap(M._merge_setup, in_axes=(0, 0, 0, 0, 0, None)),
                    static_argnums=(5,))
                t_set, (D, z, QTm, rho, tol) = timed(
                    f"setup_{n2}",
                    lambda a, b, c, dd, ee: setup(a, b, c, dd, ee, eps),
                    w_pairs[:, 0], Q_pairs[:, 0], w_pairs[:, 1],
                    Q_pairs[:, 1], e_r)
                defl = jax.jit(jax.vmap(M._deflate))
                t_def, (D2, z2, QT2, nd) = timed(
                    f"deflate_{n2}", defl, D, z, QTm, rho, tol)
                secu = jax.jit(jax.vmap(M._solve_secular))
                t_sec, (ks, sg, xx, lam) = timed(
                    f"secular_{n2}", secu, D2, z2, rho, nd, tol)
                asse = jax.jit(jax.vmap(M._assemble_u))
                t_ass, Ur = timed(
                    f"assemble_{n2}", asse, D2, z2, nd, ks, sg, xx)

                @jax.jit
                def rot(Ur, QT2, lam):
                    Qo = jnp.einsum("mij,mjk->mik", Ur, QT2,
                                    precision=jax.lax.Precision.HIGHEST)
                    o2 = jnp.argsort(lam, axis=1)
                    return jnp.take_along_axis(
                        Qo, o2[:, :, None], axis=1)

                t_rot, _ = timed(f"rotate_{n2}", rot, Ur, QT2, lam)
                ndefl_frac = float(nd.mean())
                print(f"  phases: setup {t_set:.3f}s  deflate {t_def:.3f}s"
                      f"  secular {t_sec:.3f}s  assemble {t_ass:.3f}s"
                      f"  rotate+sort {t_rot:.3f}s"
                      f"  (nondefl {ndefl_frac:.2f})", flush=True)
                levels[f"{n2}_phases"] = {
                    "setup": round(t_set, 3), "deflate": round(t_def, 3),
                    "secular": round(t_sec, 3), "assemble": round(t_ass, 3),
                    "rotate_sort": round(t_rot, 3),
                }
            s *= 2

        # end-to-end single-jit stedc for the headline number
        t_e2e, (wfull, Qfull) = timed(
            "end_to_end", jax.jit(M.stedc),
            jnp.asarray(rng.standard_normal(n)),
            jnp.asarray(rng.standard_normal(n - 1)))
        print(f"stedc end-to-end (one jit): {t_e2e:.2f}s", flush=True)
        levels["end_to_end"] = round(t_e2e, 3)
        out[n] = levels

    if os.environ.get("SLATE_TPU_METRICS"):
        metrics.dump()
    print(json.dumps({"profile_stedc": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
