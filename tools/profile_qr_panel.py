"""On-chip cost model of the geqrf panel (ops/qr_fast._qr_panel_strips).

Round-4 finding: panels are 1.9 s of dgeqrf's 2.59 s at n=8192.  This
tool separates the candidate cost terms so the round-5 panel redesign
targets the real one:

* latency term: per-column fixed dispatch cost  -> time vs m flat
* bandwidth term: per-column strip-tail traffic -> time ~ m * ib

Sweeps m x ib for one (m, 512) panel, plus the small-factorization
floor (vendor vs native chol at 256/512 — the CholQR2 panel
alternative's binding cost).

Run: python tools/profile_qr_panel.py
"""

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
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from slate_tpu.ops.qr_fast import _qr_panel_strips

    print(f"device: {jax.devices()[0]}", flush=True)
    rng = np.random.default_rng(0)

    def timed(fn, *a, tries=3):
        """Steady-state wall time with HOST READBACK of one element of
        a device-side scalar reduce as the barrier."""

        def run(args):
            out = fn(*args)
            s = jax.tree.leaves(out)[0].ravel()[-1]
            return float(np.asarray(s))

        last = None
        for attempt in range(4):
            try:
                run(a)
                break
            except Exception as e:
                last = e
                print(f"  [retry {attempt+1}: {type(e).__name__}]", flush=True)
                time.sleep(10.0 * (attempt + 1))
        else:
            raise last
        best = 1e9
        for t in range(tries):
            a2 = tuple(x + (t + 1) * 1e-13 for x in a)
            t0 = time.time()
            run(a2)
            best = min(best, time.time() - t0)
        return best

    w = 512
    for m in (1024, 2048, 8192):
        row = []
        for ib in (16, 32, 64, 128):
            P = jnp.asarray(rng.standard_normal((m, w)))
            fn = jax.jit(lambda P, ib=ib: _qr_panel_strips(P, ib)[0])
            dt = timed(fn, P)
            row.append(f"ib={ib}: {dt*1e3:7.1f}ms")
        print(f"panel m={m:5d} w={w}: " + "  ".join(row), flush=True)

    # vmapped chunk QR (the TSQR level-0 candidate): 8 x (1024, 512)
    P8 = jnp.asarray(rng.standard_normal((8, 1024, w)))
    fn8 = jax.jit(
        lambda P: jax.vmap(lambda x: _qr_panel_strips(x, 32)[0])(P)
    )
    dt = timed(fn8, P8)
    print(f"vmapped 8x(1024,512) chunk QR ib=32: {dt*1e3:7.1f}ms", flush=True)

    # small-factorization floor for CholQR-style panels
    from slate_tpu.ops.chol_kernels import chol_unblocked, cholesky

    for nb in (256, 512):
        G = jnp.asarray(rng.standard_normal((nb, nb)))
        S = G @ G.T + nb * jnp.eye(nb, dtype=jnp.float64)
        ent = [
            ("vendor_chol", jax.jit(lambda d: jax.lax.linalg.cholesky(d))),
            ("unblocked_ib32", jax.jit(lambda d: chol_unblocked(d, 32))),
            ("blocked_recipe", jax.jit(lambda d: cholesky(d, max(nb // 4, 64)))),
        ]
        out = []
        for name, fn in ent:
            try:
                dt = timed(fn, S)
                out.append(f"{name}: {dt*1e3:6.1f}ms")
            except Exception as e:
                out.append(f"{name}: FAIL({type(e).__name__})")
        print(f"chol n={nb}: " + "  ".join(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
