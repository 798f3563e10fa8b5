"""Stage-3 back-transform experiments at n=4096 (unmtr_hb2st is the
post-stedc wall-clock ceiling: ~50 s of stage 3's 50.2 s).

Variant A: current (per-sweep contiguous slice over all of Z).
Variant B: column panels — outer python loop over Z column blocks,
inner fori over sweeps; if XLA keeps the panel carry VMEM-resident the
HBM traffic drops ~100x, else it matches A.
"""
import os, sys, time
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
    from jax import lax
    from slate_tpu.ops.bulge import unmtr_hb2st

    print(f"device: {jax.devices()[0]}", flush=True)
    rng = np.random.default_rng(0)
    n, b = 4096, 128
    n_sweeps = n - 2
    J1 = (n - 3) // b + 2
    VS = jnp.asarray(rng.standard_normal((n_sweeps, J1, b)) * 0.1)
    VS = VS.at[:, :, 0].set(1.0)
    TAUS = jnp.asarray(rng.standard_normal((n_sweeps, J1)) * 0.5)
    Z = jnp.asarray(rng.standard_normal((n, n)))

    def timed(fn, *a):
        def run(args):
            out = fn(*args)
            return float(np.asarray(out.ravel()[-1]))
        for attempt in range(4):
            try:
                run(a); break
            except Exception as e:
                print(f" [retry {type(e).__name__}]", flush=True)
                time.sleep(15)
        t0 = time.time()
        run((a[0], a[1], a[2] + 1e-13) if len(a) == 3 else a)
        return time.time() - t0

    fA = jax.jit(lambda VS, TAUS, Z: unmtr_hb2st(VS, TAUS, Z, n, b))
    tA = timed(fA, VS, TAUS, Z)
    print(f"variant A (full-width slices): {tA:.2f}s", flush=True)

    w = 512

    def panel_apply(VS, TAUS, Zp):
        # Zp: (n + pad, w) one column panel
        def sweep(k, Zp):
            s = n_sweeps - 1 - k
            v = VS[s]
            tau = TAUS[s]
            Zr = lax.dynamic_slice(Zp, (s + 1, 0), (J1 * b, w)).reshape(
                J1, b, w)
            wrow = jnp.einsum("jb,jbm->jm", v, Zr)
            Zr = Zr - tau[:, None, None] * v[:, :, None] * wrow[:, None, :]
            return lax.dynamic_update_slice(
                Zp, Zr.reshape(-1, w), (s + 1, 0))
        return lax.fori_loop(0, n_sweeps, sweep, Zp)

    fB = jax.jit(panel_apply)
    pad = b + J1 * b + 8
    Zp0 = jnp.pad(Z[:, :w], ((0, pad), (0, 0)))
    tB = timed(fB, VS, TAUS, Zp0)
    print(f"variant B ({w}-col panel, ONE panel): {tB:.2f}s "
          f"-> est. full: {tB * (n // w):.1f}s", flush=True)

if __name__ == "__main__":
    main()
