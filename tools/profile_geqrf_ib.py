"""In-situ ib sweep for geqrf_fast / lu panels at n=8192 (round-5 panel
decision; see profile_qr_panel.py for the standalone panel numbers that
refuted the TSQR and CholQR panel alternatives on this chip)."""
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
    from slate_tpu.ops.qr_fast import geqrf_fast
    print(f"device: {jax.devices()[0]}", flush=True)
    rng = np.random.default_rng(0)
    n = 8192
    M = jnp.asarray(rng.standard_normal((n, n)))
    for ib in (32, 64, 128):
        fn = jax.jit(lambda x, ib=ib: geqrf_fast(x, 512, ib)[0])
        def run(x):
            return float(np.asarray(fn(x).ravel()[-1]))
        for attempt in range(4):
            try:
                run(M); break
            except Exception as e:
                print(f" [retry {type(e).__name__}]", flush=True); time.sleep(15)
        best = 1e9
        for t in range(2):
            t0 = time.time(); run(M + (t+1)*1e-13)
            best = min(best, time.time() - t0)
        gf = 4.0*n**3/3.0/best/1e9
        print(f"dgeqrf n=8192 ib={ib}: {best:.3f}s {gf:.1f} GF/s", flush=True)

if __name__ == "__main__":
    main()
