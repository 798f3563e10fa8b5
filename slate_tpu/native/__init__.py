"""On-demand compiled native (host CPU) kernels.

The reference runs its stage-2 eigensolver kernels as CPU-threaded
native code over a gathered band (reference: src/hb2st.cc:44-187,
src/heev.cc:135); this package holds the framework's equivalents,
compiled from C at first use with the system compiler and loaded via
ctypes.  Every entry degrades gracefully: if no compiler is available
the callers fall back to the jittable on-device implementations.
"""

from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
import tempfile
from typing import Optional

_DIR = os.path.dirname(os.path.abspath(__file__))
_lib = None
_lib_tried = False


def _build_dir() -> str:
    d = os.environ.get("SLATE_TPU_NATIVE_CACHE") or os.path.join(
        _DIR, "_build"
    )
    os.makedirs(d, exist_ok=True)
    return d


def load() -> Optional[ctypes.CDLL]:
    """Compile (once) and load the native kernel library, or None."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    if os.environ.get("SLATE_TPU_NO_NATIVE"):
        return None
    cc = shutil.which("cc") or shutil.which("gcc") or shutil.which("clang")
    if cc is None:
        return None
    src = os.path.join(_DIR, "hb2st.c")
    # key the cache by source + compiler + flags + microarchitecture:
    # -march=native binaries must not be shared across hosts (NFS homes)
    # and must rebuild when the source or toolchain changes
    import hashlib
    import platform

    flags = ["-O3", "-march=native", "-fPIC", "-shared"]
    with open(src, "rb") as f:
        key = hashlib.sha256(
            f.read()
            + cc.encode()
            + " ".join(flags).encode()
            + platform.machine().encode()
            + platform.node().encode()
        ).hexdigest()[:16]
    out = os.path.join(_build_dir(), f"libslate_tpu_native_{key}.so")
    try:
        if not os.path.exists(out):
            fd, tmp = tempfile.mkstemp(
                suffix=".so", dir=os.path.dirname(out)
            )
            os.close(fd)
            cmd = [cc, *flags, src, "-lm", "-o", tmp]
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                os.unlink(tmp)
                return None
            os.replace(tmp, out)
        lib = ctypes.CDLL(out)
        lib.slate_hb2st_d.restype = ctypes.c_int
        lib.slate_hb2st_d.argtypes = [
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double),
            ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int64,
        ]
        lib.slate_hb2st_range_d.restype = ctypes.c_int
        lib.slate_hb2st_range_d.argtypes = (
            lib.slate_hb2st_d.argtypes + [ctypes.c_int64, ctypes.c_int64]
        )
        _lib = lib
    except Exception:
        _lib = None
    return _lib


def hb2st_available() -> bool:
    return load() is not None


def hb2st_host(W, n: int, b: int):
    """Run the native bulge chase on diagonal-major band storage W
    ((2b+1, n_pad) numpy f64).  Returns (d, e, VS, TAUS) as numpy
    arrays with the exact shapes/semantics of ops.bulge.hb2st's real
    path.  Raises RuntimeError if the native library is unavailable.
    """
    import numpy as np

    lib = load()
    if lib is None:
        raise RuntimeError("native hb2st unavailable")
    W = np.asarray(W, dtype=np.float64)
    n_pad = W.shape[1]
    # column-major band (contiguous columns) for the C kernel
    Wt = np.ascontiguousarray(W.T)
    n_sweeps = max(n - 2, 1)
    jmax1 = (n - 3) // b + 2 if n > 2 else 1  # Jmax + 1
    VS = np.zeros((n_sweeps, jmax1, b), np.float64)
    TAUS = np.zeros((n_sweeps, jmax1), np.float64)
    if n > 2 and b >= 2:
        rc = lib.slate_hb2st_d(
            Wt.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n, n_pad, b,
            VS.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            TAUS.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            n_sweeps, jmax1,
        )
        if rc != 0:
            raise RuntimeError(f"slate_hb2st_d failed rc={rc}")
    d = Wt[:n, 0].copy()
    e = Wt[: n - 1, 1].copy()
    return d, e, VS, TAUS


def hb2st_host_device(W, n: int, b: int, chunk_sweeps: int = 1024):
    """Chunked chase with the reflector uploads OVERLAPPED: after each
    sweep range completes, its VS/TAUS rows go to an async
    jax.device_put while the next range chases (the transfer drains
    during the GIL-releasing ctypes call).  The upload is the larger
    half of stage 2 at n=8192 (537 MB against ~24 s of chase); sequential ranged calls over the persistent band are
    exactly the full chase.  Returns (d, e, VS_dev, TAUS_dev) with the
    reflectors already device-resident."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lib = load()
    if lib is None:
        raise RuntimeError("native hb2st unavailable")
    W = np.asarray(W, dtype=np.float64)
    n_pad = W.shape[1]
    Wt = np.ascontiguousarray(W.T)
    n_sweeps = max(n - 2, 1)
    jmax1 = (n - 3) // b + 2 if n > 2 else 1
    VS = np.zeros((n_sweeps, jmax1, b), np.float64)
    TAUS = np.zeros((n_sweeps, jmax1), np.float64)
    from ..aux import metrics

    vs_parts, tau_parts = [], []
    if n > 2 and b >= 2:
        for s0 in range(0, n_sweeps, chunk_sweeps):
            s1 = min(n_sweeps, s0 + chunk_sweeps)
            rc = lib.slate_hb2st_range_d(
                Wt.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                n, n_pad, b,
                VS.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                TAUS.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                n_sweeps, jmax1, s0, s1,
            )
            if rc != 0:
                raise RuntimeError(f"slate_hb2st_range_d failed rc={rc}")
            # OVERLAP CONTRACT (pairs with the VS memcpy in hb2st.c's
            # chase loop): slate_hb2st_range_d writes reflector rows only
            # for sweeps s in [s_begin, s_end), so rows [s0, s1) are
            # final here and the async upload below can drain while the
            # NEXT range computes rows >= s1.  Guard the contract on the
            # cheap TAUS proxy: any nonzero tau at a sweep >= s1 means
            # the C kernel wrote outside its range and the uploaded VS
            # rows may be racing the chase.
            assert s1 >= n_sweeps or not TAUS[s1:].any(), (
                "hb2st range contract violated: tau written beyond "
                f"sweep {s1}"
            )
            vs_parts.append(jax.device_put(VS[s0:s1]))
            tau_parts.append(jax.device_put(TAUS[s0:s1]))
            metrics.inc(
                "transfer.h2d_bytes", VS[s0:s1].nbytes + TAUS[s0:s1].nbytes
            )
    if not vs_parts:
        VSd, TAUSd = jnp.asarray(VS), jnp.asarray(TAUS)
    elif len(vs_parts) == 1:
        VSd, TAUSd = vs_parts[0], tau_parts[0]
    else:
        VSd = jnp.concatenate(vs_parts, axis=0)
        TAUSd = jnp.concatenate(tau_parts, axis=0)
    d = Wt[:n, 0].copy()
    e = Wt[: n - 1, 1].copy()
    return d, e, VSd, TAUSd
