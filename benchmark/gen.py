"""Operands from a seed: one counter hash, evaluated on the device by the
timed runs and on the host by the reference.

Element (i, j) of stream ``s`` under seed ``seed`` is a 32-bit hash of
(seed, s, i, j), so a matrix is the same whichever device, tile layout
or process builds it, and the reference rebuilds it from the seed alone,
without the program's arrays.  Values are uniform in [-0.5, 0.5): 32
bits of the hash for float64, the top 24 bits for float32, so the value
is exact in its dtype on both sides.

The Poisson gaps are copied from ``slate_tpu/soak/replay.py``
(``_arrivals``), which the benchmark may not import.
"""

from __future__ import annotations

import random

import numpy as np

M1 = 0x7FEB352D
M2 = 0x846CA68B
MASK = 0xFFFFFFFF


def _mix_int(x: int) -> int:
    """lowbias32 on a python int (the key schedule, host only)."""
    x &= MASK
    x ^= x >> 16
    x = (x * M1) & MASK
    x ^= x >> 15
    x = (x * M2) & MASK
    x ^= x >> 16
    return x


def key(seed: int, stream: int) -> int:
    """32-bit key of one operand stream; any non-negative seed (the
    driver's exceed 32 bits) folds in whole."""
    k = _mix_int(stream)
    seed = int(seed)
    while True:
        k = _mix_int(k ^ (seed & MASK))
        seed >>= 32
        if not seed:
            return k


def _mix(xp, x):
    x = x ^ (x >> xp.uint32(16))
    x = x * xp.uint32(M1)
    x = x ^ (x >> xp.uint32(15))
    x = x * xp.uint32(M2)
    return x ^ (x >> xp.uint32(16))


def hash_u32(xp, k: int, rows, cols):
    """uint32 hash of (k, row, col), broadcast over ``rows`` x ``cols``
    (numpy or jax.numpy; identical bits on both)."""
    r = xp.asarray(rows).astype(xp.uint32)
    c = xp.asarray(cols).astype(xp.uint32)
    k = xp.asarray(k).astype(xp.uint32)
    return _mix(xp, _mix(xp, r ^ k) ^ c)


def to_uniform(xp, u, dtype):
    """uint32 -> uniform [-0.5, 0.5) in ``dtype``, exactly representable."""
    if np.dtype(dtype) == np.float32:
        v = (u >> xp.uint32(8)).astype(xp.float32) * xp.float32(2.0**-24)
        return v - xp.float32(0.5)
    v = u.astype(xp.float64) * xp.float64(2.0**-32)
    return (v - xp.float64(0.5)).astype(dtype)


def uniform(xp, k: int, rows, cols, dtype):
    return to_uniform(xp, hash_u32(xp, k, rows, cols), dtype)


def general(xp, k, n, ncols, dtype):
    """An n x ncols uniform [-0.5, 0.5) matrix (HPL's A and b)."""
    return uniform(xp, k, xp.arange(n)[:, None], xp.arange(ncols)[None, :],
                   dtype)


def spd(xp, k, n, dtype):
    """The tester's SPD matrix (G + G^T)/2 + n I, G uniform."""
    G = general(xp, k, n, n, dtype)
    return (G + G.T) * dtype_scalar(xp, dtype, 0.5) + dtype_scalar(
        xp, dtype, n) * xp.eye(n, dtype=dtype)


def dtype_scalar(xp, dtype, v):
    return xp.asarray(v, dtype=dtype)


def arrivals(rng: random.Random, count: int, rate_rps: float):
    """Poisson arrival offsets (exponential gaps), deterministic in rng
    (copied from slate_tpu/soak/replay.py::_arrivals)."""
    t, out = 0.0, []
    for _ in range(count):
        out.append(round(t, 6))
        t += rng.expovariate(rate_rps)
    return out
