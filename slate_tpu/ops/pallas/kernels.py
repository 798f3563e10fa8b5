"""Pallas TPU kernels for the device tile-kernel layer.

TPU-native re-implementations of the reference's CUDA device kernels
(reference: src/cuda/device_genorm.cu, device_transpose.cu,
device_geadd.cu, device_gescale.cu, src/internal/internal_rbt_generate +
gerbt butterfly kernels; interface include/slate/internal/device.hh:92-282).

Most elementwise tile ops fuse perfectly under plain XLA (see
internal/tile_ops.py) — Pallas is reserved for the patterns XLA schedules
poorly:

  * batched tile norms with per-tile reductions and a fro (scale, sumsq)
    update — one VMEM pass per tile instead of XLA's multi-kernel
    reduce chains (device_genorm.cu's per-block reductions);
  * the recursive butterfly (RBT) pair transform — strided pair access
    that XLA turns into gather/scatter, here a single VMEM pass;
  * batched tile transpose feeding MXU-unfriendly layouts.

Every kernel has a jnp reference implementation; the dispatchers gate on
the platform (``on_tpu``) and the operand, and tests run the Pallas path
in interpreter mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def on_tpu() -> bool:
    """Whether the default backend is a TPU (where the kernels compile
    to Mosaic); every other backend runs the jnp twins or interpret
    mode."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Batched tile norms (device_genorm.cu analogue)
# ---------------------------------------------------------------------------


def _norm_kernel(t_ref, out_ref, *, kind: str):
    """One grid step = one tile; writes the tile's norm statistic."""
    t = t_ref[...]
    a = jnp.abs(t)
    if kind == "max":
        out_ref[0] = jnp.max(a)
    elif kind == "fro_sumsq":
        out_ref[0] = jnp.sum(a * a)
    elif kind == "one":  # max column sum within the tile -> still needs
        # cross-tile accumulation; emit per-column sums
        out_ref[...] = jnp.sum(a, axis=0)
    elif kind == "inf":
        out_ref[...] = jnp.sum(a, axis=1)


def pallas_norm_ok(T, kind: str) -> bool:
    """Mosaic lowering constraints for the norm kernels: 32-bit real
    dtype (the TPU VPU has no f64 vectors) and (8, 128)-divisible tile
    dims."""
    if T.dtype != jnp.float32:
        return False
    N, mb, nb = T.shape
    if mb % 8 != 0 or nb % 128 != 0:
        return False
    if kind == "inf" and mb % 128 != 0:
        return False
    return True


def _spec(block_shape, index_map):
    """BlockSpec whose block indices are int32 even under x64 (python-int
    indices would lower to 64-bit constants Mosaic cannot take)."""
    return pl.BlockSpec(
        block_shape,
        lambda *g: tuple(jnp.int32(i) for i in index_map(*g)),
    )


def _tile(n: int, sizes=(512, 256, 128, 64, 32, 16, 8)) -> int:
    """Largest block size in ``sizes`` dividing n (n itself if none
    does: a full-extent block is always legal)."""
    for s in sizes:
        if s <= n and n % s == 0:
            return s
    return n


def tile_norms_pallas(T: jnp.ndarray, kind: str, interpret: bool = False):
    """Per-tile norm statistics over a (N, mb, nb) tile stack.

    kind: 'max' -> (N,); 'fro_sumsq' -> (N,) sum of squares;
    'one' -> (N, nb) per-column sums; 'inf' -> (N, mb) per-row sums.

    One grid-free pallas_call per ~2 MiB chunk of tiles (mapped with
    lax.map): each invocation reduces its whole chunk in VMEM in a
    single pass — the analogue of device_genorm.cu's one-block-per-tile
    reductions.  Scalar statistics broadcast across the output lane dim
    and are sliced outside.
    """
    from jax import lax

    N, mb, nb = T.shape
    CH = max(1, min(64, (1 << 21) // max(mb * nb * 4, 1)))
    Np = -(-N // CH) * CH
    if Np != N:
        T = jnp.pad(T, ((0, Np - N), (0, 0), (0, 0)))
    real = T.dtype
    out_cols = mb if kind == "inf" else nb

    def kernel(t_ref, o_ref):
        a = jnp.abs(t_ref[...]).reshape(CH, mb, nb)
        if kind == "max":
            s = jnp.max(jnp.max(a, axis=2), axis=1)
            o_ref[...] = jnp.broadcast_to(s[:, None], (CH, out_cols))
        elif kind == "fro_sumsq":
            s = jnp.sum(jnp.sum(a * a, axis=2), axis=1)
            o_ref[...] = jnp.broadcast_to(s[:, None], (CH, out_cols))
        elif kind == "one":
            o_ref[...] = jnp.sum(a, axis=1)
        else:
            o_ref[...] = jnp.sum(a, axis=2)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((CH, out_cols), real),
        interpret=interpret,
        name=f"tile_norms_{kind}",
    )
    chunks = T.reshape(Np // CH, CH * mb, nb)
    out = lax.map(call, chunks).reshape(Np, out_cols)[:N]
    if kind in ("max", "fro_sumsq"):
        return out[:, 0]
    return out


def tile_norms_reference(T: jnp.ndarray, kind: str):
    """jnp twin of tile_norms_pallas."""
    a = jnp.abs(T)
    if kind == "max":
        return a.max(axis=(1, 2))
    if kind == "fro_sumsq":
        return (a * a).sum(axis=(1, 2))
    if kind == "one":
        return a.sum(axis=1)
    return a.sum(axis=2)


def tile_norms(T: jnp.ndarray, kind: str):
    """Dispatch: Pallas on TPU for Mosaic-compatible shapes/dtypes
    (f32, (8,128)-divisible tiles), jnp elsewhere."""
    if on_tpu() and pallas_norm_ok(T, kind):
        return tile_norms_pallas(T, kind)
    return tile_norms_reference(T, kind)


# ---------------------------------------------------------------------------
# Batched tile transpose (device_transpose.cu analogue)
# ---------------------------------------------------------------------------


def tile_transpose_pallas(T: jnp.ndarray, conj: bool = False, interpret: bool = False):
    """(N, mb, nb) -> (N, nb, mb), per-tile (conj-)transpose."""
    N, mb, nb = T.shape

    def kernel(t_ref, out_ref):
        t = t_ref[0]
        if conj and jnp.issubdtype(t.dtype, jnp.complexfloating):
            t = jnp.conj(t)
        out_ref[0, :, :] = t.T

    return pl.pallas_call(
        kernel,
        grid=(N,),
        in_specs=[_spec((1, mb, nb), lambda i: (i, 0, 0))],
        out_specs=_spec((1, nb, mb), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, nb, mb), T.dtype),
        interpret=interpret,
        name="tile_transpose",
    )(T)


def tile_transpose(T: jnp.ndarray, conj: bool = False):
    # XLA handles batched transposes well; the Pallas transpose above
    # stays a tested twin
    out = T.transpose(0, 2, 1)
    if conj and jnp.issubdtype(T.dtype, jnp.complexfloating):
        out = jnp.conj(out)
    return out


# ---------------------------------------------------------------------------
# Butterfly (RBT) pair transform (gerbt kernel analogue)
# ---------------------------------------------------------------------------


def butterfly_level_pallas(
    X: jnp.ndarray, D1: jnp.ndarray, D2: jnp.ndarray, transpose: bool,
    interpret: bool = False,
):
    """One butterfly level over paired row blocks.

    X: (2h, w); D1, D2: (h,).  transpose=True:
        top = D1 x1 + D2 x2 ; bot = D1 x1 - D2 x2
    else:
        top = D1 (x1 + x2) ; bot = D2 (x1 - x2)
    (matches drivers/lu._apply_butterfly).  Gridded over (half, row
    block, column block): each step reads the paired (tr, tw) blocks of
    both halves and writes one half's block.
    """
    two_h, w = X.shape
    h = two_h // 2
    tr = _tile(h)
    tw = _tile(w, (512, 256, 128))
    nr = h // tr
    s = float(np.sqrt(0.5))  # python scalar: weak-typed, not a captured const

    def kernel(x1_ref, x2_ref, d1_ref, d2_ref, out_ref):
        x1, x2 = x1_ref[...], x2_ref[...]
        d1, d2 = d1_ref[...], d2_ref[...]
        half = pl.program_id(0)

        @pl.when(half == 0)
        def _():
            top = d1 * x1 + d2 * x2 if transpose else d1 * (x1 + x2)
            out_ref[...] = s * top

        @pl.when(half == 1)
        def _():
            bot = d1 * x1 - d2 * x2 if transpose else d2 * (x1 - x2)
            out_ref[...] = s * bot

    return pl.pallas_call(
        kernel,
        grid=(2, nr, w // tw),
        in_specs=[
            _spec((tr, tw), lambda p, i, j: (i, j)),
            _spec((tr, tw), lambda p, i, j: (i + nr, j)),
            _spec((tr, 1), lambda p, i, j: (i, 0)),
            _spec((tr, 1), lambda p, i, j: (i, 0)),
        ],
        out_specs=_spec((tr, tw), lambda p, i, j: (p * nr + i, j)),
        out_shape=jax.ShapeDtypeStruct(X.shape, X.dtype),
        interpret=interpret,
        name="butterfly_level",
    )(X, X, D1.reshape(h, 1), D2.reshape(h, 1))


def butterfly_level_reference(X, D1, D2, transpose: bool):
    h = X.shape[0] // 2
    s = np.sqrt(0.5)
    x1, x2 = X[:h], X[h:]
    d1, d2 = D1[:, None], D2[:, None]
    if transpose:
        return s * jnp.concatenate([d1 * x1 + d2 * x2, d1 * x1 - d2 * x2])
    return s * jnp.concatenate([d1 * (x1 + x2), d2 * (x1 - x2)])


def butterfly_level(X, D1, D2, transpose: bool):
    # Mosaic has no f64 vector support; 32-bit floats only on the chip
    if on_tpu() and X.dtype == jnp.float32 and (X.shape[0] // 2) % 8 == 0:
        return butterfly_level_pallas(X, D1, D2, transpose)
    return butterfly_level_reference(X, D1, D2, transpose)


# ---------------------------------------------------------------------------
# Fused masked geadd/scale (device_geadd.cu / device_gescale.cu analogue)
# ---------------------------------------------------------------------------


def tile_geadd_pallas(
    alpha, A: jnp.ndarray, beta, B: jnp.ndarray, interpret: bool = False
):
    """B = alpha A + beta B over a (N, mb, nb) stack, one VMEM pass."""
    N, mb, nb = A.shape

    def kernel(a_ref, b_ref, out_ref):
        out_ref[...] = alpha * a_ref[...] + beta * b_ref[...]

    return pl.pallas_call(
        kernel,
        grid=(N,),
        in_specs=[
            _spec((1, mb, nb), lambda i: (i, 0, 0)),
            _spec((1, mb, nb), lambda i: (i, 0, 0)),
        ],
        out_specs=_spec((1, mb, nb), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(A.shape, B.dtype),
        interpret=interpret,
        name="tile_geadd",
    )(A, B)
