"""Pallas panel kernels for the factorization schedules (the third
``Option.Schedule`` family, ``pallas``).

The recursive schedules in ops/chol_kernels.py, ops/lu_kernels.py and
ops/qr_fast.py bottom out in panel/small-tile base cases below
``nb_switch`` — exactly the layer the reference delegates to hand-tuned
device tile kernels and that Elmroth & Gustavson identify as the bound
on recursive factorizations.  This module re-implements those base
cases as fused Pallas kernels: every kernel has a jnp reference twin
and takes an ``interpret`` flag so the CPU CI runs the identical kernel
bodies via ``pl.pallas_call(..., interpret=True)``.

Kernel families:

* ``chol_base``   — fused unblocked Cholesky of one diagonal block:
  in-register column loop (sqrt, scale, masked rank-1 trailing update)
  in one VMEM pass, replacing the ib-strip ``chol_unblocked``.
* ``panel_lu``    — fused unblocked partial-pivot LU of one (M, nb)
  panel with the in-register pivot search and act-masked eligibility of
  ``ops/lu_kernels.panel_lu`` (identical arithmetic, so the pivot
  order matches ``lax.linalg.lu`` exactly).
* ``larft``       — compact-WY T assembly for the QR panel base case:
  the Gram matrix V^H V accumulated over row blocks of V, then the
  strict-upper extraction and the diag(1/tau)-with-big-limit splice
  building T^{-1}; the small (<= nb) triangular inverse stays on the
  vendor solve, the same convention as the recursive trsm's <= nb
  diagonal blocks.
* ``syrk_diag`` / ``gemm_sub`` — triangle-aware syrk pieces for the
  Cholesky trailing update: only diagonal nb-blocks pay the
  full-square gemm in-kernel (masked to the lower triangle in the same
  VMEM pass); off-diagonal blocks are tiled multiply-subtract gemms.
* ``trsm_lower`` / ``trsm_upper`` — the solve-phase trsm pair behind
  the serve ``phase="solve"`` buckets (the factor cache's top-traffic
  hit family): blocked forward/backward substitution, one grid step
  per KB-row block with the solution resident in VMEM: a full-width
  MXU update, then column substitution within the diagonal block.

Mosaic lowers no ``dynamic_slice`` of a value, so the column loops
address their current column/row by iota masks and masked reductions
(exact: one nonzero summed with zeros), and keep their state in the
output refs rather than in loop-carried values.

Dispatch (the ``chol_base``/``panel_lu``/... entry points): off the TPU
the kernel body runs in interpret mode, which lowers to plain XLA ops —
how ``schedule="pallas"`` reaches driver parity on CPU and how serve
artifacts stay custom-call-free.  On the TPU an eligible operand (f32,
tiling-aligned, within the VMEM budget) runs compiled Mosaic; any other
operand takes the jnp reference twin, counted under
``pallas.reference.<kernel>`` — never the interpreter.

Toolchain caveat: interpret mode cannot initialize COMPLEX pallas
outputs (``primitives.uninitialized_value`` only handles float/int), so
``_call`` hands the kernel complex outputs as exact real/imag ref pairs
behind ``_PairRef`` and recombines them outside — lossless, and the
compiled Mosaic path (f32-only) never sees it.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernels import _spec, _tile, on_tpu

_HIGHEST = lax.Precision.HIGHEST

#: scoped-VMEM limit handed to Mosaic (the v5e has 128 MiB of VMEM; the
#: compiler's default scope is 16 MiB) and the budget eligibility checks
#: hold each call's resident blocks to
_VMEM_LIMIT = 100 * 2**20
_VMEM_BUDGET = 64 * 2**20


def _conj(x):
    return jnp.conj(x) if jnp.iscomplexobj(x) else x


def _mxu_dot(a, b):
    """In-kernel matmul at HIGHEST precision, accumulating at the
    operand dtype (the ops-layer ``hdot`` convention)."""
    return jnp.dot(a, b, precision=_HIGHEST)


def _dot_nt(a, b):
    """a @ b^T without materializing the transpose."""
    return lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), precision=_HIGHEST
    )


def _aligned(a) -> bool:
    """f32 with (8, 128)-aligned trailing dims: the operands Mosaic
    tiles natively (the TPU VPU has no f64/complex vectors)."""
    return (
        a.dtype == jnp.float32
        and a.ndim == 2
        and a.shape[0] % 8 == 0
        and a.shape[1] % 128 == 0
    )


def _route(name: str, eligible: bool) -> Optional[bool]:
    """The ``interpret`` flag for one dispatched kernel call, or None
    for the jnp reference twin.  Off the TPU the kernel body runs in
    interpret mode; on the TPU eligible operands compile to Mosaic and
    the rest take the twin by this counted branch."""
    if not on_tpu():
        return True
    if eligible:
        return False
    from ...aux import metrics

    metrics.inc("pallas.reference")
    metrics.inc(f"pallas.reference.{name}")
    return None


def _real_dtype(dt):
    return jnp.float32 if dt == jnp.dtype(jnp.complex64) else jnp.float64


class _PairRef:
    """A complex output ref stored as exact real/imag ref pairs (the
    interpret-mode caveat in the module docstring)."""

    def __init__(self, re, im, dtype):
        self.re, self.im, self.dtype = re, im, dtype
        self.shape = re.shape

    def __getitem__(self, idx):
        return lax.complex(self.re[idx], self.im[idx]).astype(self.dtype)

    def __setitem__(self, idx, v):
        self.re[idx] = jnp.real(v)
        self.im[idx] = jnp.imag(v)


def _call(kernel, out_shapes, args, interpret: bool, name: str, grid=(),
          in_specs=None, out_specs=None):
    """pallas_call adapter: ``kernel(*in_refs, *out_refs)`` with
    complex outputs behind ``_PairRef``; sequential grid semantics and
    the raised VMEM scope on the compiled path.  ``name`` is the
    kernel's role, which names its custom call in HLO and in traces."""
    single = not isinstance(out_shapes, (tuple, list))
    outs = [out_shapes] if single else list(out_shapes)
    specs = None if out_specs is None else (
        [out_specs] if single else list(out_specs)
    )
    expanded, exp_specs, plan = [], [], []
    for k, o in enumerate(outs):
        cplx = jnp.issubdtype(o.dtype, jnp.complexfloating)
        plan.append((cplx, len(expanded), o.dtype))
        shape = jax.ShapeDtypeStruct(
            o.shape, _real_dtype(o.dtype) if cplx else o.dtype
        )
        for _ in range(2 if cplx else 1):
            expanded.append(shape)
            if specs is not None:
                exp_specs.append(specs[k])

    def kern(*refs):
        out_refs = refs[len(args):]
        wrapped = [
            _PairRef(out_refs[i], out_refs[i + 1], dt) if cplx
            else out_refs[i]
            for cplx, i, dt in plan
        ]
        kernel(*refs[: len(args)], *wrapped)

    kw = {}
    if grid:
        kw.update(grid=grid, in_specs=in_specs, out_specs=exp_specs)
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid) if grid else None,
            vmem_limit_bytes=_VMEM_LIMIT,
        )
    raw = pl.pallas_call(
        kern, out_shape=tuple(expanded), interpret=interpret, name=name, **kw
    )(*args)
    results = [
        lax.complex(raw[i], raw[i + 1]).astype(dt) if cplx else raw[i]
        for cplx, i, dt in plan
    ]
    return results[0] if single else tuple(results)


def _loop(n: int, body, carry=None):
    """``body(j, carry)`` for j in [0, n) with an int32 index: Mosaic
    has no 64-bit integers, which python-int bounds become under x64."""
    zero = jnp.int32(0)
    return lax.fori_loop(
        zero, jnp.int32(n), body, zero if carry is None else carry
    )


def _col(a, cols, j):
    """Column j of a 2-D value as (rows, 1), by a masked lane sum."""
    return jnp.sum(jnp.where(cols == j, a, jnp.zeros_like(a)), axis=1,
                   keepdims=True)


def _row(a, rows, j):
    """Row j of a 2-D value as (1, cols), by a masked sublane sum."""
    return jnp.sum(jnp.where(rows == j, a, jnp.zeros_like(a)), axis=0,
                   keepdims=True)


def _at(v, idx, j):
    """Entry j of a float vector laid out along ``idx``, as a scalar."""
    return jnp.sum(jnp.where(idx == j, v, jnp.zeros_like(v)))


# ---------------------------------------------------------------------------
# Fused unblocked Cholesky base case (chol_unblocked analogue)
# ---------------------------------------------------------------------------


def _chol_base_kernel(g_ref, o_ref):
    b = o_ref.shape[0]
    o_ref[...] = g_ref[...]
    rows = lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = lax.broadcasted_iota(jnp.int32, (b, b), 1)
    rvec = lax.broadcasted_iota(jnp.int32, (b, 1), 0)

    def body(j, carry):
        a = o_ref[...]
        col = _col(a, cols, j)
        pv = jnp.sqrt(_at(col, rvec, j))
        l = jnp.where(rvec > j, col / pv, jnp.zeros_like(col))
        # l as a row vector (the masked diagonal transposes it), then
        # the masked rank-1 trailing update in the same pass
        lrow = jnp.sum(
            jnp.where(rows == cols, l, jnp.zeros_like(a)), axis=0,
            keepdims=True,
        )
        a = jnp.where((rows > j) & (cols > j), a - l * _conj(lrow), a)
        # write the factored column: pivot on the diagonal, l below;
        # entries above the diagonal pass through (callers tril)
        newcol = jnp.where(rvec == j, pv.astype(a.dtype), l)
        o_ref[...] = jnp.where((cols == j) & (rows >= j), newcol, a)
        return carry

    _loop(b, body)


def chol_base_reference(G: jnp.ndarray) -> jnp.ndarray:
    """jnp twin: the ib-strip unblocked Cholesky the recursive schedule
    uses (entries above the diagonal pass through untouched)."""
    from ..chol_kernels import chol_unblocked

    return chol_unblocked(G)


def chol_base_pallas(G: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """Fused unblocked Cholesky of a (b, b) block, one VMEM pass."""
    return _call(
        _chol_base_kernel, jax.ShapeDtypeStruct(G.shape, G.dtype), (G,),
        interpret, "chol_panel",
    )


def chol_base(G: jnp.ndarray) -> jnp.ndarray:
    ok = _aligned(G) and G.shape[0] == G.shape[1] and G.shape[0] <= 1024
    interpret = _route("chol_base", ok)
    if interpret is None:
        return chol_base_reference(G)
    return chol_base_pallas(G, interpret=interpret)


# ---------------------------------------------------------------------------
# Fused panel LU with in-register partial-pivot search (panel_lu analogue)
# ---------------------------------------------------------------------------


def _panel_lu_kernel(p_ref, lu_ref, perm_ref, *, pivot: bool, act):
    """Arithmetic replicates ops/lu_kernels.panel_lu exactly (same op
    sequence -> identical pivot order, identical floats); the pivot is
    the first row of maximal magnitude, argmax's tie rule.  Row indices
    and the permutation ride as f32 (exact below 2^24 rows) and every
    reduction runs over a lane-dense float array: the f32 factor on the
    chip was wrong (||PA - LU|| / (||A|| n) = 9.2e-5) with int32 and
    (M, 1)-shaped reductions in the pivot search."""
    M, nb = lu_ref.shape
    f32 = jnp.float32
    lu_ref[...] = p_ref[...]
    lanes = lax.broadcasted_iota(jnp.int32, (1, M), 1).astype(f32)
    perm_ref[...] = lanes
    rows = lax.broadcasted_iota(jnp.int32, (M, 1), 0)
    rowsf = rows.astype(f32)
    cols = lax.broadcasted_iota(jnp.int32, (1, nb), 1)

    def body(j, carry):
        a = lu_ref[...]
        jf = j.astype(f32)
        col = _col(a, cols, j)
        if pivot:
            elig = rows >= j if act is None else (rows >= j) & (rows < act)
            mag = jnp.abs(a)
            mag = jnp.where(
                elig & (cols == j), mag, jnp.asarray(-jnp.inf, mag.dtype)
            )
            top = jnp.max(mag)
            piv = jnp.min(jnp.where(mag == top, rowsf, f32(M)))
        else:
            piv = jf
        is_j, is_p = rows == j, rowsf == piv
        # swap rows j <-> piv
        rj = _row(a, rows, j)
        rp = jnp.sum(jnp.where(is_p, a, jnp.zeros_like(a)), axis=0,
                     keepdims=True)
        a = jnp.where(is_j, rp, jnp.where(is_p, rj, a))
        perm = perm_ref[...]
        pj = _at(perm, lanes, jf)
        pp = _at(perm, lanes, piv)
        perm_ref[...] = jnp.where(
            lanes == jf, pp, jnp.where(lanes == piv, pj, perm)
        )
        pv = _at(rp, cols, j)
        col = jnp.where(is_j, pv, jnp.where(is_p, _at(rj, cols, j), col))
        safe = jnp.where(pv == 0, jnp.ones_like(pv), pv)
        l = jnp.where((rows > j) & (pv != 0), col / safe, jnp.zeros_like(col))
        a = jnp.where((cols == j) & (rows > j), l, a)
        urow = jnp.where(cols > j, rp, jnp.zeros_like(rp))
        lu_ref[...] = a - l * urow
        return carry

    _loop(min(M, nb), body)


def panel_lu_reference(
    panel: jnp.ndarray, pivot: bool = True, act: Optional[int] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """jnp twin: the fori_loop panel factor the recursive schedule uses."""
    from ..lu_kernels import panel_lu as _panel_lu

    return _panel_lu(panel, pivot=pivot, act=act)


def panel_lu_pallas(
    panel: jnp.ndarray,
    pivot: bool = True,
    act: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused partial-pivot panel LU: per-column pivot search, row swap,
    scale and rank-1 update all inside one kernel invocation.  ``act``
    is static (the recursive schedule's canonical-height pad rows must
    never pivot)."""
    M, nb = panel.shape
    lu, perm = _call(
        functools.partial(_panel_lu_kernel, pivot=pivot, act=act),
        (
            jax.ShapeDtypeStruct((M, nb), panel.dtype),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
        ),
        (panel,),
        interpret,
        "lu_panel",
    )
    return lu, perm.reshape(M).astype(jnp.int32)


def panel_lu(
    panel: jnp.ndarray, pivot: bool = True, act: Optional[int] = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    # the panel, its factor and ~4 full-size temporaries stay in VMEM
    ok = _aligned(panel) and 6 * panel.size * 4 <= _VMEM_BUDGET
    interpret = _route("panel_lu", ok)
    if interpret is None:
        return panel_lu_reference(panel, pivot=pivot, act=act)
    return panel_lu_pallas(panel, pivot=pivot, act=act, interpret=interpret)


# ---------------------------------------------------------------------------
# Compact-WY T assembly (householder.larft analogue)
# ---------------------------------------------------------------------------


def _larft_kernel(v_ref, t_ref, o_ref, *, nsteps: int):
    """T^{-1} = strict_upper(V^H V) + diag(1/tau): the Gram matrix
    accumulates over the row blocks of V in the resident output, the
    assembly (tau == 0 large-diagonal limit included) runs on the last
    step."""
    i = pl.program_id(0)
    nb = o_ref.shape[0]

    @pl.when(i == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    V = v_ref[...]
    o_ref[...] += _mxu_dot(_conj(V).T, V)

    @pl.when(i == nsteps - 1)
    def _():
        VhV = o_ref[...]
        rows = lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
        cols = lax.broadcasted_iota(jnp.int32, (nb, nb), 1)
        U = jnp.where(cols > rows, VhV, jnp.zeros_like(VhV))
        taus = t_ref[...]
        big = jnp.asarray(1e30, VhV.dtype)
        d = jnp.where(
            taus != 0, 1.0 / jnp.where(taus == 0, 1, taus), big
        ).astype(VhV.dtype)
        o_ref[...] = U + jnp.where(rows == cols, d, jnp.zeros_like(U))


def larft_reference(V: jnp.ndarray, taus: jnp.ndarray) -> jnp.ndarray:
    """jnp twin: the compact-WY identity in ops/householder.larft."""
    from ..householder import larft as _larft

    return _larft(V, taus)


def larft_pallas(
    V: jnp.ndarray, taus: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """Compact-WY T for the QR panel base case: the Gram/assembly stage
    (the MXU-heavy 2 M nb^2 part) in one kernel, gridded over row
    blocks of V so each step fits the scoped VMEM; the <= nb triangular
    inverse stays on the vendor solve, the same convention as the
    recursive schedules' <= nb diagonal trsm blocks."""
    M, nb = V.shape
    if taus.shape[0] < nb:
        taus = jnp.concatenate(
            [taus, jnp.zeros((nb - taus.shape[0],), taus.dtype)]
        )
    tm = M if M <= 512 else _tile(M)
    nsteps = M // tm
    Tinv = _call(
        functools.partial(_larft_kernel, nsteps=nsteps),
        jax.ShapeDtypeStruct((nb, nb), V.dtype),
        (V, taus.reshape(1, nb)),
        interpret,
        "larft",
        grid=(nsteps,),
        in_specs=[
            _spec((tm, nb), lambda i: (i, 0)),
            _spec((1, nb), lambda i: (0, 0)),
        ],
        out_specs=_spec((nb, nb), lambda i: (0, 0)),
    )
    T = lax.linalg.triangular_solve(
        Tinv, jnp.eye(nb, dtype=V.dtype), left_side=True, lower=False
    )
    live = (taus != 0)[None, :] & (taus != 0)[:, None]
    return jnp.where(live, T, jnp.zeros_like(T))


def larft(V: jnp.ndarray, taus: jnp.ndarray) -> jnp.ndarray:
    ok = _aligned(V) and V.shape[1] <= 1024
    interpret = _route("larft", ok)
    if interpret is None:
        return larft_reference(V, taus)
    return larft_pallas(V, taus, interpret=interpret)


# ---------------------------------------------------------------------------
# Triangle-aware syrk pieces (the Cholesky trailing update)
# ---------------------------------------------------------------------------


def _syrk_diag_kernel(c_ref, a_ref, o_ref):
    C = c_ref[...]
    A = a_ref[...]
    t = C.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols = lax.broadcasted_iota(jnp.int32, (t, t), 1)
    # entries above the diagonal pass through untouched (_syrk_lower's
    # contract: callers only consume the lower triangle)
    o_ref[...] = jnp.where(rows >= cols, C - _mxu_dot(A, _conj(A).T), C)


def syrk_diag_reference(C: jnp.ndarray, A: jnp.ndarray) -> jnp.ndarray:
    """jnp twin of the diagonal-block base case of _syrk_lower."""
    from ...internal.precision import hdot as _dot

    t = C.shape[0]
    rows = jnp.arange(t)[:, None]
    cols = jnp.arange(t)[None, :]
    return jnp.where(rows >= cols, C - _dot(A, _conj(A).T), C)


def syrk_diag_pallas(
    C: jnp.ndarray, A: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """Diagonal nb-block of the trailing update: the one place that
    pays a full-square gemm, fused with the lower-triangle mask in a
    single VMEM pass."""
    return _call(
        _syrk_diag_kernel, jax.ShapeDtypeStruct(C.shape, C.dtype), (C, A),
        interpret, "syrk_diag",
    )


def syrk_diag(C: jnp.ndarray, A: jnp.ndarray) -> jnp.ndarray:
    ok = (
        _aligned(C) and _aligned(A)
        and 4 * (2 * C.size + A.size) * 4 <= _VMEM_BUDGET
    )
    interpret = _route("syrk_diag", ok)
    if interpret is None:
        return syrk_diag_reference(C, A)
    return syrk_diag_pallas(C, A, interpret=interpret)


def _gemm_sub_kernel(c_ref, a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        o_ref[...] = c_ref[...]

    o_ref[...] -= _dot_nt(a_ref[...], _conj(b_ref[...]))


def gemm_sub_reference(
    C: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray
) -> jnp.ndarray:
    """jnp twin: C - A B^H (the off-diagonal syrk block)."""
    from ...internal.precision import hdot as _dot

    return C - _dot(A, _conj(B).T)


def gemm_sub_pallas(
    C: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """Off-diagonal syrk block: multiply-subtract C - A B^H, tiled over
    (rows of C, cols of C, the contraction)."""
    (m, n), k = C.shape, A.shape[1]
    tm, tn, tk = _tile(m), _tile(n, (512, 256, 128)), _tile(k, (512, 256, 128))
    return _call(
        _gemm_sub_kernel,
        jax.ShapeDtypeStruct(C.shape, C.dtype),
        (C, A, B),
        interpret,
        "gemm_sub",
        grid=(m // tm, n // tn, k // tk),
        in_specs=[
            _spec((tm, tn), lambda i, j, s: (i, j)),
            _spec((tm, tk), lambda i, j, s: (i, s)),
            _spec((tn, tk), lambda i, j, s: (j, s)),
        ],
        out_specs=_spec((tm, tn), lambda i, j, s: (i, j)),
    )


def gemm_sub(C: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    ok = _aligned(C) and _aligned(A) and _aligned(B)
    interpret = _route("gemm_sub", ok)
    if interpret is None:
        return gemm_sub_reference(C, A, B)
    return gemm_sub_pallas(C, A, B, interpret=interpret)


# ---------------------------------------------------------------------------
# The solve-phase trsm pair (serve phase="solve" buckets)
# ---------------------------------------------------------------------------


def _trsm_kb(n: int) -> int:
    """Row-block size of the substitution: 128 (the lane tile the
    compiled diagonal block needs) where it divides n."""
    return _tile(n, (128, 32, 16, 8, 4, 2, 1))


def _block_subst(D, rhs, lower: bool, unit: bool):
    """Solve one (kb, kb) triangular diagonal block against rhs by
    column substitution — backward stable, where an explicit inverse
    of the block loses accuracy with its condition (the f32 gesv
    backward error on the chip reached 6.4 eps with Newton-inverted
    blocks).  Reads only D's own triangle (packed LU storage)."""
    kb = D.shape[0]
    rows = lax.broadcasted_iota(jnp.int32, (kb, 1), 0)
    cols = lax.broadcasted_iota(jnp.int32, (1, kb), 1)

    def body(t, carry):
        b, X = carry
        c = t if lower else kb - 1 - t
        dcol = _col(D, cols, c)
        xc = _row(b, rows, c)
        if not unit:
            xc = xc / _at(dcol, rows, c)
        X = jnp.where(rows == c, xc, X)
        later = rows > c if lower else rows < c
        return jnp.where(later, b - dcol * xc, b), X

    return _loop(kb, body, (rhs, jnp.zeros_like(rhs)))[1]


def _trsm_kernel(lrow_ref, d_ref, b_ref, x_ref, *, lower: bool, unit: bool,
                 kb: int, nblk: int):
    i = pl.program_id(0)
    k = i if lower else nblk - 1 - i

    @pl.when(i == 0)
    def _():
        x_ref[...] = jnp.zeros(x_ref.shape, x_ref.dtype)

    # full-width update: rows of X not yet solved are still zero, so
    # the unsolved columns of this row block contribute nothing
    # (packed-LU storage included: the other triangle multiplies zero
    # rows)
    rhs = b_ref[...] - _mxu_dot(lrow_ref[...], x_ref[...])
    r0 = pl.multiple_of(k * kb, kb)
    x_ref[pl.ds(r0, kb), :] = _block_subst(d_ref[...], rhs, lower, unit)


def trsm_blocked(
    T: jnp.ndarray, B: jnp.ndarray, lower: bool, unit: bool = False
) -> jnp.ndarray:
    """The Pallas pair's algorithm in plain jnp: one ``fori_loop`` over
    KB-row blocks, each diagonal block solved by ``_block_subst``.
    Pure XLA ops with one small loop body — custom-call-free on CPU
    (where the vendor solve is a LAPACK call) and quick to compile for
    the TPU's emulated f64 (n=8192: the vendor solve compiles ~57 s per
    sweep for a described v5e)."""
    n, nrhs = B.shape
    kb = _trsm_kb(n)
    nblk = n // kb

    def blk(i, X):
        r0 = (i if lower else nblk - 1 - i) * kb
        # full-width update, as in _trsm_kernel: unsolved rows are zero
        Trow = lax.dynamic_slice(T, (r0, 0), (kb, n))
        rhs = lax.dynamic_slice(B, (r0, 0), (kb, nrhs)) - _mxu_dot(Trow, X)
        D = lax.dynamic_slice(T, (r0, r0), (kb, kb))
        Xk = _block_subst(D, rhs, lower, unit)
        return lax.dynamic_update_slice(X, Xk, (r0, 0))

    return lax.fori_loop(0, nblk, blk, jnp.zeros_like(B))


def trsm_lower_reference(
    L: jnp.ndarray, B: jnp.ndarray, unit: bool = False
) -> jnp.ndarray:
    """jnp twin: the vendor lower-triangular solve."""
    return lax.linalg.triangular_solve(
        L, B, left_side=True, lower=True, unit_diagonal=unit
    )


def trsm_upper_reference(U: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """jnp twin: the vendor upper-triangular solve."""
    return lax.linalg.triangular_solve(U, B, left_side=True, lower=False)


def _trsm_pallas_call(T, B, lower, unit, interpret):
    n, nrhs = B.shape
    kb = _trsm_kb(n)
    nblk = n // kb

    def blk(i):
        return i if lower else nblk - 1 - i

    return _call(
        functools.partial(
            _trsm_kernel, lower=lower, unit=unit, kb=kb, nblk=nblk
        ),
        jax.ShapeDtypeStruct(B.shape, B.dtype),
        (T, T, B),
        interpret,
        "trsm_lower" if lower else "trsm_upper",
        grid=(nblk,),
        in_specs=[
            _spec((kb, n), lambda i: (blk(i), 0)),
            _spec((kb, kb), lambda i: (blk(i), blk(i))),
            _spec((kb, nrhs), lambda i: (blk(i), 0)),
        ],
        out_specs=_spec((n, nrhs), lambda i: (0, 0)),
    )


def trsm_lower_pallas(
    L: jnp.ndarray, B: jnp.ndarray, unit: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:
    """L X = B by blocked forward substitution in one kernel (reads
    only the lower triangle, so packed-LU storage is fine)."""
    return _trsm_pallas_call(L, B, lower=True, unit=unit, interpret=interpret)


def trsm_upper_pallas(
    U: jnp.ndarray, B: jnp.ndarray, interpret: bool = False
) -> jnp.ndarray:
    """U X = B by blocked backward substitution in one kernel (reads
    only the upper triangle)."""
    return _trsm_pallas_call(U, B, lower=False, unit=False,
                             interpret=interpret)


def _trsm_ok(T, B) -> bool:
    """The triangle's row blocks and the resident solution (both
    double-buffered, the right-hand side lane-padded) within budget."""
    n = T.shape[0]
    lanes = -(-B.shape[1] // 128) * 128
    resident = 2 * (128 * n + n * lanes) * 4
    return (
        _aligned(T) and B.dtype == jnp.float32 and n % 128 == 0
        and resident <= _VMEM_BUDGET
    )


def trsm_lower(
    L: jnp.ndarray, B: jnp.ndarray, unit: bool = False
) -> jnp.ndarray:
    interpret = _route("trsm", _trsm_ok(L, B))
    if interpret is None:
        return trsm_lower_reference(L, B, unit=unit)
    return trsm_lower_pallas(L, B, unit=unit, interpret=interpret)


def trsm_upper(U: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    interpret = _route("trsm", _trsm_ok(U, B))
    if interpret is None:
        return trsm_upper_reference(U, B)
    return trsm_upper_pallas(U, B, interpret=interpret)
