"""Phase names inside the compiled solves and on the profiler's host plane.

The factorization and solve phases carry ``jax.named_scope`` names, which
XLA keeps in each optimized HLO op's ``op_name`` metadata; a profile
reader attributes device time to a phase by them.  Every Pallas kernel
carries its role as its ``name``.  An armed driver phase opens a
``jax.profiler.TraceAnnotation``, so a profile shows it on the host
plane, on the clock of the ops it dispatched.
"""

import glob
import re

import jax
import jax.extend.core
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.aux import metrics, spans
from slate_tpu.ops import chol_kernels
from slate_tpu.ops.pallas import kernels as pk_tiles
from slate_tpu.ops.pallas import panel_kernels as pk

SCOPES = (
    "getrf.panel", "getrf.swap", "getrf.trsm", "getrf.update",
    "getrs.permute", "getrs.trsm_lower", "getrs.trsm_upper",
    "potrf.panel", "potrf.trsm", "potrf.update",
    "potrs.trsm_lower", "potrs.trsm_upper",
)
#: the programs each scope family must appear in
PROGRAMS = {"getrf": ("gesv",), "getrs": ("gesv",),
            "potrf": ("posv", "chol_fori"), "potrs": ("posv",)}
#: ops that do a phase's work; none may sit outside a phase scope.  A
#: gather outside every loop is the drivers' layout work (the Hermitian
#: mirror's diagonal), so gathers count inside loop bodies only.
WORK = re.compile(r"= \S+ (dot|triangular-solve|custom-call)\(|"
                  r"= \S+ gather\(.*/while/body/")
OP_NAME = re.compile(r'op_name="([^"]*)"')
N, NB = 64, 16


def _spd(n, seed=0):
    a = np.random.default_rng(seed).standard_normal((n, n))
    return a @ a.T + n * np.eye(n)


def _optimized_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.fixture(scope="module")
def hlo():
    """Optimized HLO of the three programs, on named schedules: on the
    CPU ``auto`` takes the vendor kernels."""
    rng = np.random.default_rng(1)
    A, B = rng.standard_normal((N, N)), rng.standard_normal((N, 2))
    S = _spd(N)

    def gesv(A, B):
        X, *_ = st.gesv(st.Matrix.from_global(A, NB),
                        st.Matrix.from_global(B, NB),
                        {st.Option.Schedule: st.Schedule.Flat})
        return X.to_global()

    def posv(S, B):
        X, *_ = st.posv(
            st.HermitianMatrix.from_global(S, NB, uplo=st.Uplo.Lower),
            st.Matrix.from_global(B, NB),
            {st.Option.Schedule: st.Schedule.Recursive,
             st.Option.BlockSize: NB})
        return X.to_global()

    return {"gesv": _optimized_hlo(gesv, A, B),
            "posv": _optimized_hlo(posv, S, B),
            "chol_fori": _optimized_hlo(
                lambda G: chol_kernels.chol_fori(G, NB), S)}


def _phase(op_name: str):
    """The innermost phase scope on an op's scope path, or None."""
    found = [p for p in op_name.split("/") if p in SCOPES]
    return found[-1] if found else None


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_names_the_compiled_ops(hlo, scope):
    for prog in PROGRAMS[scope.split(".")[0]]:
        names = OP_NAME.findall(hlo[prog])
        assert any(_phase(n) == scope for n in names), (prog, scope)


@pytest.mark.parametrize("prog", ("gesv", "posv", "chol_fori"))
def test_every_work_op_sits_in_a_phase(hlo, prog):
    work = [line for line in hlo[prog].splitlines() if WORK.search(line)]
    assert work, prog
    for line in work:
        m = OP_NAME.search(line)
        assert m and _phase(m.group(1)), line


def test_scopes_do_not_nest(hlo):
    for text in hlo.values():
        for name in OP_NAME.findall(text):
            assert sum(p in SCOPES for p in name.split("/")) <= 1, name


def _pallas_names(fn, *args):
    """The ``name`` of every pallas_call in fn's jaxpr, nested ones too."""
    out = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
                continue
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        sub = sub.jaxpr
                    if isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


_f32 = np.float32
_S128 = _spd(128).astype(_f32)
_M = np.random.default_rng(2).standard_normal((256, 128)).astype(_f32)
_T = np.random.default_rng(3).standard_normal((4, 8, 128)).astype(_f32)

KERNELS = {
    "chol_panel": lambda: (lambda G: pk.chol_base_pallas(G, True), _S128),
    "lu_panel": lambda: (lambda P: pk.panel_lu_pallas(P, interpret=True),
                         _M),
    "larft": lambda: (lambda V: pk.larft_pallas(
        V, jnp.ones((128,), _f32), True), _M),
    "syrk_diag": lambda: (lambda C: pk.syrk_diag_pallas(C, C, True),
                          _S128),
    "gemm_sub": lambda: (lambda C: pk.gemm_sub_pallas(C, C, C, True),
                         _S128),
    "trsm_lower": lambda: (lambda L: pk.trsm_lower_pallas(
        L, L[:, :8], interpret=True), _S128),
    "trsm_upper": lambda: (lambda U: pk.trsm_upper_pallas(
        U, U[:, :8], interpret=True), _S128),
    "tile_norms_max": lambda: (lambda T: pk_tiles.tile_norms_pallas(
        T, "max", True), _T),
    "tile_transpose": lambda: (lambda T: pk_tiles.tile_transpose_pallas(
        T, interpret=True), _T),
    "butterfly_level": lambda: (lambda X: pk_tiles.butterfly_level_pallas(
        X, X[:128, 0], X[:128, 1], True, True), _M),
    "tile_geadd": lambda: (lambda T: pk_tiles.tile_geadd_pallas(
        1.0, T, 2.0, T, True), _T),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_call_carries_its_name(name):
    fn, arg = KERNELS[name]()
    assert _pallas_names(fn, arg) == [name]


# ---------------------------------------------------------------------------
# host annotations on the profiler's clock
# ---------------------------------------------------------------------------


@pytest.fixture
def _off():
    metrics.off()
    spans.off()
    spans.clear()
    yield
    metrics.off()
    metrics.reset()
    spans.off()
    spans.clear()


def _operands():
    rng = np.random.default_rng(4)
    return (st.Matrix.from_global(rng.standard_normal((N, N)), NB),
            st.Matrix.from_global(rng.standard_normal((N, 2)), NB))


def test_driver_annotation_covers_its_ops(_off, tmp_path):
    """With spans on, an eager gesv under the profiler leaves a ``gesv``
    event on the host plane that covers every XLA op it ran (on the CPU
    the ops run on host threads, on the same clock)."""
    from jax.profiler import ProfileData

    A, B = _operands()
    spans.on()
    st.gesv(A, B)  # compile outside the profile
    async_dispatch = jax.config.values["jax_cpu_enable_async_dispatch"]
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    try:
        with jax.profiler.trace(str(tmp_path)):
            X, *_ = st.gesv(A, B)
    finally:
        jax.config.update("jax_cpu_enable_async_dispatch", async_dispatch)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = [ev for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for ln in p.lines for ev in ln.events]
    (g,) = [ev for ev in host if ev.name == "gesv"]
    ops = [ev for ev in host if any(k == "hlo_op" for k, _v in ev.stats)]
    assert ops
    for ev in ops:
        assert g.start_ns <= ev.start_ns and ev.end_ns <= g.end_ns, ev.name
    assert np.all(np.isfinite(np.asarray(X.to_global())))


def test_annotations_open_only_when_armed(_off, monkeypatch):
    """Off, a driver call opens no annotation; spans on, each driver
    phase and span block opens one of its name."""
    opened = []

    class Counting:
        def __init__(self, name, **_kw):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    A, B = _operands()
    st.gesv(A, B)
    with spans.span("off_block"):
        pass
    with metrics.phase("off_phase"):
        pass
    assert opened == []
    spans.on()
    st.gesv(A, B)
    with spans.span("block"):
        pass
    assert {"gesv", "getrf", "getrs", "block"} <= set(opened)
    spans.off()
    opened.clear()
    metrics.on()
    with metrics.phase("timed"):
        pass
    assert opened == ["timed"]
