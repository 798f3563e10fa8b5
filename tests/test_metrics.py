"""Observability layer tests: aux/metrics.py (counters/gauges/timers,
compile-vs-run split, cost_analysis capture, JSONL round-trip,
zero-overhead-when-off, thread safety, the fallback/precision counters)."""

import json
import threading

import numpy as np
import pytest

from slate_tpu.aux import metrics


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends with metrics off and empty."""
    metrics.off()
    metrics.reset()
    yield
    metrics.off()
    metrics.reset()


# ---------------------------------------------------------------------------
# counters / gauges / timers
# ---------------------------------------------------------------------------


def test_counters_and_gauges():
    metrics.on()
    metrics.inc("a")
    metrics.inc("a", 2)
    metrics.inc("b", 0.5)
    metrics.gauge("g", 3.25)
    assert metrics.counters() == {"a": 3, "b": 0.5}
    assert metrics.gauges() == {"g": 3.25}
    metrics.reset()
    assert metrics.counters() == {}
    assert metrics.gauges() == {}


def test_timer_stats():
    metrics.on()
    metrics.observe("t", 0.5)
    metrics.observe("t", 1.5)
    t = metrics.timers()["t"]
    assert t["count"] == 2
    assert t["total_s"] == pytest.approx(2.0)
    assert t["min_s"] == pytest.approx(0.5)
    assert t["max_s"] == pytest.approx(1.5)


def test_phase_records_timer_and_event():
    metrics.on()
    with metrics.phase("work") as ph:
        pass
    assert ph.seconds >= 0.0
    assert metrics.timers()["work"]["count"] == 1
    assert metrics.summary()["timers"]["work"]["count"] == 1


def test_phase_always_measures_without_recording():
    assert not metrics.is_on()
    with metrics.phase("hidden", always=True) as ph:
        x = sum(range(100))
    assert x == 4950
    assert ph.seconds > 0.0  # measured for the caller...
    metrics.on()
    assert metrics.timers() == {}  # ...but nothing was recorded


# ---------------------------------------------------------------------------
# histograms: log buckets, percentiles, deltas, JSONL
# ---------------------------------------------------------------------------


def test_histogram_percentiles_accurate_to_a_bucket():
    """p50/p95/p99 from the log-spaced buckets track the exact sample
    percentiles within one bucket ratio (10/decade => ~26% worst case;
    lognormal latencies land well inside that)."""
    import random

    random.seed(7)
    vals = sorted(random.lognormvariate(-5, 1) for _ in range(4000))
    metrics.on()
    for v in vals:
        metrics.observe_hist("lat", v)
    for p in (50, 95, 99):
        est = metrics.percentile("lat", p)
        exact = vals[int(p / 100 * len(vals)) - 1]
        assert est == pytest.approx(exact, rel=0.3), p
    s = metrics.hist_summary("lat")
    assert s["count"] == 4000
    assert s["min_s"] == pytest.approx(vals[0], abs=1e-6)  # 6-dp rounded
    assert s["max_s"] == pytest.approx(vals[-1], abs=1e-6)
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max_s"]


def test_histogram_extremes_clamped():
    metrics.on()
    metrics.observe_hist("h", 1e-9)   # underflow bucket
    metrics.observe_hist("h", 5e3)    # overflow bucket
    assert metrics.percentile("h", 1) == pytest.approx(1e-9)
    assert metrics.percentile("h", 99) == pytest.approx(5e3)


def test_histogram_single_observation_exact():
    metrics.on()
    metrics.observe_hist("one", 0.0123)
    # min/max clamping makes a single-sample histogram exact at every p
    assert metrics.percentile("one", 50) == pytest.approx(0.0123)
    assert metrics.percentile("one", 99) == pytest.approx(0.0123)


def test_observe_hist_off_is_noop():
    assert not metrics.is_on()
    metrics.observe_hist("h", 1.0)
    metrics.on()
    assert metrics.histograms() == {}
    assert metrics.percentile("h", 50) is None
    assert metrics.hist_summary("h") is None


def test_deltas_hist_windows_percentiles():
    metrics.on()
    metrics.observe_hist("d", 100.0)  # pre-window outlier
    with metrics.deltas() as d:
        for v in (0.001, 0.002, 0.004, 0.008):
            metrics.observe_hist("d", v)
        w = d.hist("d")
    assert w["count"] == 4
    assert w["total_s"] == pytest.approx(0.015)
    assert w["p99"] < 0.02  # the pre-window 100s sample is excluded
    assert d.hist("missing") is None


def test_hist_jsonl_round_trip_and_report(tmp_path):
    metrics.on()
    for v in (0.001, 0.01, 0.1):
        metrics.observe_hist("serve.latency.test.total", v)
    rep = metrics.report()
    assert "histogram" in rep and "serve.latency.test.total" in rep
    path = str(tmp_path / "h.jsonl")
    metrics.dump(path)
    rows = metrics.load_jsonl(path)
    h = [r for r in rows if r["type"] == "hist"]
    assert len(h) == 1 and h[0]["name"] == "serve.latency.test.total"
    assert h[0]["count"] == 3
    assert sum(c for _le, c in h[0]["buckets"]) == 3
    # bucket upper edges bracket the observations
    les = [le for le, _c in h[0]["buckets"]]
    assert all(isinstance(le, float) for le in les)
    # the percentile helper re-ranks from the wire form the same way
    counts = [0] * (len(metrics.HIST_EDGES) + 1)
    edge_index = {f"{e:.9g}": i for i, e in enumerate(metrics.HIST_EDGES)}
    for le, c in h[0]["buckets"]:
        counts[edge_index[f"{le:.9g}"]] = c
    est = metrics.Histogram.percentile_from(counts, 99)
    assert est == pytest.approx(h[0]["p99"], rel=0.35)
    assert metrics.summary()["histograms"]["serve.latency.test.total"][
        "count"] == 3


def test_driver_phase_feeds_histogram():
    """kind="driver" phases (the @instrumented decorator) land in a
    same-named histogram — factor/solve percentiles for free."""
    metrics.on()

    @metrics.instrumented("hist_drv")
    def drv():
        return 1

    for _ in range(3):
        drv()
    assert metrics.hist_summary("hist_drv")["count"] == 3
    # plain phases do NOT (timers already cover them)
    with metrics.phase("plain"):
        pass
    assert metrics.hist_summary("plain") is None


def test_hist_reset_clears():
    metrics.on()
    metrics.observe_hist("h", 1.0)
    metrics.reset()
    assert metrics.histograms() == {}


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------


def test_off_records_nothing():
    assert not metrics.is_on()
    metrics.inc("n")
    metrics.gauge("g", 1)
    metrics.observe("t", 1.0)
    with metrics.phase("p"):
        pass

    @metrics.instrumented("fn")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    metrics.on()
    assert metrics.counters() == {}
    assert metrics.gauges() == {}
    assert metrics.timers() == {}


def test_instrumented_off_is_single_bool_check():
    """With metrics AND spans off the wrapper takes the early-return
    branch: no Timer object, no dict writes (the zero-overhead contract,
    like trace.on_ in the reference)."""
    calls = []

    @metrics.instrumented("probe")
    def fn():
        calls.append(metrics.is_on())

    fn()
    assert calls == [False]
    metrics.on()
    assert metrics.timers() == {}  # the off-path call left no trace


def test_instrument_jit_off_passthrough():
    import jax

    jitted = jax.jit(lambda x: x * 2)
    wrapped = metrics.instrument_jit(jitted, "double")
    out = wrapped(np.float64(3.0))
    assert float(out) == 6.0
    metrics.on()
    assert metrics.counters() == {}


# ---------------------------------------------------------------------------
# compile/run split + cost_analysis
# ---------------------------------------------------------------------------


def test_compile_run_split_tiny_jit():
    import jax.numpy as jnp

    metrics.on()
    f = metrics.jit(lambda a, b: a @ b, name="mm")
    x = jnp.ones((8, 8))
    f(x, x)  # first dispatch: compile
    f(x, x)  # cached: run
    f(x, x)
    c = metrics.counters()
    assert c["mm.compilations"] == 1
    assert c["jit.compilations"] == 1
    t = metrics.timers()
    assert t["mm.compile"]["count"] == 1
    assert t["mm.run"]["count"] == 2
    # a new shape signature recompiles — the recompile-storm signal
    y = jnp.ones((4, 4))
    f(y, y)
    assert metrics.counters()["mm.compilations"] == 2


def test_cost_analysis_flops_captured():
    import jax.numpy as jnp

    metrics.on()
    f = metrics.jit(lambda a, b: a @ b, name="mm8")
    x = jnp.ones((8, 8), jnp.float32)
    f(x, x)
    cost = metrics.costs().get("mm8")
    assert cost is not None
    assert cost["flops"] == pytest.approx(2 * 8**3 / 2, rel=1.0)  # 8^3..2*8^3
    assert cost["bytes_accessed"] > 0


def test_traced_calls_inside_outer_jit():
    """Calls inlined into an outer jit (tracer args) pass through with a
    counter instead of bogus trace-time timings."""
    import jax
    import jax.numpy as jnp

    metrics.on()
    inner = metrics.jit(lambda a: a + 1, name="inner")
    outer = jax.jit(lambda a: inner(a) * 2)
    out = outer(jnp.ones((4,)))
    np.testing.assert_array_equal(np.asarray(out), 4.0)
    c = metrics.counters()
    assert c.get("inner.traced_calls", 0) >= 1
    assert "inner.compilations" not in c


# ---------------------------------------------------------------------------
# JSONL round-trip + report
# ---------------------------------------------------------------------------


def test_jsonl_round_trip(tmp_path):
    import jax.numpy as jnp

    metrics.on()
    f = metrics.jit(lambda a: a * 2, name="x2")
    f(jnp.ones((4,)))
    metrics.inc("c", 7)
    with metrics.context("entry1"):
        with metrics.phase("ph"):
            pass
    path = str(tmp_path / "m.jsonl")
    assert metrics.dump(path) == path
    rows = metrics.load_jsonl(path)
    types = {r["type"] for r in rows}
    assert {"meta", "event", "counter", "timer"} <= types
    assert rows[0]["type"] == "meta"
    counters = {r["name"]: r["value"] for r in rows if r["type"] == "counter"}
    assert counters["c"] == 7
    events = [r for r in rows if r["type"] == "event"]
    kinds = {e["kind"] for e in events}
    assert "compile" in kinds and "phase" in kinds
    ph = [e for e in events if e["name"] == "ph"][0]
    assert ph["context"] == "entry1"
    # every line is valid standalone JSON (the exporter contract)
    with open(path) as fh:
        for line in fh:
            json.loads(line)


def test_report_table():
    metrics.on()
    metrics.observe("alpha", 0.25)
    metrics.inc("beta", 2)
    rep = metrics.report()
    assert "alpha" in rep and "beta" in rep and "timer" in rep


# ---------------------------------------------------------------------------
# thread safety
# ---------------------------------------------------------------------------


def test_thread_safety_counters_and_timers():
    metrics.on()
    N, M = 8, 200

    def work(i):
        for _ in range(M):
            metrics.inc("shared")
            metrics.observe(f"t{i % 2}", 0.001)
            with metrics.phase(f"p{i % 2}"):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert metrics.counters()["shared"] == N * M
    t0 = metrics.timers()["t0"]
    t1 = metrics.timers()["t1"]
    assert t0["count"] + t1["count"] == N * M
    p0 = metrics.timers()["p0"]
    p1 = metrics.timers()["p1"]
    assert p0["count"] + p1["count"] == N * M


# ---------------------------------------------------------------------------
# wired counters: fallbacks, precision policy
# ---------------------------------------------------------------------------


def test_fallbacks_gathered_counter_increments(rng, grid22):
    """The gathered-fallback route must bump `fallbacks.gathered` (the
    aggregate MULTICHIP dryruns grep for) and the per-route counter."""
    from slate_tpu.drivers import blas3
    from slate_tpu.enums import Side, Uplo
    from slate_tpu.internal import fallbacks
    from slate_tpu.matrix.matrix import Matrix, TriangularMatrix

    metrics.on()
    fallbacks.reset()
    n, nb = 64, 16
    L0 = np.tril(rng.standard_normal((n, n))) + n * np.eye(n)
    L = TriangularMatrix.from_global(L0, nb, grid=grid22, uplo=Uplo.Lower)
    # non-conformable tiles (B mb != A nb): known gathered fallback
    B = Matrix.from_global(rng.standard_normal((n, 4)), 32, grid=grid22)
    blas3.trmm(Side.Left, 1.0, L, B)
    c = metrics.counters()
    assert c.get("fallbacks.gathered") == 1
    assert c.get("fallbacks.trmm") == 1
    # the legacy per-route Counter still ticks independently
    assert fallbacks.counters().get("trmm") == 1
    fallbacks.reset()


def test_precision_activation_counter(rng):
    import slate_tpu as st

    metrics.on()
    A = st.Matrix.from_global(
        rng.standard_normal((32, 32)).astype(np.float32), 16
    )
    B = st.Matrix.from_global(
        rng.standard_normal((32, 32)).astype(np.float32), 16
    )
    C = st.Matrix.from_global(np.zeros((32, 32), np.float32), 16)
    st.gemm(1.0, A, B, 0.0, C)
    assert metrics.counters().get(
        "precision.accurate_matmul_activations", 0) >= 1
    metrics.reset()
    # f64 inputs do not activate the policy
    A64 = st.Matrix.from_global(rng.standard_normal((32, 32)), 16)
    C64 = st.Matrix.from_global(np.zeros((32, 32)), 16)
    st.gemm(1.0, A64, A64, 0.0, C64)
    assert "precision.accurate_matmul_activations" not in metrics.counters()


def test_accurate_matmul_attached_to_eig_drivers():
    """Round-5 regression: @accurate_matmul must sit on he2hb itself (it
    had been displaced onto the _size_bucket_runs helper, silently
    running f32/c64 he2hb at bf16-pass precision)."""
    from slate_tpu.drivers import eig

    for fn in (eig.he2hb, eig.unmtr_he2hb, eig.heev, eig.hegst, eig.hegv):
        assert getattr(fn, "_accurate_matmul", False), fn.__name__
    # the helper is NOT a driver and must not carry the policy wrapper
    assert not hasattr(eig._size_bucket_runs, "_accurate_matmul")


def test_he2hb_f32_band_accuracy(rng):
    """f32 he2hb must preserve the spectrum to f32-parity bounds (guards
    the precision-policy placement end to end on CPU)."""
    import slate_tpu as st
    from slate_tpu.drivers.eig import he2hb

    n, nb = 48, 8
    G = rng.standard_normal((n, n)).astype(np.float32)
    S = ((G + G.T) / 2).astype(np.float32)
    A = st.HermitianMatrix.from_global(S, nb, uplo=st.Uplo.Lower)
    band, V, T = he2hb(A)
    wb = np.linalg.eigvalsh(np.asarray(band.full_global(), dtype=np.float64))
    wa = np.linalg.eigvalsh(S.astype(np.float64))
    scale = max(np.abs(wa).max(), 1.0)
    assert np.abs(wb - wa).max() / scale < 50 * n * np.finfo(np.float32).eps
