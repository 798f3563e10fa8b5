"""Request-lifecycle tracing tests: aux/spans.py (ring-buffer bounds,
nesting/ids, zero-overhead-off, Chrome export schema round-trip),
the serve lifecycle span chain (admit -> queued -> execute -> deliver),
the chaos-integrated retry/backoff span, and the
SLO surface (oldest_queued_s gauge, slo_burn tiers, health latency)."""

import json
import threading

import numpy as np
import pytest

from slate_tpu.aux import faults, metrics, spans


@pytest.fixture(autouse=True)
def _clean_registry():
    """Every test starts and ends with spans/metrics/faults off and
    empty."""
    for mod in (metrics, spans):
        mod.off()
    metrics.reset()
    spans.clear()
    faults.reset()
    yield
    for mod in (metrics, spans):
        mod.off()
    metrics.reset()
    spans.clear()
    faults.reset()


def _service(**kw):
    from slate_tpu.serve.cache import ExecutableCache
    from slate_tpu.serve.service import SolverService

    cfg = dict(
        cache=ExecutableCache(manifest_path=None), batch_max=4,
        batch_window_s=0.002, dim_floor=16, nrhs_floor=4,
    )
    cfg.update(kw)
    return SolverService(**cfg)


def _prob(n, seed=0):
    r = np.random.default_rng(seed)
    return r.standard_normal((n, n)) + n * np.eye(n), r.standard_normal((n, 2))


# ---------------------------------------------------------------------------
# ring buffer: bounds, eviction, clear
# ---------------------------------------------------------------------------


def test_ring_buffer_bounded():
    spans.on(ring=8)
    for i in range(20):
        with spans.span(f"s{i}"):
            pass
    snap = spans.snapshot()
    assert len(snap) == 8  # flight recorder: last N only
    assert [s.name for s in snap] == [f"s{i}" for i in range(12, 20)]
    assert spans.evicted() == 12
    spans.clear()
    assert spans.snapshot() == [] and spans.evicted() == 0


def test_ring_resize_on_reenable():
    spans.on(ring=4)
    assert spans.capacity() == 4
    spans.on(ring=16)
    assert spans.capacity() == 16
    spans.on()  # bare re-enable keeps the configured capacity
    assert spans.capacity() == 16


# ---------------------------------------------------------------------------
# nesting, ids, annotation
# ---------------------------------------------------------------------------


def test_nesting_parent_child_ids():
    spans.on()
    tr = spans.new_trace()
    with spans.span("outer", trace=tr) as o:
        assert spans.current() is o
        with spans.span("inner") as i:
            assert spans.current() is i
            spans.annotate(depth=2)
    assert spans.current() is None
    inner = next(s for s in spans.snapshot() if s.name == "inner")
    outer = next(s for s in spans.snapshot() if s.name == "outer")
    assert inner.parent == outer.sid and inner.sid != outer.sid
    assert inner.trace == tr  # trace id inherited through nesting
    assert inner.attrs["depth"] == 2
    assert outer.t_start <= inner.t_start <= inner.t_end <= outer.t_end


def test_trace_ids_unique():
    spans.on()
    ids = {spans.new_trace() for _ in range(100)}
    assert len(ids) == 100


def test_manual_start_end_cross_thread():
    spans.on()
    sp = spans.start("lifecycle", trace=spans.new_trace(), lane="worker")
    done = threading.Event()

    def finisher():
        spans.end(sp, outcome="ok")
        done.set()

    threading.Thread(target=finisher).start()
    assert done.wait(5)
    rec = spans.snapshot()[-1]
    assert rec is sp and rec.attrs["outcome"] == "ok"
    # end() is idempotent: a second resolution must not double-record
    spans.end(sp, outcome="late")
    assert len(spans.snapshot()) == 1
    assert sp.attrs["outcome"] == "ok"


def test_exception_stamps_outcome():
    spans.on()
    with pytest.raises(ValueError):
        with spans.span("work"):
            raise ValueError("boom")
    assert spans.snapshot()[-1].attrs["outcome"] == "ValueError"


# ---------------------------------------------------------------------------
# zero overhead off
# ---------------------------------------------------------------------------


def test_off_records_nothing_and_returns_none():
    assert not spans.is_on()
    assert spans.start("x") is None
    spans.end(None)
    assert spans.record("x", 0.0, 1.0) is None
    assert spans.event("x") is None
    assert spans.current() is None
    spans.annotate(a=1)
    with spans.span("y") as sp:
        assert sp is None
    spans.on()
    assert spans.snapshot() == []  # the off-path calls left no trace


def test_serve_stream_zero_span_overhead_off(tmp_path):
    """With spans AND metrics off, a serve stream records nothing: the
    lifecycle call sites cost one bool each (the PR 2/PR 4
    zero-overhead criterion extended to the tracing layer)."""
    svc = _service()
    A, B = _prob(12)
    futs = [svc.submit("gesv", A, B) for _ in range(4)]
    for f in futs:
        assert np.all(np.isfinite(f.result(timeout=300)))
    svc.stop()
    spans.on()
    metrics.on()
    assert spans.snapshot() == []
    assert not metrics.histograms()


# ---------------------------------------------------------------------------
# Chrome export schema
# ---------------------------------------------------------------------------


def test_chrome_export_schema_round_trip(tmp_path):
    spans.on()
    tr = spans.new_trace()
    root = spans.start("request", trace=tr, lane="client", routine="gesv")
    with spans.span("child", trace=tr, lane="replica-0"):
        pass
    spans.event("breaker_open", trace=tr, lane="replica-0", bucket="b")
    spans.end(root, outcome="ok")
    path = str(tmp_path / "t.json")
    assert spans.export_chrome(path) == path
    data = json.load(open(path))
    evs = data["traceEvents"]
    assert isinstance(evs, list) and data["displayTimeUnit"] == "ms"
    metas = [e for e in evs if e["ph"] == "M"]
    lanes = {e["args"]["name"] for e in metas}
    assert {"client", "replica-0"} <= lanes
    complete = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert {"request", "child"} <= set(complete)
    req = complete["request"]
    assert req["args"]["trace"] == tr
    assert req["args"]["outcome"] == "ok"
    assert req["dur"] >= 0 and req["ts"] >= 0  # microseconds, rebased
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["name"] == "breaker_open" and inst["args"]["bucket"] == "b"
    # tids are stable ints shared per lane
    assert complete["child"]["tid"] == inst["tid"]


def test_instrumented_driver_lands_on_ring():
    """@metrics.instrumented mirrors driver phases onto the span ring
    (one flight recorder), with metrics on or off."""

    @metrics.instrumented("probe_driver")
    def fn():
        return 7

    spans.on()
    assert fn() == 7
    assert "probe_driver" in {s.name for s in spans.snapshot()}


# ---------------------------------------------------------------------------
# serve lifecycle chain
# ---------------------------------------------------------------------------


def test_serve_request_span_chain_complete():
    spans.on(ring=4096)
    svc = _service()
    A, B = _prob(12)
    futs = [svc.submit("gesv", A, B) for _ in range(6)]
    for f in futs:
        assert np.all(np.isfinite(f.result(timeout=300)))
    svc.stop()
    bytr = spans.by_trace()
    roots = [
        sps for sps in bytr.values()
        if any(s.name == "request" for s in sps)
    ]
    assert len(roots) == 6  # one trace per request, no orphans
    for sps in roots:
        names = {s.name for s in sps}
        assert {"request", "admit", "queued"} <= names
        assert "execute" in names or "direct" in names
        root = next(s for s in sps if s.name == "request")
        assert root.attrs["outcome"] == "ok"
        assert root.attrs["bucket"] == "gesv.16x16x4.float64"
        # children nest inside the root interval
        for s in sps:
            if s.name in ("admit", "queued", "execute"):
                assert s.t_start >= root.t_start - 1e-6
                assert s.t_end <= root.t_end + 1e-6


def test_rejected_admission_closes_chain():
    spans.on()
    svc = _service(max_queue=1, start=False)  # paused: everything queues
    A, B = _prob(12)
    svc.submit("gesv", A, B)
    from slate_tpu.serve.service import Rejected

    with pytest.raises(Rejected):
        svc.submit("gesv", A, B)
    roots = [s for s in spans.snapshot() if s.name == "request"]
    assert roots and roots[-1].attrs["outcome"] == "Rejected"
    svc.stop()


def test_chaos_retry_span_shows_backoff_interval():
    """ISSUE satellite: a retried request's trace must carry a backoff
    span whose interval matches the recorded decorrelated-jitter delay
    — 'this request was slow because it sat out a retry backoff' is
    answerable from the flight recorder alone."""
    spans.on(ring=4096)
    metrics.on()
    svc = _service(retry_backoff_s=0.01, retry_seed=3)
    faults.arm("execute", once=True)  # exactly one batched failure
    faults.on()
    A, B = _prob(12)
    X = svc.submit("gesv", A, B, retries=2).result(timeout=300)
    assert np.all(np.isfinite(X))
    svc.stop()
    back = [s for s in spans.snapshot() if s.name == "backoff"]
    assert len(back) == 1
    sp = back[0]
    assert sp.trace is not None and sp.parent is not None
    assert sp.attrs["retries_left"] == 1
    # the span IS the planned backoff window, and it matches the
    # serve.retry_backoff_s timer the metrics layer recorded
    t = metrics.timers()["serve.retry_backoff_s"]
    assert sp.attrs["backoff_s"] == pytest.approx(t["total_s"], rel=1e-3)
    assert sp.dur_s == pytest.approx(sp.attrs["backoff_s"], rel=1e-3)
    # the retried request still delivered with a complete chain
    chain = {s.name for s in spans.by_trace()[sp.trace]}
    assert {"request", "admit", "queued", "execute", "backoff"} <= chain
    # the queued histogram saw the request ONCE (its second wait was
    # backoff, not queueing — re-observing would inflate queued p99
    # and break the queued-vs-execute subtraction)
    q = metrics.hist_summary("serve.latency.gesv.16x16x4.float64.queued")
    t = metrics.hist_summary("serve.latency.gesv.16x16x4.float64.total")
    assert q["count"] == t["count"] == 1


def test_refine_iterations_annotate_enclosing_span():
    """The mixed drivers stamp iteration counts onto the caller's span
    (spans.span parents explicitly too); with no enclosing span the
    count still lands on the ring as a `refine` instant."""
    import jax

    jax.config.update("jax_enable_x64", True)
    import slate_tpu as st
    from slate_tpu.matrix.matrix import Matrix

    spans.on()
    rng = np.random.default_rng(0)
    A = rng.standard_normal((16, 16)) + 16 * np.eye(16)
    B = rng.standard_normal((16, 2))
    with spans.span("solve") as sp:
        _X, info, iters = st.gesv_mixed(
            Matrix.from_global(A, 8), Matrix.from_global(B, 8)
        )
    assert int(info) == 0
    assert sp.attrs["refine_iters"] == iters
    assert sp.attrs["refine_converged"] is True
    spans.clear()
    st.gesv_mixed(Matrix.from_global(A, 8), Matrix.from_global(B, 8))
    inst = [s for s in spans.snapshot() if s.name == "refine"]
    assert inst and inst[0].attrs["refine_iters"] == iters


# ---------------------------------------------------------------------------
# SLO surface: oldest-queued gauge, burn tiers, health latency
# ---------------------------------------------------------------------------


def test_oldest_queued_gauge_exposes_stuck_head_of_line():
    metrics.on()
    svc = _service(start=False)  # no worker: requests sit queued
    A, B = _prob(12)
    import time as _t

    svc.submit("gesv", A, B)
    _t.sleep(0.05)
    svc.submit("gesv", A, B)  # admission re-gauges the queues
    g = metrics.gauges()["serve.replica.0.oldest_queued_s"]
    assert g >= 0.05  # the HEAD's age, not the newest request's
    h = svc.health()
    assert h["replicas"][0]["oldest_queued_s"] >= g
    svc.stop()
    assert metrics.gauges()["serve.replica.0.oldest_queued_s"] == 0.0


def test_health_latency_percentiles_and_slo_burn():
    metrics.on()
    svc = _service()
    A, B = _prob(12)
    futs = [svc.submit("gesv", A, B, deadline=300.0) for _ in range(5)]
    for f in futs:
        assert np.all(np.isfinite(f.result(timeout=300)))
    h = svc.health()
    lat = h["latency"]["gesv.16x16x4.float64"]
    assert lat["count"] == 5
    assert 0 < lat["p50"] <= lat["p95"] <= lat["p99"]
    # generous deadlines: every request lands in the healthy (<=50%)
    # tier — only the requests denominator ticks
    assert h["slo_burn"]["requests"] == 5
    assert "exhausted" not in h["slo_burn"]
    svc.stop()


def test_serve_latency_split_counts_align():
    metrics.on()
    svc = _service()
    A, B = _prob(12)
    futs = [svc.submit("gesv", A, B) for _ in range(7)]
    for f in futs:
        f.result(timeout=300)
    svc.stop()
    lbl = "gesv.16x16x4.float64"
    hh = metrics.histograms()
    q = hh[f"serve.latency.{lbl}.queued"]
    x = hh[f"serve.latency.{lbl}.execute"]
    t = hh[f"serve.latency.{lbl}.total"]
    rep = hh["serve.latency.replica.0.total"]
    assert q["count"] == x["count"] == t["count"] == rep["count"] == 7
    # queued + execute <= total on every percentile-free aggregate
    assert t["total_s"] >= x["total_s"]
