"""Process-wide metrics registry: counters, gauges, timers, and a
JSONL event exporter (the observability layer the reference gets from
``include/slate/internal/Trace.hh`` plus its testers' GFLOP/s columns).

Design goals, in order:

1. **Zero overhead when off** — every public hot-path entry point
   (:func:`inc`, :func:`observe`, :class:`phase`, the
   :func:`instrumented` decorator, :func:`instrument_jit` wrappers)
   starts with a single module-level bool check, exactly like
   ``trace.on_`` in the reference's tracer.
2. **Compile-vs-execute split** — :func:`instrument_jit` wraps a
   ``jax.jit`` callable and detects first dispatch per shape signature
   (cache-size growth), so a recompile storm shows up as the
   ``jit.compilations`` counter and per-name ``<name>.compile`` timers
   instead of silently inflating "run" time.
3. **FLOP/byte attribution** — at compile time the wrapper captures
   ``jitted.lower(...).compile().cost_analysis()`` so achieved vs.
   theoretical GFLOP/s needs no hand-derived formulas
   (:func:`costs`, ``flops`` gauges).  Skippable with
   ``SLATE_TPU_METRICS_COST=0`` (the AOT lower/compile is a second
   compile of the same program; cheap on CPU, noticeable on-chip).
4. **One timeline with the device** — an armed :class:`phase` (every
   eager ``@instrumented`` driver call) also opens a
   ``jax.profiler.TraceAnnotation`` of its name, so a profiled run
   shows it on the host plane, on the same clock as the device ops.

Activation::

    SLATE_TPU_METRICS=/path/out.jsonl python app.py   # on + dump at exit
    # or programmatically:
    from slate_tpu.aux import metrics
    metrics.on()
    ...
    print(metrics.report())
    metrics.dump("out.jsonl")

JSONL schema (one object per line): ``{"type": "meta"|"event"|
"counter"|"gauge"|"timer"|"hist"|"cost", ...}``; events carry ``name``,
``kind`` ("phase"|"compile"|"run"), ``t_start`` (seconds since the
metrics epoch), ``dur_s``, ``thread``, and the active :func:`context`
label.  Counters/gauges/timers/histograms are the end-of-run
summaries; ``hist`` lines carry count/min/max/p50/p95/p99 plus the
nonzero ``[le, count]`` bucket rows on the fixed log lattice
(:data:`HIST_EDGES`), so ``tools/latency_report.py`` re-ranks any
percentile from one dump.

Tail latency lives in :class:`Histogram` (:func:`observe_hist`,
:func:`percentile`): fixed log-spaced buckets, so p50/p95/p99 of every
driver phase (``kind="driver"`` phases feed a same-named histogram
automatically) and of the serve queued/execute/total split
(``serve.latency.*``, see serve/service.py) are one call away — means
hide the p99, and Clipper-style SLOs are stated in percentiles.
Per-request timelines are ``aux/spans`` (trace ids + Chrome export);
metric events mirror onto its ring when both layers are on.

The containment layers report through this registry too: serve/ emits
``serve.worker_restarts``, ``serve.breaker_open/half_open/closed``,
``serve.retries`` + the ``serve.retry_backoff_s`` timer,
``serve.invalid_input``, and the ``serve.deadline_miss_queued/_late``
split; ``aux/faults`` counts every injection as
``faults.injected.<site>`` — ``tools/chaos_report.py`` joins the
injected-vs-recovered pair from one JSONL.  The mixed-precision
drivers (drivers/mixed.py over refine/) emit the ``refine.calls`` /
``refine.iterations`` / ``refine.converged`` / ``refine.fallbacks``
counters and the ``refine.residual`` gauge, global and per-routine —
``tools/refine_report.py`` turns one JSONL into the per-routine
iterations/converged/fallback-rate table.
"""

from __future__ import annotations

import atexit
import functools
import json
import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import spans as _spans

_enabled = False
_lock = threading.RLock()
_t0: Optional[float] = None

_counters: Dict[str, float] = {}
_gauges: Dict[str, float] = {}
# name -> [count, total_s, min_s, max_s]
_timers: Dict[str, List[float]] = {}
_hists: Dict[str, "Histogram"] = {}
_events: List[dict] = []
_costs: Dict[str, dict] = {}
_timeline: List[dict] = []
_context = threading.local()

_MAX_EVENTS = 200_000
_MAX_TIMELINE = 100_000
_dropped_events = 0
_dropped_timeline = 0


# ---------------------------------------------------------------------------
# registry control
# ---------------------------------------------------------------------------


def on() -> None:
    """Enable metrics collection (one bool flips; nothing is allocated)."""
    global _enabled, _t0
    with _lock:
        _enabled = True
        if _t0 is None:
            _t0 = time.perf_counter()


def off() -> None:
    global _enabled
    _enabled = False


def is_on() -> bool:
    return _enabled


def reset() -> None:
    """Clear every counter/gauge/timer/event (keeps on/off state)."""
    global _t0, _dropped_events, _dropped_timeline
    with _lock:
        _counters.clear()
        _gauges.clear()
        _timers.clear()
        _hists.clear()
        _events.clear()
        _costs.clear()
        _timeline.clear()
        _dropped_events = 0
        _dropped_timeline = 0
        _t0 = time.perf_counter() if _enabled else None


# ---------------------------------------------------------------------------
# primitives: counters, gauges, timers, events
# ---------------------------------------------------------------------------


def inc(name: str, value: float = 1) -> None:
    """Increment a counter.  No-op (one bool check) when metrics are off."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + value


def gauge(name: str, value: float) -> None:
    """Set a gauge to its latest value."""
    if not _enabled:
        return
    with _lock:
        _gauges[name] = float(value)


def observe(name: str, seconds: float) -> None:
    """Record one duration into the named timer's (count, total, min, max)."""
    if not _enabled:
        return
    with _lock:
        t = _timers.get(name)
        if t is None:
            _timers[name] = [1, seconds, seconds, seconds]
        else:
            t[0] += 1
            t[1] += seconds
            t[2] = min(t[2], seconds)
            t[3] = max(t[3], seconds)


# -- timeline rows (sampled time-series snapshots; the soak plane) ----------


def record_timeline(fields: Dict[str, Any]) -> None:
    """Append one time-series sample row (the soak fabric's
    ``{"type": "timeline"}`` JSONL rows — ``soak/timeline.py`` samples
    ``health()`` + devmon gauges through here on a background cadence).
    Every existing summary line is an END-OF-RUN aggregate; these rows
    are the mid-run trajectory — a quarantine storm that engaged and
    recovered before the dump is invisible to every other row type.
    Bounded like ``_events`` (oldest kept, newest dropped past
    :data:`_MAX_TIMELINE`, drop count surfaced in the meta line); a
    ``t`` stamp relative to the registry clock is added when absent.
    No-op (one bool check) when metrics are off."""
    if not _enabled:
        return
    global _dropped_timeline
    with _lock:
        if len(_timeline) >= _MAX_TIMELINE:
            _dropped_timeline += 1
            return
        row = dict(fields)
        if "t" not in row:
            row["t"] = round(time.perf_counter() - (_t0 or 0.0), 6)
        _timeline.append(row)


def timeline() -> List[dict]:
    """Snapshot of the recorded timeline rows, oldest first."""
    with _lock:
        return [dict(r) for r in _timeline]


# -- bounded-cardinality key families ---------------------------------------


class CappedKeys:
    """Cardinality cap for metric-name families keyed by an UNBOUNDED
    id (matrix fingerprints, tenant ids): the registry is a plain dict,
    so a churning id stream would otherwise leak one key per distinct
    id forever.  The first ``cap`` distinct ids are tracked —
    :meth:`track` returns True and the caller emits its per-id metrics
    — later ids return False and the caller routes the event into one
    overflow counter instead.  Thread-safe; one instance per family
    (serve.factor_cache.fp.*, serve.tenant.*)."""

    __slots__ = ("cap", "_seen", "_lock")

    def __init__(self, cap: int):
        self.cap = int(cap)
        self._seen: set = set()
        self._lock = threading.Lock()

    def track(self, key: str) -> bool:
        """True when ``key`` may emit per-key metrics (already tracked,
        or tracked now because the family is under its cap)."""
        with self._lock:
            if key in self._seen:
                return True
            if len(self._seen) < self.cap:
                self._seen.add(key)
                return True
            return False

    def __len__(self) -> int:
        return len(self._seen)

    def reset(self) -> None:
        with self._lock:
            self._seen.clear()


# -- histograms (fixed log-spaced buckets; the tail-latency primitive) ------

#: bucket lattice: 10 buckets per decade from 1 µs to 1000 s.  FIXED for
#: every histogram so JSONL dumps from different runs/replicas merge
#: bucket-by-bucket (the Prometheus argument), and recording is one
#: log10 + one list increment — no per-observation allocation.
HIST_PER_DECADE = 10
HIST_LO_S = 1e-6
HIST_EDGES = tuple(
    HIST_LO_S * 10.0 ** (i / HIST_PER_DECADE)
    for i in range(9 * HIST_PER_DECADE + 1)
)


class Histogram:
    """Fixed-bucket log-spaced histogram of seconds.  Bucket 0 is the
    underflow (< ``HIST_LO_S``), bucket ``i`` covers
    ``[EDGES[i-1], EDGES[i])``, the last bucket is the overflow.
    ``percentile`` interpolates geometrically inside the winning bucket
    and clamps to the observed min/max, so p50/p95/p99 are accurate to
    one bucket ratio (~26%) worst-case, exact at the extremes."""

    __slots__ = ("counts", "count", "total", "min", "max")

    def __init__(self):
        self.counts = [0] * (len(HIST_EDGES) + 1)
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = 0.0

    def observe(self, seconds: float) -> None:
        v = max(float(seconds), 0.0)
        if v < HIST_LO_S:
            i = 0
        else:
            i = min(
                int(math.log10(v / HIST_LO_S) * HIST_PER_DECADE) + 1,
                len(HIST_EDGES),
            )
        self.counts[i] += 1
        self.count += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)

    @staticmethod
    def percentile_from(counts, p: float, lo: Optional[float] = None,
                        hi: Optional[float] = None) -> Optional[float]:
        """p-th percentile (0..100) from a bucket-count list laid out on
        ``HIST_EDGES`` (the shared static so :class:`deltas` and
        tools/latency_report.py rank windows/dumps the same way)."""
        total = sum(counts)
        if total <= 0:
            return None
        rank = max(1, math.ceil(p / 100.0 * total))
        cum = 0
        for i, k in enumerate(counts):
            cum += k
            if cum >= rank:
                if i == 0:
                    # underflow bucket: the observed min (when known) is
                    # strictly better than the lattice floor
                    est = lo if lo is not None else HIST_LO_S
                elif i >= len(HIST_EDGES):
                    est = hi if hi is not None else HIST_EDGES[-1]
                else:
                    b_lo, b_hi = HIST_EDGES[i - 1], HIST_EDGES[i]
                    frac = (rank - (cum - k)) / max(k, 1)
                    est = b_lo * (b_hi / b_lo) ** frac
                if lo is not None:
                    est = max(est, lo)
                if hi is not None:
                    est = min(est, hi)
                return est
        return None  # unreachable: cum == total >= rank

    def percentile(self, p: float) -> Optional[float]:
        return self.percentile_from(
            self.counts, p,
            lo=(self.min if self.count else None),
            hi=(self.max if self.count else None),
        )

    def summary(self) -> dict:
        return {
            "count": self.count,
            "total_s": round(self.total, 6),
            "min_s": round(self.min, 6) if self.count else 0.0,
            "max_s": round(self.max, 6),
            "p50": round(self.percentile(50) or 0.0, 6),
            "p95": round(self.percentile(95) or 0.0, 6),
            "p99": round(self.percentile(99) or 0.0, 6),
        }

    def bucket_rows(self) -> List[list]:
        """Nonzero ``[le, count]`` rows (le = bucket upper edge;
        ``"inf"`` for the overflow bucket) — the JSONL wire form."""
        rows = []
        for i, k in enumerate(self.counts):
            if not k:
                continue
            le = (
                "inf" if i >= len(HIST_EDGES)
                else float(f"{HIST_EDGES[min(i, len(HIST_EDGES) - 1)]:.9g}")
            )
            rows.append([le, k])
        return rows


def observe_hist(name: str, seconds: float) -> None:
    """Record one duration into the named histogram (log-spaced fixed
    buckets).  One bool check when metrics are off."""
    if not _enabled:
        return
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = Histogram()
        h.observe(seconds)


def percentile(name: str, p: float) -> Optional[float]:
    """p-th percentile (0..100) of a histogram; None when absent."""
    with _lock:
        h = _hists.get(name)
        return h.percentile(p) if h is not None else None


def hist_summary(name: str) -> Optional[dict]:
    """count/total/min/max/p50/p95/p99 of one histogram (None if
    absent) — what ``health()`` surfaces per bucket."""
    with _lock:
        h = _hists.get(name)
        return h.summary() if h is not None and h.count else None


def histograms() -> Dict[str, dict]:
    with _lock:
        return {k: h.summary() for k, h in _hists.items() if h.count}


def _hist_counts() -> Dict[str, tuple]:
    """Raw (counts, count, total) snapshot — the deltas window state."""
    with _lock:
        return {
            k: (tuple(h.counts), h.count, h.total)
            for k, h in _hists.items()
        }


def _emit_event(name: str, start: float, stop: float, kind: str,
                extra: Optional[dict] = None) -> None:
    """Append a timeline event (and mirror it onto the span ring when
    spans are on)."""
    global _dropped_events
    ev = {
        "name": name,
        "kind": kind,
        "t_start": round(start - (_t0 or start), 6),
        "dur_s": round(stop - start, 6),
        "thread": threading.get_ident(),
    }
    ctx = getattr(_context, "label", None)
    if ctx:
        ev["context"] = ctx
    if extra:
        ev["extra"] = extra
    with _lock:
        if len(_events) < _MAX_EVENTS:
            _events.append(ev)
        else:
            _dropped_events += 1
    if _spans.is_on():
        # one flight recorder: metric events (driver phases, per-bucket
        # compile/run dispatches) land on the span ring so a Chrome
        # export shows them in the same lanes as the request spans
        _spans.record(name, start, stop, kind=kind)


class phase:
    """Context manager timing one phase: updates the named timer, appends
    a timeline event, and (if spans are on) a span.  While armed it also
    holds a ``jax.profiler.TraceAnnotation`` of its name open, so a
    profiled run shows the phase on the host plane.

    ``always=True`` measures even with metrics off (for callers that
    need ``.seconds`` as a return value, e.g. heev_staged's stage dict)
    but only *records* when metrics are on.
    """

    __slots__ = ("name", "kind", "always", "seconds", "_start", "_ann")

    def __init__(self, name: str, kind: str = "phase", always: bool = False):
        self.name = name
        self.kind = kind
        self.always = always
        self.seconds = 0.0
        self._start = 0.0
        self._ann = None

    def __enter__(self):
        if _enabled or self.always or _spans.is_on():
            self._ann = _spans.annotation(self.name)
            self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        # _start == 0.0 means nothing was armed at __enter__ (also guards
        # against metrics/spans flipping on mid-block)
        if self._start == 0.0 or not (
            _enabled or self.always or _spans.is_on()
        ):
            return False
        stop = time.perf_counter()
        self.seconds = stop - self._start
        if _enabled:
            observe(self.name, self.seconds)
            if self.kind == "driver":
                # per-driver latency distribution: the factor/solve
                # histograms percentile() and the latency report read
                observe_hist(self.name, self.seconds)
            _emit_event(self.name, self._start, stop, self.kind)
            return False
        if _spans.is_on():
            _spans.record(self.name, self._start, stop, kind=self.kind)
        return False


class context:
    """Tag every event recorded inside with a label (tester/bench entry
    names), so a JSONL from a sweep is attributable per entry."""

    def __init__(self, label: str):
        self.label = label
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_context, "label", None)
        _context.label = self.label
        return self

    def __exit__(self, *exc):
        _context.label = self._prev
        return False


class deltas:
    """Counter-delta window: snapshot on enter, ``d.get(name)`` reads the
    live increment since.  The serving tests/bench use it to assert
    "no compiles in steady state" without global resets::

        with metrics.deltas() as d:
            ...
        assert d.get("jit.compilations") == 0
    """

    def __enter__(self):
        self._before = counters()
        self._hbefore = _hist_counts()
        return self

    def __exit__(self, *exc):
        return False

    def get(self, name: str) -> float:
        return counters().get(name, 0) - self._before.get(name, 0)

    def hist(self, name: str) -> Optional[dict]:
        """Windowed histogram stats: count/total/p50/p95/p99 over the
        observations recorded since __enter__ (bucket-count deltas —
        bench entries report per-entry tail latency without a global
        reset).  None when nothing landed in the window."""
        cur = _hist_counts().get(name)
        if cur is None:
            return None
        before = self._hbefore.get(name)
        if before is None:
            counts = list(cur[0])
            dc, dt = cur[1], cur[2]
        else:
            counts = [a - b for a, b in zip(cur[0], before[0])]
            dc, dt = cur[1] - before[1], cur[2] - before[2]
        if dc <= 0:
            return None
        return {
            "count": dc,
            "total_s": round(dt, 6),
            "p50": round(Histogram.percentile_from(counts, 50) or 0.0, 6),
            "p95": round(Histogram.percentile_from(counts, 95) or 0.0, 6),
            "p99": round(Histogram.percentile_from(counts, 99) or 0.0, 6),
        }

    def all(self) -> Dict[str, float]:
        now = counters()
        keys = set(now) | set(self._before)
        out = {
            k: now.get(k, 0) - self._before.get(k, 0) for k in sorted(keys)
        }
        return {k: v for k, v in out.items() if v}


def instrumented(name: str) -> Callable:
    """Decorator: record one phase per driver call (wall time, the span
    ring, the profiler's host plane).  With metrics AND spans off, the
    overhead is one bool check per call."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kw):
            if not _enabled and not _spans.is_on():
                return fn(*args, **kw)
            import jax

            # calls inlined into an outer jit trace would record trace
            # wall time as a driver phase — pass through with a counter
            # instead (same rule as instrument_jit/gated_jit)
            if any(isinstance(a, jax.core.Tracer)
                   for a in jax.tree_util.tree_leaves((args, kw))):
                inc(f"{name}.traced_calls")
                return fn(*args, **kw)
            with phase(name, kind="driver"):
                return fn(*args, **kw)

        wrapper.__wrapped__ = fn
        return wrapper

    return deco


# ---------------------------------------------------------------------------
# jit instrumentation: compile/run split + cost_analysis attribution
# ---------------------------------------------------------------------------


def _capture_cost_enabled() -> bool:
    v = os.environ.get("SLATE_TPU_METRICS_COST")
    if v is not None:
        return v not in ("", "0")
    # default: on for CPU (the AOT second compile is cheap), OFF on
    # accelerators — a second compile of a large program there costs as
    # much as the first, mid-entry, where no time-budget check can
    # fire.  SLATE_TPU_METRICS_COST=1 opts back in explicitly.
    try:
        import jax

        return jax.default_backend() == "cpu"
    except Exception:  # noqa: BLE001 — attribution must never break a run
        return True


def _cost_analysis(jitted, args, kw) -> Optional[dict]:
    """Cost/memory record via the AOT path (lower -> compile ->
    devmon.analyze_compiled — ONE extraction shared with the device
    telemetry plane, so this legacy capture emits the same record
    schema: flops/bytes plus the memory_analysis fields and the
    device kind the report tools key peaks on).  This compiles the
    program a second time (the dispatch cache is not shared with
    AOT), so it runs at most once per (name, signature) and only when
    SLATE_TPU_METRICS_COST is on."""
    try:
        from . import devmon  # lazy: devmon imports metrics at module load

        out = devmon.analyze_compiled(jitted.lower(*args, **kw).compile())
        if out:
            out["device_kind"] = devmon.default_device_kind()
        return out
    except Exception:  # noqa: BLE001 — attribution must never break a run
        return None


def instrument_jit(jitted, name: str, capture_cost: bool = True,
                   precompiled: bool = False):
    """Wrap a ``jax.jit`` callable: per dispatch, record wall time into
    ``<name>.compile`` (first dispatch for a new shape signature — the
    compile+trace+execute wall) or ``<name>.run`` (cached executable),
    count ``jit.compilations``, and capture ``cost_analysis`` flops/bytes
    at compile time.  Tracer arguments (calls inlined into an outer jit)
    pass straight through with only a ``<name>.traced_calls`` counter.

    ``precompiled=True`` declares the callable an already-built AOT
    executable (a ``Lowered.compile()`` result): every dispatch is a
    run, never a compile — the caller owns the compile accounting
    (bench.py's devmon capture path records it explicitly)."""
    seen_sigs = set()  # fallback signature tracking if _cache_size is absent

    def _cache_size():
        f = getattr(jitted, "_cache_size", None)
        if f is not None:
            try:
                return f()
            except Exception:  # noqa: BLE001
                return None
        return None

    @functools.wraps(getattr(jitted, "__wrapped__", jitted))
    def wrapper(*args, **kw):
        if not _enabled:
            return jitted(*args, **kw)
        import jax

        if any(isinstance(a, jax.core.Tracer)
               for a in jax.tree_util.tree_leaves((args, kw))):
            inc(f"{name}.traced_calls")
            return jitted(*args, **kw)
        before = _cache_size()
        start = time.perf_counter()
        out = jitted(*args, **kw)
        # execution barrier: without it an async backend returns a future
        # in ~1 ms and ".run" would time dispatch, not the kernel.  This
        # sync point exists only with metrics ON (the off path is
        # untouched).
        try:
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 — metrics must never break a run
            pass
        stop = time.perf_counter()
        after = _cache_size()
        if precompiled:
            compiled = False
        elif after is not None:
            compiled = after > (before or 0)
        else:
            sig = tuple(
                (getattr(l, "shape", None), str(getattr(l, "dtype", type(l))))
                for l in jax.tree_util.tree_leaves((args, kw))
            )
            compiled = sig not in seen_sigs
            seen_sigs.add(sig)
        if compiled:
            inc("jit.compilations")
            inc(f"{name}.compilations")
            observe(f"{name}.compile", stop - start)
            extra = None
            if capture_cost and _capture_cost_enabled():
                cost = _cost_analysis(jitted, args, kw)
                if cost:
                    # one canonical store-and-gauge path with the
                    # devmon capture.  XLA's -1 "unknowable cost"
                    # sentinel is dropped by the shared extractor
                    # (devmon.analyze_compiled), so an absent key —
                    # not a raw -1 — is the registry's no-data marker
                    record_cost(name, cost)
                    extra = cost
            _emit_event(name, start, stop, "compile", extra)
        else:
            observe(f"{name}.run", stop - start)
            _emit_event(name, start, stop, "run")
        return out

    wrapper.jitted = jitted
    return wrapper


def jit(fn=None, *, name: Optional[str] = None, capture_cost: bool = True,
        **jit_kw):
    """``jax.jit`` drop-in that returns an instrumented callable:
    ``metrics.jit(f, name="potrf.kernel", static_argnums=(1,))``."""
    if fn is None:
        return functools.partial(jit, name=name, capture_cost=capture_cost,
                                 **jit_kw)
    import jax

    return instrument_jit(
        jax.jit(fn, **jit_kw),
        name or getattr(fn, "__name__", "jit"),
        capture_cost=capture_cost,
    )


def gated_jit(fn, name: str, donate_argnums=(), **jit_kw):
    """Metrics-gated jit for eager kernel call sites: with metrics OFF
    (or under tracing) the original unjitted function runs, bit-identical
    to the un-instrumented code; with metrics ON, dispatch goes through a
    lazily created instrumented jit so the compile/run split and
    cost_analysis land under `name`.  One shared helper so the gate logic
    (Tracer passthrough, lazy creation) lives in one place.

    ``donate_argnums`` is applied only on non-CPU backends (resolved at
    first dispatch): XLA:CPU does not implement donation and would warn
    on every call.  Callers must pass freshly built temporaries in
    donated positions — a donated buffer is invalidated after the call
    (drivers pass padded/mirrored copies, never user-held storage)."""
    holder: list = []

    @functools.wraps(fn)
    def gate(*args, **kw):
        if not _enabled:
            return fn(*args, **kw)
        import jax

        if any(isinstance(a, jax.core.Tracer)
               for a in jax.tree_util.tree_leaves((args, kw))):
            return fn(*args, **kw)
        if not holder:
            with _lock:  # double-check: racing first calls must not
                if not holder:  # build (and compile) the jit twice
                    kwj = dict(jit_kw)
                    if donate_argnums and jax.default_backend() != "cpu":
                        kwj["donate_argnums"] = donate_argnums
                    holder.append(instrument_jit(jax.jit(fn, **kwj), name))
        return holder[0](*args, **kw)

    return gate


def record_cost(name: str, cost: dict) -> None:
    """Record one executable's cost/memory attribution under ``name``
    (the devmon capture path: flops / bytes_accessed plus the
    memory_analysis argument/output/temp/peak byte fields), so the
    JSONL dump carries a ``{"type": "cost", ...}`` row per executable
    and :func:`costs` serves it to bench.py / the report tools.  Also
    mirrors flops/bytes onto the same gauges :func:`instrument_jit`'s
    capture would have set.  One bool check when metrics are off."""
    if not _enabled:
        return
    with _lock:
        _costs[name] = dict(cost)
    if cost.get("flops", -1) > 0:
        gauge(f"{name}.flops", cost["flops"])
    if cost.get("bytes_accessed") is not None:
        gauge(f"{name}.bytes_accessed", cost["bytes_accessed"])
    if cost.get("peak_bytes") is not None:
        gauge(f"{name}.peak_bytes", cost["peak_bytes"])


def record_factor_flops(routine: str, fl: dict) -> None:
    """Feed one factorization's schedule accounting (a dict with
    ``model``/``exec`` FLOP counts and a ``units`` shape set — see
    ops/*_kernels ``*_schedule_flops``) into the ``factor.flops_model``
    / ``factor.flops_exec`` counter pair, global and per-routine, plus
    a ``factor.<routine>.compile_units`` gauge — the waste ratio of
    every factorization schedule is then one counter read away."""
    if not _enabled:
        return
    inc("factor.flops_model", fl["model"])
    inc("factor.flops_exec", fl["exec"])
    inc(f"factor.{routine}.flops_model", fl["model"])
    inc(f"factor.{routine}.flops_exec", fl["exec"])
    gauge(f"factor.{routine}.compile_units", len(fl["units"]))


# ---------------------------------------------------------------------------
# snapshots, report, JSONL export
# ---------------------------------------------------------------------------


def counters() -> Dict[str, float]:
    with _lock:
        return dict(_counters)


def gauges() -> Dict[str, float]:
    with _lock:
        return dict(_gauges)


def timers() -> Dict[str, dict]:
    with _lock:
        return {
            k: {"count": int(v[0]), "total_s": v[1], "min_s": v[2],
                "max_s": v[3]}
            for k, v in _timers.items()
        }


def costs() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _costs.items()}


def summary() -> dict:
    """One structured dict with everything (bench/tester per-entry use)."""
    return {
        "counters": counters(),
        "gauges": gauges(),
        "timers": {
            k: {kk: (round(vv, 6) if isinstance(vv, float) else vv)
                for kk, vv in v.items()}
            for k, v in timers().items()
        },
        "histograms": histograms(),
        "costs": costs(),
    }


def report() -> str:
    """Human-readable summary table: timers (with achieved GFLOP/s where
    a cost_analysis capture matched the timer name), then counters."""
    with _lock:
        tsnap = {k: list(v) for k, v in _timers.items()}
        csnap = dict(_counters)
        costsnap = {k: dict(v) for k, v in _costs.items()}
        hsnap = {k: h.summary() for k, h in _hists.items() if h.count}
    lines = []
    if tsnap:
        hdr = (f"{'timer':40} {'count':>6} {'total(s)':>10} {'mean(s)':>10} "
               f"{'max(s)':>10} {'GFLOP/s':>9}")
        lines.append(hdr)
        lines.append("-" * len(hdr))
        for name in sorted(tsnap, key=lambda k: -tsnap[k][1]):
            cnt, total, mn, mx = tsnap[name]
            base = name.rsplit(".", 1)[0] if name.endswith((".run", ".compile")) else name
            gf = ""
            cost = costsnap.get(base)
            # rate only for run-time entries (compile wall is not a rate),
            # and only when the name compiled exactly once — with several
            # shape signatures the stored cost belongs to the LAST
            # compile, and flops(last)/mean(all shapes) is no real rate
            if (cost and cost.get("flops", -1) > 0
                    and not name.endswith(".compile")
                    and csnap.get(f"{base}.compilations", 0) == 1):
                mean = total / max(cnt, 1)
                if mean > 0:
                    gf = f"{cost['flops'] / mean / 1e9:9.1f}"
            lines.append(
                f"{name:40} {int(cnt):6d} {total:10.4f} "
                f"{total / max(cnt, 1):10.4f} {mx:10.4f} {gf:>9}"
            )
    if hsnap:
        lines.append("")
        hdr = (f"{'histogram':44} {'count':>6} {'p50(s)':>10} "
               f"{'p95(s)':>10} {'p99(s)':>10} {'max(s)':>10}")
        lines.append(hdr)
        lines.append("-" * len(hdr))
        for name in sorted(hsnap):
            h = hsnap[name]
            lines.append(
                f"{name:44} {h['count']:6d} {h['p50']:10.4f} "
                f"{h['p95']:10.4f} {h['p99']:10.4f} {h['max_s']:10.4f}"
            )
    if csnap:
        lines.append("")
        lines.append(f"{'counter':50} {'value':>12}")
        lines.append("-" * 63)
        for name in sorted(csnap):
            v = csnap[name]
            vs = f"{int(v)}" if float(v).is_integer() else f"{v:.3g}"
            lines.append(f"{name:50} {vs:>12}")
    return "\n".join(lines) if lines else "(no metrics recorded)"


def dump(path: Optional[str] = None) -> Optional[str]:
    """Write the full registry as JSONL: a meta line, every timeline
    event, then counter/gauge/timer/cost summary lines.  ``path``
    defaults to ``$SLATE_TPU_METRICS``.  Returns the path written (or
    None if there is nowhere to write)."""
    path = path or os.environ.get("SLATE_TPU_METRICS")
    if not path:
        return None
    with _lock:
        events = [dict(e) for e in _events]
        csnap = dict(_counters)
        gsnap = dict(_gauges)
        tsnap = {k: list(v) for k, v in _timers.items()}
        hsnap = {
            k: (h.summary(), h.bucket_rows())
            for k, h in _hists.items() if h.count
        }
        costsnap = {k: dict(v) for k, v in _costs.items()}
        tlsnap = [dict(r) for r in _timeline]
        dropped = _dropped_events
        dropped_tl = _dropped_timeline
    with open(path, "w") as f:
        meta = {"type": "meta", "schema": 1, "unix_time": time.time(),
                "pid": os.getpid()}
        if dropped:
            meta["dropped_events"] = dropped
        if dropped_tl:
            meta["dropped_timeline"] = dropped_tl
        f.write(json.dumps(meta) + "\n")
        for ev in events:
            f.write(json.dumps({"type": "event", **ev}) + "\n")
        for row in tlsnap:
            f.write(json.dumps({"type": "timeline", **row}) + "\n")
        for name in sorted(csnap):
            f.write(json.dumps(
                {"type": "counter", "name": name, "value": csnap[name]}
            ) + "\n")
        for name in sorted(gsnap):
            f.write(json.dumps(
                {"type": "gauge", "name": name, "value": gsnap[name]}
            ) + "\n")
        for name in sorted(tsnap):
            cnt, total, mn, mx = tsnap[name]
            f.write(json.dumps({
                "type": "timer", "name": name, "count": int(cnt),
                "total_s": round(total, 6), "min_s": round(mn, 6),
                "max_s": round(mx, 6),
            }) + "\n")
        for name in sorted(hsnap):
            summ, buckets = hsnap[name]
            f.write(json.dumps({
                "type": "hist", "name": name, **summ, "buckets": buckets,
            }) + "\n")
        for name in sorted(costsnap):
            f.write(json.dumps(
                {"type": "cost", "name": name, **costsnap[name]}
            ) + "\n")
    return path


def load_jsonl(path: str) -> List[dict]:
    """Parse a metrics JSONL back into a list of dicts (round-trip
    helper for tests and analysis notebooks)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# ---------------------------------------------------------------------------
# measurement helpers (the shared methodology of bench.py and tools/)
# ---------------------------------------------------------------------------


def measure_best(fn, args, trials: int = 3, perturb=None,
                 name: Optional[str] = None) -> float:
    """Best-of wall time of a jitted scalarized call with HOST READBACK
    of one scalar as the barrier.  ``perturb(args, t) -> args`` varies
    the inputs per trial so no layer can serve a cached result.
    Records ``<name>.best_s`` as a gauge when on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def _scal(leaf):
        x = jnp.asarray(leaf).ravel()
        return x[0].astype(jnp.float64) + x[-1].astype(jnp.float64)

    def scalarized(*a):
        return sum(_scal(l) for l in jax.tree_util.tree_leaves(fn(*a)))

    sj = instrument_jit(jax.jit(scalarized), name or "measure_best")
    # warmup/compile with a distinct perturbation
    float(np.asarray(sj(*(perturb(args, 17) if perturb else args))))
    best = float("inf")
    for t in range(trials):
        a = args if perturb is None else perturb(args, t)
        jax.block_until_ready(a)
        t0 = time.perf_counter()
        float(np.asarray(sj(*a)))
        best = min(best, time.perf_counter() - t0)
    if name:
        gauge(f"{name}.best_s", best)
    return best


def measure_steady(fn, *args, name: Optional[str] = None):
    """Steady-state (second-call) wall time with host readback barrier:
    compile+run once, rerun on perturbed input (so no layer serves a
    cached result), read one scalar back.  Returns ``(seconds,
    output)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def run(a):
        out = fn(*a)
        s = jax.tree_util.tree_leaves(out)[0].ravel()[-1]
        float(np.asarray(s))
        return out

    run(args)
    a2 = jax.tree_util.tree_map(
        lambda x: x + jnp.asarray(1e-14, x.dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        args,
    )
    t0 = time.perf_counter()
    out = run(a2)
    dt = time.perf_counter() - t0
    if name:
        gauge(f"{name}.steady_s", dt)
    return dt, out


# ---------------------------------------------------------------------------
# env activation: SLATE_TPU_METRICS=/path/out.jsonl
# ---------------------------------------------------------------------------

if os.environ.get("SLATE_TPU_METRICS"):
    on()
    atexit.register(dump)
