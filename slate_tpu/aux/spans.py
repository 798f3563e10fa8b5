"""Request-scoped span tracing: trace ids, parent/child spans, a
bounded ring-buffer flight recorder, and Chrome trace-event export.

This is the per-request half of the observability layer (the SLATE
SC'19 tracer renders per-task timelines because aggregate counters
cannot explain where one solve spent its time; Dapper is the
distributed ancestor — see PAPERS.md "Tracing").  ``aux/metrics``
answers "how much, on average"; this module answers "where did THIS
request's time go": every serve request gets a **trace id**, and the
lifecycle stages (admit -> queued -> coalesce -> execute | direct ->
retry/backoff -> deliver) record **spans** — named intervals with
monotonic timestamps, a parent link, a lane (replica/worker), and an
attrs dict (bucket label, backoff interval, refine iteration count,
artifact-restore outcome, ...).

Design rules, same as metrics/trace/faults:

1. **Zero overhead off** — every entry point starts with one
   module-level bool check; OFF is the default.  The serve hot path
   pays exactly one branch per call site when tracing is disabled.
2. **Bounded memory** — completed spans land in a ring buffer
   (``collections.deque(maxlen=ring)``): a long-running service keeps
   the LAST N spans, flight-recorder style, and ``evicted()`` counts
   what scrolled off.  Nothing ever grows without bound.
3. **Crash-safe cross-thread spans** — a span is appended to the ring
   only when it *ends* (Chrome "complete" events); a request whose
   root span never ended is visible as an orphan in the export, which
   is the bug signal, not a formatting problem.

Activation::

    SLATE_TPU_TRACE_RING=8192 python app.py   # on at import, ring of 8192
    # or programmatically:
    from slate_tpu.aux import spans
    spans.on(ring=4096)
    ...
    spans.export_chrome("trace.json")   # load in Perfetto / chrome://tracing

Span taxonomy the serve tier emits (service.py / cache.py):
``request`` (root: admit -> deliver, attrs ``routine``/``bucket``/
``outcome`` — plus ``tenant``/``priority`` on a tenancy-enabled
service), ``admit``, ``queued`` (ends at dispatch; attrs
``replica``), ``coalesce``, ``execute`` (the padded-batch dispatch;
attrs ``batch``), ``direct`` (fallback / keyless path), ``backoff``
(the planned retry delay; attrs ``backoff_s``/``retries_left``),
``build`` (cold executable build; attrs ``origin``), ``restore``
(artifact-restore entries; attrs ``outcome``/``origin``), and instant
events ``breaker_open``/``breaker_half_open``/``breaker_closed`` plus
the admission plane's ``shed`` (attrs ``tenant``/``priority``/
``level``), ``overload_enter``/``overload_exit`` (attrs ``level``/
``sheds``), and ``adaptive_window`` (attrs ``bucket``/``window_s``/
``direction`` — the AIMD trajectory, one instant per decision).
Driver phases (``@metrics.instrumented``) mirror onto the same ring.

On the profiler's clock: a :class:`span` block, and every armed
``metrics.phase`` (so every eager ``@instrumented`` driver call), also
holds a ``jax.profiler.TraceAnnotation`` of its name open.  In a
profiled run these sit on the host plane beside the device ops, and
name what the host was doing in a gap between them.  Spans opened on
one thread and ended on another (``queued``, ``request``) stay on the
ring only: a profiler annotation must end on the thread that began it.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

#: default flight-recorder capacity for programmatic on()
DEFAULT_RING = 4096

RING_ENV = "SLATE_TPU_TRACE_RING"

_enabled = False
_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_RING)
_evicted = 0
_t0: Optional[float] = None

_ids = itertools.count(1)  # span ids (next() is atomic under the GIL)
_trace_ids = itertools.count(1)
_tls = threading.local()  # per-thread stack of context-managed spans


def now() -> float:
    """The span clock (monotonic; shared with metrics phases)."""
    return time.perf_counter()


def annotation(name: str):
    """An open ``jax.profiler.TraceAnnotation(name)``; the caller exits
    it on the same thread.  Cheap when no profile is being taken."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name)
    ann.__enter__()
    return ann


class Span:
    """One named interval: ``[t_start, t_end]`` on a thread/lane, with
    a trace id, a parent span id, and an attrs dict.  Mutable until
    :func:`end` stamps ``t_end`` and pushes it onto the ring."""

    __slots__ = (
        "name", "trace", "sid", "parent", "t_start", "t_end", "thread",
        "lane", "kind", "attrs",
    )

    def __init__(self, name, trace=None, parent=None, lane=None,
                 kind="span", attrs=None, t_start=None):
        self.name = name
        self.trace = trace
        self.sid = next(_ids)
        self.parent = parent.sid if isinstance(parent, Span) else parent
        self.t_start = now() if t_start is None else t_start
        self.t_end: Optional[float] = None
        self.thread = threading.get_ident()
        self.lane = lane
        self.kind = kind
        self.attrs = dict(attrs) if attrs else {}

    @property
    def dur_s(self) -> float:
        return (self.t_end - self.t_start) if self.t_end is not None else 0.0

    def to_json(self) -> dict:
        d = {
            "name": self.name, "trace": self.trace, "span": self.sid,
            "parent": self.parent, "t_start": round(self.t_start, 6),
            "dur_s": round(self.dur_s, 6), "thread": self.thread,
            "lane": self.lane, "kind": self.kind,
        }
        if self.attrs:
            d["attrs"] = self.attrs
        return d

    def __repr__(self):  # debugging aid, never parsed
        return (f"Span({self.name!r}, trace={self.trace}, sid={self.sid}, "
                f"dur={self.dur_s:.6f}, attrs={self.attrs})")


# ---------------------------------------------------------------------------
# control
# ---------------------------------------------------------------------------


def on(ring: Optional[int] = None) -> None:
    """Enable span recording with a flight-recorder ring of ``ring``
    completed spans (oldest evicted; :func:`evicted` counts them).
    ``ring=None`` keeps the current capacity (:data:`DEFAULT_RING`
    initially, or whatever ``SLATE_TPU_TRACE_RING``/an earlier explicit
    ``on(ring=)`` configured) — a bare re-enable never shrinks it."""
    global _enabled, _ring, _t0
    with _lock:
        if ring is not None and _ring.maxlen != int(ring):
            _ring = deque(_ring, maxlen=max(1, int(ring)))
        if _t0 is None:
            _t0 = now()
        _enabled = True


def off() -> None:
    global _enabled
    _enabled = False


def is_on() -> bool:
    return _enabled

def capacity() -> int:
    return _ring.maxlen or 0


def clear() -> None:
    global _evicted, _t0
    with _lock:
        _ring.clear()
        _evicted = 0
        _t0 = now() if _enabled else None


def evicted() -> int:
    """Completed spans the bounded ring has dropped (oldest-first)."""
    return _evicted


def pressure() -> dict:
    """Eviction-pressure snapshot of the flight recorder: capacity,
    current fill, lifetime evictions, and the estimated coverage
    window (newest end minus oldest start across the ring) — the span
    of history a ring->spec soak recording can still reconstruct.  A
    nonzero ``evicted`` with a short ``window_s`` means a recording
    taken NOW is already truncated; ``health()["trace_ring"]``
    surfaces this so the gap is visible before it becomes a silently
    short load spec."""
    with _lock:
        size = len(_ring)
        if size:
            oldest = _ring[0]
            newest = _ring[-1]
            window = (newest.t_end if newest.t_end is not None
                      else newest.t_start) - oldest.t_start
        else:
            window = 0.0
        return {
            "capacity": _ring.maxlen or 0,
            "size": size,
            "evicted": _evicted,
            "window_s": round(max(window, 0.0), 6),
        }


def new_trace() -> str:
    """A fresh trace id (one per serve request)."""
    return f"t{os.getpid():x}-{next(_trace_ids):x}"


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------


def _push(sp: Span) -> None:
    global _evicted
    with _lock:
        if len(_ring) == _ring.maxlen:
            _evicted += 1
        _ring.append(sp)


def start(name: str, trace: Optional[str] = None, parent=None,
          lane: Optional[str] = None, **attrs) -> Optional[Span]:
    """Open a span (not yet on the ring; :func:`end` completes it).
    For cross-thread lifecycle spans the caller holds the handle —
    the context-manager :func:`span` is the nested single-thread
    form.  Returns None when tracing is off."""
    if not _enabled:
        return None
    return Span(name, trace=trace, parent=parent, lane=lane, attrs=attrs)


def end(sp: Optional[Span], **attrs) -> None:
    """Stamp ``t_end``, merge ``attrs``, and push onto the ring.
    Idempotent: a span already ended is left untouched (resolution
    paths may race — first outcome wins, like Future.set_result)."""
    if sp is None or not _enabled:
        return
    if sp.t_end is not None:
        return
    sp.t_end = now()
    if attrs:
        sp.attrs.update(attrs)
    _push(sp)


def record(name: str, t_start: float, t_end: float,
           trace: Optional[str] = None, parent=None,
           lane: Optional[str] = None, kind: str = "span",
           **attrs) -> Optional[Span]:
    """Append one already-measured interval (both timestamps from
    :func:`now`'s clock).  The bulk path: per-item spans of a batch,
    metrics/trace mirrors, planned backoff windows."""
    if not _enabled:
        return None
    sp = Span(name, trace=trace, parent=parent, lane=lane, kind=kind,
              attrs=attrs, t_start=t_start)
    sp.t_end = t_end
    _push(sp)
    return sp


def event(name: str, trace: Optional[str] = None, parent=None,
          lane: Optional[str] = None, **attrs) -> Optional[Span]:
    """Instant event (zero-duration; breaker transitions and friends)."""
    if not _enabled:
        return None
    t = now()
    return record(name, t, t, trace=trace, parent=parent, lane=lane,
                  kind="instant", **attrs)


class span:
    """Context manager for nested single-thread spans: parents onto the
    innermost active span of this thread (or an explicit ``parent`` —
    e.g. a request's root span held by another thread), becomes
    :func:`current` inside the block (so :func:`annotate` reaches it)
    and holds a profiler annotation of its name open::

        with spans.span("factor", trace=tr):
            ...
            spans.annotate(iters=3)
    """

    __slots__ = ("name", "trace", "lane", "parent", "attrs", "_sp", "_ann")

    def __init__(self, name: str, trace: Optional[str] = None,
                 lane: Optional[str] = None, parent=None, **attrs):
        self.name = name
        self.trace = trace
        self.lane = lane
        self.parent = parent
        self.attrs = attrs
        self._sp: Optional[Span] = None
        self._ann = None

    def __enter__(self) -> Optional[Span]:
        if not _enabled:
            return None
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        parent = self.parent if self.parent is not None else (
            stack[-1] if stack else None
        )
        tr = self.trace
        if tr is None and isinstance(parent, Span):
            tr = parent.trace
        self._ann = annotation(self.name)
        self._sp = Span(self.name, trace=tr, parent=parent, lane=self.lane,
                        attrs=self.attrs)
        stack.append(self._sp)
        return self._sp

    def __exit__(self, exc_type, *exc) -> bool:
        sp = self._sp
        if sp is None:
            return False
        self._ann.__exit__(None, None, None)
        self._ann = None
        stack = getattr(_tls, "stack", None)
        if stack and stack[-1] is sp:
            stack.pop()
        if exc_type is not None:
            sp.attrs.setdefault("outcome", exc_type.__name__)
        end(sp)
        return False


def current() -> Optional[Span]:
    """The innermost context-managed span on this thread (None when
    off or outside every block)."""
    if not _enabled:
        return None
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def annotate(_sp: Optional[Span] = None, **attrs) -> None:
    """Merge attrs into ``_sp`` (or this thread's :func:`current` span).
    The hook the refine drivers use to stamp iteration counts onto
    whatever span their caller is inside.  No-op when off/outside."""
    if not _enabled:
        return
    sp = _sp if _sp is not None else current()
    if sp is not None:
        sp.attrs.update(attrs)


# ---------------------------------------------------------------------------
# snapshots + export
# ---------------------------------------------------------------------------


def snapshot() -> List[Span]:
    """The ring's completed spans, oldest first."""
    with _lock:
        return list(_ring)


def by_trace() -> Dict[str, List[Span]]:
    """Ring spans grouped by trace id (spans without one are dropped) —
    the orphan check: a delivered request's trace must contain a
    completed ``request`` root plus its lifecycle children."""
    out: Dict[str, List[Span]] = {}
    for sp in snapshot():
        if sp.trace is not None:
            out.setdefault(sp.trace, []).append(sp)
    return out


def export_chrome(path: str, process_name: Optional[str] = None) -> str:
    """Write the ring as Chrome trace-event JSON (the ``traceEvents``
    array format; open in Perfetto / chrome://tracing).  One lane per
    replica/worker: spans with a ``lane`` string share a named tid;
    lane-less spans fall back to one tid per OS thread.  Spans carry
    ``trace``/``span``/``parent`` ids and attrs in ``args``.
    ``process_name`` labels this process's pid track (Chrome's
    ``process_name`` metadata) — the fleet tier's per-host exports set
    it so ``tools/trace_stitch.py`` renders each host as a named
    process in the stitched view."""
    items = snapshot()
    rows = []  # (name, t0, t1, lane, thread, kind, args)
    for sp in items:
        args = {"span": sp.sid}
        if sp.trace is not None:
            args["trace"] = sp.trace
        if sp.parent is not None:
            args["parent"] = sp.parent
        args.update(sp.attrs)
        rows.append((sp.name, sp.t_start, sp.t_end, sp.lane, sp.thread,
                     sp.kind, args))
    pid = os.getpid()
    tids: Dict[str, int] = {}

    def tid_for(lane, thread):
        key = lane if lane is not None else f"thread-{thread}"
        if key not in tids:
            tids[key] = len(tids)
        return tids[key]

    t0 = min((r[1] for r in rows), default=_t0 or 0.0)
    evs = []
    for name, start_t, stop_t, lane, thread, kind, args in rows:
        ev = {
            "name": name,
            "cat": kind,
            "pid": pid,
            "tid": tid_for(lane, thread),
            "ts": round((start_t - t0) * 1e6, 3),
            "args": args,
        }
        if kind == "instant":
            ev["ph"] = "i"
            ev["s"] = "p"
        else:
            ev["ph"] = "X"
            ev["dur"] = round(((stop_t or start_t) - start_t) * 1e6, 3)
        evs.append(ev)
    evs.sort(key=lambda e: e["ts"])
    meta = [
        {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
         "args": {"name": key}}
        for key, tid in sorted(tids.items(), key=lambda kv: kv[1])
    ]
    if process_name is not None:
        meta.insert(0, {"name": "process_name", "ph": "M", "pid": pid,
                        "args": {"name": str(process_name)}})
    with open(path, "w") as f:
        json.dump({"traceEvents": meta + evs, "displayTimeUnit": "ms"}, f)
    return path


# ---------------------------------------------------------------------------
# env activation: SLATE_TPU_TRACE_RING=N
# ---------------------------------------------------------------------------

_env_ring = os.environ.get(RING_ENV)
if _env_ring:
    try:
        _n = int(_env_ring)
    except ValueError as e:
        raise ValueError(
            f"{RING_ENV}={_env_ring!r}: expected an integer ring size"
        ) from e
    if _n > 0:
        on(ring=_n)
