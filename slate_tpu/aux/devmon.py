"""Device telemetry plane: per-executable cost/memory capture, HBM
gauges, and the roofline peaks table (Williams, Waterman & Patterson,
CACM 2009 — see PAPERS.md).

Three sensors, one module:

1. **Executable cost/memory capture** — :func:`analyze_compiled` reads
   ``cost_analysis()`` (flops, bytes accessed, transcendentals) and
   ``memory_analysis()`` (argument/output/temp bytes, peak when the
   runtime reports one) off a ``jax`` AOT-compiled executable;
   :func:`capture_jitted` does the lower -> compile -> analyze chain
   for a ``jax.jit`` callable and records the result into the metrics
   cost registry, so a ``SLATE_TPU_METRICS`` JSONL carries
   ``{"type": "cost", "name": ..., "flops": ..., "peak_bytes": ...}``
   rows ``tools/roofline_report.py`` and ``tools/warmup_report.py``
   join.  The serving cache (serve/cache.py) calls this at every cold
   build and artifact restore, keyed ``serve.<bucket>.b<batch>``, and
   persists the record beside the warmup manifest entry.
2. **Device memory gauges** — :func:`sample_devices` polls
   ``device.memory_stats()`` per visible device into
   ``serve.device.<i>.bytes_in_use`` gauges plus a process-lifetime
   high-water mark (``.bytes_in_use_peak``), with a graceful ``None``
   on backends without the API (XLA:CPU returns nothing) — the HBM
   pressure signal admission reads before the device arena exists.
3. **Roofline attribution** — :func:`peaks_for` resolves a device
   kind to (peak FLOP/s, peak bytes/s) from the built-in table or the
   ``SLATE_TPU_PEAKS`` JSON override; :func:`roofline` joins measured
   wall time with captured flops/bytes into achieved FLOP/s,
   arithmetic intensity, the compute-vs-memory-bound verdict, and
   fraction-of-roof.

Zero overhead when off (the registry design goal, metrics.py goal 1):
every producer call site gates on :func:`is_on` — one module-level
bool.  Activation: ``SLATE_TPU_DEVMON=1`` at import, or
:func:`on` programmatically.  The capture itself costs one extra
backend compile per (bucket, batch) at COLD BUILD time only (the AOT
lowering is not shared with the dispatch cache); steady state and the
devmon-off path never pay anything.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

from . import metrics as _metrics

_enabled = False
_lock = threading.Lock()
#: device id -> process-lifetime high-water mark of bytes_in_use (kept
#: here so backends whose memory_stats lacks peak_bytes_in_use still
#: get a monotone peak from repeated samples)
_hwm: Dict[Any, int] = {}

PEAKS_ENV = "SLATE_TPU_PEAKS"

#: built-in peak table: lowercase device-kind substring -> (peak
#: FLOP/s, peak bytes/s), matched by substring in this order, so the
#: v5e's "TPU v5 lite" finds its own row ahead of the v5p's "tpu v5".
#: TPU rows are the bf16 peaks and HBM bandwidth of Google Cloud's
#: per-generation pages ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e").
#: The cpu row is deliberately modest (a few Skylake-class cores with
#: AVX f64 and dual-channel DRAM) — the roofline verdict needs the
#: RATIO (the ridge point), not vendor-sheet precision, and
#: SLATE_TPU_PEAKS overrides per deployment.
DEFAULT_PEAKS: Dict[str, Dict[str, float]] = {
    "cpu": {"flops": 5.0e10, "bytes_per_s": 2.0e10},
    "tpu v4": {"flops": 2.75e14, "bytes_per_s": 1.2e12},
    "tpu v5 lite": {"flops": 1.97e14, "bytes_per_s": 8.19e11},
    "tpu v5": {"flops": 4.59e14, "bytes_per_s": 2.765e12},
    "tpu v6 lite": {"flops": 9.18e14, "bytes_per_s": 1.64e12},
}


# ---------------------------------------------------------------------------
# activation
# ---------------------------------------------------------------------------


def on() -> None:
    """Enable device telemetry capture (one bool flips)."""
    global _enabled
    _enabled = True


def off() -> None:
    global _enabled
    _enabled = False


def is_on() -> bool:
    return _enabled


def reset() -> None:
    """Clear the high-water marks (keeps on/off state) — test hygiene."""
    with _lock:
        _hwm.clear()


# ---------------------------------------------------------------------------
# executable cost/memory capture
# ---------------------------------------------------------------------------


def analyze_compiled(compiled) -> Optional[dict]:
    """Cost + memory record of one AOT-compiled executable: flops /
    bytes_accessed / transcendentals from ``cost_analysis()``,
    argument/output/temp/generated-code bytes from
    ``memory_analysis()``, and ``peak_bytes`` — the runtime's
    ``peak_memory_in_bytes`` when it reports one, else the
    argument+output+temp sum (the resident-set bound XLA:CPU gives
    us).  Missing APIs degrade to omitted fields; a record with
    nothing in it is None.  Never raises."""
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            for key, label in (("flops", "flops"),
                               ("bytes accessed", "bytes_accessed"),
                               ("transcendentals", "transcendentals")):
                v = ca.get(key)
                # XLA reports -1 for unknowable costs (CPU while
                # loops): that is "no data", not a number to rate with
                if v is not None and float(v) >= 0:
                    out[label] = float(v)
    except Exception:  # noqa: BLE001 — attribution must never break a build
        pass
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for attr, label in (
                ("argument_size_in_bytes", "argument_bytes"),
                ("output_size_in_bytes", "output_bytes"),
                ("temp_size_in_bytes", "temp_bytes"),
                ("alias_size_in_bytes", "alias_bytes"),
                ("generated_code_size_in_bytes", "generated_code_bytes"),
            ):
                v = getattr(ma, attr, None)
                if v is not None and int(v) >= 0:
                    out[label] = int(v)
            peak = getattr(ma, "peak_memory_in_bytes", None)
            # absent OR zero: some PJRT plugins expose the attribute
            # without filling it — either way the arg+out+temp sum is
            # the computable bound
            if not peak and (
                "argument_bytes" in out or "output_bytes" in out
                or "temp_bytes" in out
            ):
                # aliased (donated) buffers appear in BOTH the argument
                # and output totals — subtract them once or the bound
                # double-counts every donated batch operand
                peak = max(
                    out.get("argument_bytes", 0)
                    + out.get("output_bytes", 0)
                    + out.get("temp_bytes", 0)
                    - out.get("alias_bytes", 0),
                    0,
                )
            if peak is not None and int(peak) > 0:
                out["peak_bytes"] = int(peak)
    except Exception:  # noqa: BLE001
        pass
    return out or None


def capture_jitted(jitted, args, name: Optional[str] = None,
                   record: bool = True):
    """AOT lower -> compile -> analyze one ``jax.jit`` callable at
    ``args`` (arrays or ``jax.ShapeDtypeStruct`` specs).  Returns
    ``(compiled, cost)`` — the compiled executable (callable; reusable
    so the capture compile is not wasted) and the cost/memory record
    (either may be None on failure; capture must never break a build).
    With ``record`` and a ``name``, the record also lands in the
    metrics cost registry (when metrics are on), tagged with the
    default device kind so the roofline report can resolve peaks."""
    compiled = cost = None
    try:
        compiled = jitted.lower(*args).compile()
        cost = analyze_compiled(compiled)
    except Exception:  # noqa: BLE001 — capture must never break a build
        return compiled, None
    if cost is not None:
        cost["device_kind"] = default_device_kind()
        if record and name:
            _metrics.record_cost(name, cost)
    return compiled, cost


def default_device_kind() -> str:
    """Lowercased device kind of the default backend's first device
    (the peaks-table key); "unknown" when jax is unavailable."""
    try:
        import jax

        d = jax.devices()[0]
        return str(getattr(d, "device_kind", d.platform)).lower()
    except Exception:  # noqa: BLE001
        return "unknown"


# ---------------------------------------------------------------------------
# device memory gauges
# ---------------------------------------------------------------------------


def bytes_in_use(device=None) -> Optional[int]:
    """Instantaneous HBM bytes in use on one device (default: the
    default backend's first device), or None on backends without
    ``memory_stats`` (XLA:CPU) — the fabric arena's budget-pressure
    probe; graceful degradation, never a crash."""
    try:
        if device is None:
            import jax

            device = jax.devices()[0]
        fn = getattr(device, "memory_stats", None)
        stats = fn() if fn is not None else None
        if stats:
            v = stats.get("bytes_in_use")
            return int(v) if v is not None else None
    except Exception:  # noqa: BLE001 — telemetry must never crash
        pass
    return None


def sample_devices(devices=None) -> List[dict]:
    """One memory snapshot per device: ``{"id", "platform", "kind",
    "bytes_in_use", "bytes_limit", "peak_bytes_in_use"}`` with the
    byte fields None on backends without ``memory_stats`` (XLA:CPU) —
    graceful degradation, never a crash.  Maintains a process-lifetime
    high-water mark per device (the monotone peak even when the
    backend reports only instantaneous use) and, with metrics on,
    emits ``serve.device.<i>.bytes_in_use`` / ``.bytes_in_use_peak``
    gauges."""
    if devices is None:
        try:
            import jax

            devices = jax.devices()
        except Exception:  # noqa: BLE001 — telemetry must never crash
            return []
    out = []
    for d in devices:
        did = getattr(d, "id", None)
        row = {
            "id": did,
            "platform": getattr(d, "platform", None),
            "kind": getattr(d, "device_kind", None),
            "bytes_in_use": None,
            "bytes_limit": None,
            "peak_bytes_in_use": None,
        }
        stats = None
        try:
            fn = getattr(d, "memory_stats", None)
            stats = fn() if fn is not None else None
        except Exception:  # noqa: BLE001 — unsupported backend, not an error
            stats = None
        if stats:
            in_use = stats.get("bytes_in_use")
            row["bytes_in_use"] = (
                int(in_use) if in_use is not None else None
            )
            limit = stats.get("bytes_limit")
            row["bytes_limit"] = int(limit) if limit is not None else None
            peak = stats.get("peak_bytes_in_use")
            with _lock:
                prev = _hwm.get(did, 0)
                cand = max(
                    prev,
                    int(peak) if peak is not None else 0,
                    int(in_use) if in_use is not None else 0,
                )
                if cand > 0:
                    _hwm[did] = cand
                    row["peak_bytes_in_use"] = cand
            if _metrics.is_on():
                if row["bytes_in_use"] is not None:
                    _metrics.gauge(
                        f"serve.device.{did}.bytes_in_use",
                        row["bytes_in_use"],
                    )
                if row["peak_bytes_in_use"] is not None:
                    _metrics.gauge(
                        f"serve.device.{did}.bytes_in_use_peak",
                        row["peak_bytes_in_use"],
                    )
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# roofline peaks + attribution
# ---------------------------------------------------------------------------


def _env_peaks() -> Dict[str, Dict[str, float]]:
    """The ``SLATE_TPU_PEAKS`` override table: a JSON object mapping
    device-kind substrings to ``{"flops": ..., "bytes_per_s": ...}``.
    A malformed value degrades to the built-in table (telemetry never
    crashes the host), counted ``devmon.peaks_parse_error``."""
    raw = os.environ.get(PEAKS_ENV)
    if not raw:
        return {}
    try:
        doc = json.loads(raw)
        out = {}
        for kind, row in doc.items():
            f, b = float(row["flops"]), float(row["bytes_per_s"])
            if f <= 0 or b <= 0:
                # zero/negative roofs would divide-by-zero the ridge
                # and the frac-of-roof — malformed, not a table row
                raise ValueError(f"peaks for {kind!r} must be positive")
            out[str(kind).lower()] = {"flops": f, "bytes_per_s": b}
        return out
    except Exception:  # noqa: BLE001
        _metrics.inc("devmon.peaks_parse_error")
        return {}


def peaks_for(kind: Optional[str] = None) -> dict:
    """Resolve a device kind to its roofline peaks: ``{"flops",
    "bytes_per_s", "ridge", "kind", "source"}`` with ridge = peak
    FLOP/s / peak bytes/s (the arithmetic intensity where the roof
    changes slope).  ``SLATE_TPU_PEAKS`` rows win over the built-in
    table; a kind that neither knows raises ``ValueError`` — a roofline
    against another device's roofs is not a measurement."""
    k = (kind if kind is not None else default_device_kind()).lower()
    table = DEFAULT_PEAKS
    source = "default"
    env = _env_peaks()
    row = None
    for sub, vals in env.items():
        if sub in k:
            row, source = vals, "env"
            break
    if row is None:
        for sub, vals in table.items():
            if sub in k:
                row = vals
                break
    if row is None:
        raise ValueError(
            f"no roofline peaks for device kind {k!r}: add a row to "
            f"{PEAKS_ENV} or devmon.DEFAULT_PEAKS"
        )
    return {
        "kind": k,
        "flops": float(row["flops"]),
        "bytes_per_s": float(row["bytes_per_s"]),
        "ridge": float(row["flops"]) / float(row["bytes_per_s"]),
        "source": source,
    }


def roofline(flops: float, bytes_accessed: float, seconds: float,
             peaks: Optional[dict] = None) -> Optional[dict]:
    """Roofline attribution of one measured execution: achieved
    FLOP/s, arithmetic intensity (flops / bytes accessed), the
    compute- vs memory-bound verdict (intensity vs the ridge point),
    the attainable roof ``min(peak_flops, intensity * peak_bw)``, and
    the achieved fraction of it.  None when the inputs cannot rate
    (zero/negative flops, bytes, or wall) — the caller's
    "unclassifiable" signal, never a division error."""
    if not (flops and flops > 0 and bytes_accessed and bytes_accessed > 0
            and seconds and seconds > 0):
        return None
    pk = peaks if peaks is not None else peaks_for()
    if not (pk.get("flops", 0) > 0 and pk.get("bytes_per_s", 0) > 0):
        return None  # degenerate hand-passed roofs: unclassifiable
    # accept the bare SLATE_TPU_PEAKS row shape too: ridge is derived
    # when the caller did not pass a peaks_for() result
    ridge = pk.get("ridge") or pk["flops"] / pk["bytes_per_s"]
    achieved = flops / seconds
    intensity = flops / bytes_accessed
    roof = min(pk["flops"], intensity * pk["bytes_per_s"])
    return {
        "achieved_flops": achieved,
        "achieved_gflops": achieved / 1e9,
        "intensity": intensity,
        "ridge": ridge,
        "bound": "compute" if intensity >= ridge else "memory",
        "roof_flops": roof,
        "frac_of_roof": achieved / roof,
        "peaks_source": pk.get("source", "caller"),
    }


# ---------------------------------------------------------------------------
# env activation: SLATE_TPU_DEVMON=1
# ---------------------------------------------------------------------------

if os.environ.get("SLATE_TPU_DEVMON") not in (None, "", "0"):
    on()
