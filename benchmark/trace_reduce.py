"""Reduces a profiler trace (``.xplane.pb``) to device-op intervals and
from them to the numbers the per-layer metrics read.

Read from chip traces by hand (PERF.md, "Reading the trace"): each
chip is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds one
event per executed HLO operation, named by its HLO text
(``%solve.163 = f32[256,256]... custom-call(...),
custom_call_target="tpu_custom_call"``).  The line ``Async XLA Ops``
holds DMAs in flight beside the ops and is not work of the core.  A
Mosaic (Pallas) kernel is an op whose HLO is a ``tpu_custom_call`` (its
name is the kernel's); a collective is an op of the all-reduce /
all-gather / reduce-scatter / collective-permute / all-to-all / send /
recv families, its blocking ``-start``/``-done`` halves included.
Container ops (a ``while`` or ``conditional`` whose body ops appear
inside it) are dropped: only leaf ops count as work, so busy time is
the union of the leaves.

The window is the host annotation ``bench.window`` that the harness
puts around the traced sub-window; device intervals are clipped to it.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE = re.compile(r"^/device:(TPU|GPU|CPU):(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ragged-all-to-all|collective-broadcast|send|recv)(-start|-done)?"
    r"([.\-_]\d+)*$")
MOSAIC = "tpu_custom_call"


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} .xplane.pb files in {trace_dir}")
    return paths[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:  # an event whose stats cannot be read has none
        return {}


def op_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" ", 1)[0].lstrip("%")


def is_mosaic(name: str) -> bool:
    return MOSAIC in name


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(op_name(name)))


def window_of(pdata):
    """(start_ns, end_ns) of the harness's ``bench.window`` annotation."""
    found = []
    for plane in pdata.planes:
        if DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW:
                    found.append((ev.start_ns, ev.end_ns))
    if not found:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return min(s for s, _ in found), max(e for _, e in found)


def leaves(events):
    """Drop events that contain a later event of the same line."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[0] < e[1] and nxt[1] <= e[1]:
            continue  # a container: its body ops follow inside it
        out.append(e)
    return out


def device_ops(pdata, window=None) -> dict:
    """{device index: [(start_ns, end_ns, name, kind)]} of leaf ops,
    clipped to ``window``; kind is 'mosaic', 'collective' or 'op'."""
    out = {}
    for plane in pdata.planes:
        m = DEVICE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                kind = ("mosaic" if is_mosaic(name)
                        else "collective" if is_collective(name)
                        else "op")
                evs.append((ev.start_ns, ev.end_ns, op_name(name), kind))
        clipped = []
        for s, e, name, kind in leaves(evs):
            if window is not None:
                s, e = max(s, window[0]), min(e, window[1])
            if e > s:
                clipped.append((s, e, name, kind))
        out[int(m.group(2))] = clipped
    return out


def union(intervals):
    """Merged, sorted [(s, e)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def reduce(pdata, devices=None) -> dict:
    """The numbers of one traced window, per device and averaged.

    ``devices``: the device indices the cell used (default: every device
    plane in the trace).  Times are seconds."""
    win = window_of(pdata)
    ops = device_ops(pdata, win)
    if devices is not None:
        ops = {d: ops.get(d, []) for d in devices}
    window_s = (win[1] - win[0]) * 1e-9
    per = {}
    for d, evs in ops.items():
        busy = union((s, e) for s, e, _n, _k in evs)
        comp = union((s, e) for s, e, _n, k in evs if k != "collective")
        coll = union((s, e) for s, e, _n, k in evs if k == "collective")
        per[d] = {
            "busy_s": length(busy) * 1e-9,
            "mosaic_s": sum(e - s for s, e, _n, k in evs
                            if k == "mosaic") * 1e-9,
            "collective_s": length(coll) * 1e-9,
            "exposed_collective_s": length(subtract(coll, comp)) * 1e-9,
            "busy": busy,
        }
    n = max(1, len(per))
    busy_s = sum(p["busy_s"] for p in per.values()) / n
    return {
        "window_s": window_s,
        "window_ns": win,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "mosaic_s": sum(p["mosaic_s"] for p in per.values()) / n,
        "collective_s": sum(p["collective_s"] for p in per.values()) / n,
        "exposed_collective_s_max": max(
            (p["exposed_collective_s"] for p in per.values()), default=0.0),
        "per_device": per,
        "ops": ops,
    }


def _op_family(name: str) -> str:
    """``fusion.123`` -> ``fusion``: ops summed by family."""
    return re.sub(r"([.\-_]\d+)+$", "", op_name(name))


def breakdown(pdata, red: dict, top: int = 10) -> dict:
    """The device ops that took most time (summed over devices, by op
    family) and the longest idle gaps of device 0 (or the first), each
    named by the host event that was running in it."""
    fam = {}
    for evs in red["ops"].values():
        for s, e, name, _k in evs:
            f = _op_family(name)
            fam[f] = fam.get(f, 0) + (e - s)
    n = max(1, len(red["ops"]))
    dev_ops = sorted(((f, t * 1e-9 / n) for f, t in fam.items()),
                     key=lambda x: -x[1])[:top]
    gaps = []
    if red["per_device"]:
        d0 = sorted(red["per_device"])[0]
        busy = red["per_device"][d0]["busy"]
        win = red["window_ns"]
        idle = subtract([win], busy)
        idle.sort(key=lambda g: g[0] - g[1])
        host = _host_events(pdata, win)
        for s, e in idle[:top]:
            gaps.append([_host_at(host, s, e), (e - s) * 1e-9])
    return {"device_ops": [[f, t] for f, t in dev_ops], "idle_gaps": gaps}


def _host_events(pdata, win):
    out = []
    for plane in pdata.planes:
        if DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != WINDOW and ev.end_ns > win[0] and ev.start_ns < win[1]:
                    out.append((ev.start_ns, ev.end_ns, ev.name))
    return out


def _host_at(host, s, e) -> str:
    """The shortest host event covering most of [s, e] (the innermost
    thing the host was doing), or 'host idle'."""
    best = None
    for hs, he, name in host:
        cover = min(he, e) - max(hs, s)
        if cover * 2 >= (e - s):
            if best is None or (he - hs) < best[0]:
                best = (he - hs, name)
    return best[1] if best else "host idle"


def dump(pdata, limit: int = 30) -> list:
    """Planes, lines and the first distinct event names with their stats:
    the by-hand look at a trace."""
    out = []
    for plane in pdata.planes:
        ent = {"plane": plane.name, "lines": []}
        for line in plane.lines:
            evs = list(line.events)
            first = {}
            for ev in evs:
                if ev.name not in first and len(first) < limit:
                    first[ev.name] = {
                        "start_ns": ev.start_ns, "dur_ns": ev.duration_ns,
                        "stats": {k: str(v)[:160] for k, v in _stats(ev).items()},
                    }
            ent["lines"].append({"line": line.name, "events": len(evs),
                                 "first": first})
        out.append(ent)
    return out
