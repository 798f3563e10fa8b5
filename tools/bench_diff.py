#!/usr/bin/env python
"""Perf regression sentinel: diff two bench JSON artifacts and fail on
GFLOP/s regressions or peak-memory growth past thresholds.

    python tools/bench_diff.py old.json new.json
    python tools/bench_diff.py --baseline old.json live.json
    python tools/bench_diff.py --floor BENCH_FLOOR_CPU.json live.json

Accepts either shape of bench artifact: the raw ``bench.py`` stdout
line (``{"metric", "value", "extra", ...}``) or the driver's recorded
wrapper (``{"rc", "tail", "parsed": {...}}`` — the checked-in
``BENCH_r*.json`` trajectory).  Compared fields, per ``extra`` entry
and for the headline ``value``:

* **rates** (higher is better): ``gflops``, ``requests_per_s`` — a
  candidate below ``baseline * (1 - --max-drop)`` is a regression;
* **memory** (lower is better): ``peak_bytes`` — a candidate above
  ``baseline * (1 + --max-mem-growth)`` is growth past threshold;
* **latency** (lower is better): ``p99_s`` — a candidate above
  ``baseline * (1 + --max-lat-growth)`` is a tail regression (the
  ``soak_sustained`` entry's client-observed p99; in ``--floor`` mode
  the baseline value is a hard ceiling).

``--floor`` switches to absolute-floor semantics: the baseline file's
rate values are hard minimums and its ``peak_bytes`` values hard
ceilings (no fractional slack) — the shape of a checked-in floor file
(``BENCH_FLOOR_CPU.json``) deliberately set far below any healthy run,
so the ``run_tests.py --perf`` gate is robust across machines while a
real collapse (a serialization bug, an accidental O(n^4) path, a
donation regression doubling copies) still trips it.

Entries marked ``skipped`` or ``error`` on either side are reported
and excluded (a partial sweep must stay diagnosable, not auto-fail);
``--require-all`` makes a baseline entry missing from the candidate a
failure.  Exit status: 0 = no regression, 1 = regression/growth, 2 =
unusable input.
"""

import argparse
import json
import sys

RATE_FIELDS = ("gflops", "requests_per_s")
MEM_FIELDS = ("peak_bytes",)
LAT_FIELDS = ("p99_s",)


def load_bench(path):
    """The ``{"metric", "value", "extra"}`` payload of either artifact
    shape; None when the file is missing/unreadable/not JSON or has no
    parsed bench line (e.g. a sweep that died before printing) — every unusable input maps to exit code 2, never to
    the regression verdict."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench_diff: cannot read {path}: "
              f"{type(e).__name__}: {e}")
        return None
    if not isinstance(doc, dict):  # bare null / number / list
        return None
    if "parsed" in doc:
        doc = doc["parsed"]
    if not isinstance(doc, dict) or "extra" not in doc:
        return None
    return doc


def entry_state(entry):
    """Why an entry is (not) comparable: ``"ok"`` carries numbers;
    ``"skipped"``/``"error"`` are bench's recorded non-results;
    ``"malformed"`` is anything that is not a dict at all (a
    hand-edited floor file, a partially-written sweep) — reported,
    never crashed on."""
    if not isinstance(entry, dict):
        return "malformed"
    if "skipped" in entry:
        return "skipped"
    if "error" in entry:
        return "error"
    return "ok"


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bench_diff")
    ap.add_argument("baseline_pos", nargs="?", default=None,
                    metavar="baseline", help="baseline bench JSON")
    ap.add_argument("candidate", help="candidate bench JSON (live run "
                                      "or a later BENCH_r*.json)")
    ap.add_argument("--baseline", dest="baseline_opt", default=None,
                    help="baseline bench JSON (alternative spelling "
                         "for live-vs-baseline runs)")
    ap.add_argument("--max-drop", type=float, default=0.30,
                    help="allowed fractional rate drop before a "
                         "regression verdict (default 0.30)")
    ap.add_argument("--max-mem-growth", type=float, default=0.50,
                    help="allowed fractional peak-memory growth "
                         "(default 0.50)")
    ap.add_argument("--max-lat-growth", type=float, default=1.00,
                    help="allowed fractional p99 latency growth "
                         "(default 1.00 — tails are noisy on shared "
                         "CPU runners)")
    ap.add_argument("--floor", action="store_true",
                    help="baseline values are absolute floors "
                         "(rates) / ceilings (peak_bytes), no "
                         "fractional slack")
    ap.add_argument("--require-all", action="store_true",
                    help="fail when a baseline entry is missing from "
                         "the candidate")
    args = ap.parse_args(argv)

    base_path = args.baseline_opt or args.baseline_pos
    if base_path is None:
        ap.error("a baseline is required (positional or --baseline)")
    base = load_bench(base_path)
    cand = load_bench(args.candidate)
    if base is None or cand is None:
        which = base_path if base is None else args.candidate
        print(f"bench_diff: {which} carries no parsed bench payload "
              "(sweep died before its JSON line?)")
        return 2

    regress, notes = [], []
    compared = [0]  # comparisons actually made: zero proves nothing

    def check_rate(label, field, old, new):
        compared[0] += 1
        floor = old if args.floor else old * (1.0 - args.max_drop)
        ok = new >= floor
        verdict = "ok" if ok else "REGRESSION"
        delta = (new - old) / old * 100.0 if old else float("inf")
        print(f"{label:40} {field:>14} {old:>12.1f} -> {new:>12.1f} "
              f"({delta:+6.1f}%) {verdict}")
        if not ok:
            regress.append(
                f"{label}.{field}: {new:.1f} below "
                + (f"floor {floor:.1f}" if args.floor
                   else f"{old:.1f} - {args.max_drop * 100:.0f}%")
            )

    def check_mem(label, field, old, new):
        compared[0] += 1
        ceil = old if args.floor else old * (1.0 + args.max_mem_growth)
        ok = new <= ceil
        verdict = "ok" if ok else "MEM GROWTH"
        delta = (new - old) / old * 100.0 if old else float("inf")
        print(f"{label:40} {field:>14} {old:>12.0f} -> {new:>12.0f} "
              f"({delta:+6.1f}%) {verdict}")
        if not ok:
            regress.append(
                f"{label}.{field}: {new:.0f} above "
                + (f"ceiling {ceil:.0f}" if args.floor
                   else f"{old:.0f} + {args.max_mem_growth * 100:.0f}%")
            )

    def check_lat(label, field, old, new):
        compared[0] += 1
        ceil = old if args.floor else old * (1.0 + args.max_lat_growth)
        ok = new <= ceil
        verdict = "ok" if ok else "LAT GROWTH"
        delta = (new - old) / old * 100.0 if old else float("inf")
        print(f"{label:40} {field:>14} {old:>12.4f} -> {new:>12.4f} "
              f"({delta:+6.1f}%) {verdict}")
        if not ok:
            regress.append(
                f"{label}.{field}: {new:.4f} above "
                + (f"ceiling {ceil:.4f}" if args.floor
                   else f"{old:.4f} + {args.max_lat_growth * 100:.0f}%")
            )

    hdr = (f"{'entry':40} {'field':>14} {'baseline':>12}    "
           f"{'candidate':>12}")
    print(hdr)
    print("-" * len(hdr))
    if isinstance(base.get("value"), (int, float)) and isinstance(
        cand.get("value"), (int, float)
    ):
        # the headline is comparable only when both sides measured the
        # SAME metric — a CPU --quick run vs a TPU trajectory file
        # carries different headline names (sgemm_n512 vs sgemm_n8192)
        # and a -99% "regression" there would be pure shape noise
        if base.get("metric") == cand.get("metric"):
            check_rate("(headline)", base.get("metric", "value"),
                       float(base["value"]), float(cand["value"]))
        else:
            notes.append(
                f"headline metrics differ ({base.get('metric')} vs "
                f"{cand.get('metric')}); not compared"
            )

    bex, cex = base.get("extra") or {}, cand.get("extra") or {}
    if not isinstance(bex, dict) or not isinstance(cex, dict):
        print("bench_diff: 'extra' is not an entry map")
        return 2
    for label in sorted(bex):
        be, ce = bex[label], cex.get(label)
        bstate = entry_state(be)
        if bstate != "ok":
            notes.append(f"{label}: baseline entry {bstate}")
            continue
        cstate = "missing" if ce is None else entry_state(ce)
        if cstate != "ok":
            msg = f"{label}: candidate entry {cstate}"
            notes.append(msg)
            if args.require_all:
                regress.append(msg)
            continue
        for field in RATE_FIELDS:
            if field in be and field in ce:
                check_rate(label, field, float(be[field]),
                           float(ce[field]))
        for field in MEM_FIELDS:
            if field in be and field in ce:
                check_mem(label, field, float(be[field]),
                          float(ce[field]))
        for field in LAT_FIELDS:
            # p99_s is None when a run delivered nothing (all shed) —
            # nothing to compare, not a crash
            if be.get(field) is not None and ce.get(field) is not None:
                check_lat(label, field, float(be[field]),
                          float(ce[field]))

    for n in notes:
        print(f"note: {n}")
    if regress:
        print(f"\nFAIL: {len(regress)} regression(s):")
        for r in regress:
            print(f"  {r}")
        return 1
    if not compared[0]:
        # an all-skipped/errored sweep (or two files sharing no
        # comparable fields) verified NOTHING — that is unusable
        # input, never a clean bill of health
        print("\nbench_diff: no comparable fields between the two "
              "artifacts — nothing was verified")
        return 2
    mode = "floor" if args.floor else f"drop<{args.max_drop * 100:.0f}%"
    print(f"\nbench_diff ok ({mode}): {compared[0]} comparison(s), no "
          "regression, no memory growth past threshold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
