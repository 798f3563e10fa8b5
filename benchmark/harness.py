"""Finds the benchmark's parts by name, checks the device, and prints the
result line.

Every part lives in a file of its own under ``benchmark/``:

- ``BENCHMARK.json`` (at the root) lists cells, metrics and configs;
- ``configs/<config>.json``: one deployment (the file BENCHMARK.json names);
- ``traffic/<traffic>.json``: one traffic mix, a data file that names the
  general driver reading it (``"driver"``);
- ``drivers/<driver>.py``: one general traffic driver;
- ``layer_metrics/<metric>.py``: one per-layer metric reader, ``read(run)``;
- ``routines/<routine>.py``: one routine's public driver call on global
  device arrays, ``call(st, A, B, nb, opts)``, its control,
  ``control(config, A, B)``, and how the benchmark's tests check that
  control on the CPU: ``CONTROL_N``, the sizes at which it runs there
  and fails the config's limits, or, where none does, ``CONTROL_N = ()``
  and ``CONTROL_CHIP``, its readings on the chip at the cell's size, one
  dict of numbers per seed (the routine the config names);
- ``work/<routine>.py``: the model operations and bytes of one routine,
  part of the yardstick: kept apart from the routine's file, which calls
  the program, so that the readers that count work import no program.

A later PR adds a cell, a mix, a metric or a routine by adding files and
entries; nothing here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class HarnessError(Exception):
    """A cell that cannot run here (bad name, no chip, missing part)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise HarnessError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def _module(path: str, name: str):
    if not os.path.exists(path):
        raise HarnessError(f"missing benchmark part {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _safe(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in name)


def driver(kind: str, root: str = ROOT):
    return _module(os.path.join(root, "benchmark", "drivers", f"{kind}.py"),
                   f"bench_driver_{_safe(kind)}")


def reader(metric: str, root: str = ROOT):
    return _module(
        os.path.join(root, "benchmark", "layer_metrics", f"{metric}.py"),
        f"bench_metric_{_safe(metric)}")


def routine(name: str, root: str = ROOT):
    return _module(
        os.path.join(root, "benchmark", "routines", f"{name}.py"),
        f"bench_routine_{_safe(name)}")


def work(routine: str, root: str = ROOT):
    return _module(os.path.join(root, "benchmark", "work", f"{routine}.py"),
                   f"bench_work_{_safe(routine)}")


def roof(device_kind: str, root: str = ROOT) -> dict:
    """The chip's published peaks; a kind not in the table is an error."""
    table = load_json(os.path.join(root, "benchmark", "roofs.json"))
    try:
        return table["kinds"][device_kind]
    except KeyError:
        raise HarnessError(
            f"no roof for device kind {device_kind!r} in roofs.json") from None


class Cell:
    """One workload of BENCHMARK.json with its config, traffic and metrics."""

    def __init__(self, name: str, root: str = ROOT):
        bench = benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise HarnessError(f"no workload {name!r} in BENCHMARK.json")
        self.root = root
        self.bench = bench
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfgs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(os.path.join(root, cfgs[self.entry["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            root, "benchmark", "traffic", f"{self.entry['traffic']}.json"))
        self.end_to_end = [m for m in bench["end_to_end"] if self._has(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if m["moves"] in moved and self._has(m)]

    def _has(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def driver(self):
        return driver(self.traffic["driver"], self.root)

    def routine(self):
        return routine(self.config["routine"], self.root)

    def work(self):
        return work(self.config["routine"], self.root)


def parts(root: str = ROOT) -> dict:
    """Every cell and per-layer metric BENCHMARK.json names, resolved to
    its files (the discovery check of benchmark/tests)."""
    bench = benchmark(root)
    out = {"cells": {}, "metrics": {}}
    for w in bench["workloads"]:
        c = Cell(w["name"], root)
        out["cells"][w["name"]] = {
            "driver": c.driver().__name__,
            "routine": c.routine().__name__,
            "end_to_end": [m["name"] for m in c.end_to_end],
            "per_layer": [m["name"] for m in c.per_layer],
        }
    for m in bench["per_layer"]:
        out["metrics"][m["name"]] = reader(m["name"], root).__name__
    return out


def check_device(chips: int, allow_cpu: bool = False):
    """The devices the cell runs on; anything but enough TPU chips is
    an error (no CPU fallback)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise HarnessError(f"needs a TPU, found {devices[0].platform}")
    if len(devices) < chips:
        raise HarnessError(
            f"the cell needs {chips} chips, found {len(devices)}")
    return devices


def memory_peak(devices) -> int:
    """The peak on the fullest chip: the allocator's peak of buffers in
    use plus the peak it reserved for loaded programs.  The TPU runtime
    reserves each program's temporaries when it loads the program, and
    ``peak_bytes_in_use`` leaves them out (PERF.md, section 4)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else 0


def emit(result: dict, checks: list) -> None:
    """Print the compared numbers as the last stderr lines and the result
    as the last stdout line, with the checks under the last key."""
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} limit {limit!r} "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ok in checks}
    print(json.dumps(result), flush=True)
