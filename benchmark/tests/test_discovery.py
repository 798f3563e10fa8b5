"""A cell, a traffic mix, a per-layer metric and a routine are found by
name: adding them as new files and entries needs no edit of any file
already there."""

import json
import os
import shutil

import pytest

import harness
from conftest import control_cases, library_cells, make_tiny, run_cell
from test_faults import FAULT_KINDS, control_fails, fault_fails


def _copy(root: str) -> dict:
    """A copy of the benchmark in ``root``; the bytes of each of its files
    under benchmark/."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(harness.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    return before


def _unchanged(before: dict) -> None:
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p


def _write(root: str, rel: str, text: str) -> None:
    with open(os.path.join(root, "benchmark", rel), "w") as f:
        f.write(text)


def _add_entries(root: str, config: dict = None, cell: dict = None) -> None:
    """Append a config and a cell to BENCHMARK.json, and the cell's name
    to solve_s's cells."""
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    if config:
        bench["configs"].append(config)
    if cell:
        bench["workloads"].append(cell)
        for m in bench["end_to_end"]:
            if m["name"] == "solve_s":
                m["workloads"].append(cell["name"])
    with open(path, "w") as f:
        json.dump(bench, f)


def test_new_cell_and_metric_are_listed_without_edits(tmp_path):
    root = str(tmp_path)
    before = _copy(root)
    # new files only: a traffic mix and a metric reader
    _write(root, "traffic/dummy.mix.json", json.dumps(
        {"driver": "library_solve", "operands": 1, "trace_solves": 1}))
    _write(root, "layer_metrics/dummy.metric.py",
           "def read(run):\n    return None\n")
    # new entries in BENCHMARK.json
    _add_entries(root, cell={"name": "dummy.cell",
                             "config": control_cases(root)[0][0],
                             "traffic": "dummy.mix", "chips": 1,
                             "why": "test"})
    path = os.path.join(root, "BENCHMARK.json")
    bench = harness.load_json(path)
    bench["per_layer"].append({
        "name": "dummy.metric", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device (XLA:TPU)",
        "moves": "solve_s", "workloads": ["dummy.cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    parts = harness.parts(root)
    assert "dummy.cell" in parts["cells"]
    assert parts["cells"]["dummy.cell"]["per_layer"] == ["dummy.metric"]
    assert "solve_s" in parts["cells"]["dummy.cell"]["end_to_end"]
    assert "dummy.metric" in parts["metrics"]
    _unchanged(before)


PROBE_CALL = '''
CONTROL_N = (512,)


def call(st, A, B, nb, opts):
    Bm = st.Matrix.from_global(B, nb)
    X, _LU, _piv, _info = st.gesv(st.Matrix.from_global(A, nb), Bm, opts)
    return X.to_global()


def control(config, A, B):
    import jax.numpy as jnp

    return jnp.linalg.solve(jnp.asarray(A, jnp.float32),
                            jnp.asarray(B, jnp.float32))
'''

PROBE_WORK = '''
def ops(n, nrhs):
    return 2.0 / 3.0 * n**3 + 2.0 * n**2 * nrhs


def bytes_moved(n, nrhs, itemsize):
    return float(itemsize) * (2.0 * n * n + 2.0 * n * nrhs)
'''

PROBE_CONFIG = {
    "name": "probe", "routine": "probe_solve", "dtype": "float64",
    "matrix": "hpl_uniform", "n": 1024, "nrhs": 2, "nb": 128,
    "grid": [1, 1], "options": {}, "residual_rule": "hpl",
    "residual_limit": 16.0, "gap_limit": 1e-5,
    "control": {"dtype": "float32"}}
PROBE_FILE = "benchmark/configs/probe.json"


def _add_probe(root: str, routine: str) -> None:
    """A config whose routine the harness has never seen, and its cell,
    as new files and appended entries."""
    with open(os.path.join(root, PROBE_FILE), "w") as f:
        json.dump(dict(PROBE_CONFIG, routine=routine), f)
    _add_entries(
        root,
        config={"name": "probe", "source": "test",
                "file": PROBE_FILE, "reduced": [],
                "why": "test"},
        cell={"name": "probe.cell", "config": "probe", "traffic": "library",
              "chips": 1, "why": "test"})


def test_new_routine_joins_by_new_files(tmp_path, capsys, monkeypatch):
    root, tiny = str(tmp_path / "repo"), str(tmp_path / "tiny")
    os.makedirs(root)
    os.makedirs(tiny)
    before = _copy(root)
    _write(root, "routines/probe_solve.py", PROBE_CALL)
    _write(root, "work/probe_solve.py", PROBE_WORK)
    _add_probe(root, "probe_solve")

    parts = harness.parts(root)
    assert parts["cells"]["probe.cell"]["routine"] == \
        "bench_routine_probe_solve"
    assert "solve_s" in parts["cells"]["probe.cell"]["end_to_end"]
    _unchanged(before)
    # the CPU rehearsal cuts it by the one rule, and the fault and
    # control tests take it up
    make_tiny(tiny, root)
    cfg = harness.load_json(os.path.join(tiny, PROBE_FILE))
    assert (cfg["n"], cfg["nb"], cfg["nrhs"]) == (256, 64, 2)
    assert "probe.cell" in library_cells(root)
    assert ("probe", 512) in control_cases(root)
    # and the run path calls its routine from the copy: correct as it
    # stands, not correct with each fault planted in it, and its control
    # fails
    rc, res = run_cell(tiny, "probe.cell", seconds=0.5, capsys=capsys)
    assert rc == 0 and res["correct"] is True and res["attempted"] > 0
    for fault in FAULT_KINDS:
        fault_fails(tiny, "probe.cell", fault, monkeypatch, capsys)
    control_fails(root, "probe", 512)


@pytest.mark.parametrize("gap,fails", [(2e-5, True), (5e-6, False)])
def test_chip_readings_stand_in_for_the_cpu_control(tmp_path, gap, fails):
    """A routine whose control no CPU size separates names its chip
    readings instead; the control test holds them to the config's
    limits."""
    root = str(tmp_path)
    _copy(root)
    _write(root, "routines/probe_solve.py", PROBE_CALL.replace(
        "CONTROL_N = (512,)",
        f"CONTROL_N = ()\nCONTROL_CHIP = ({{'gap': {gap}}},) * 3"))
    _write(root, "work/probe_solve.py", PROBE_WORK)
    _add_probe(root, "probe_solve")
    assert ("probe", None) in control_cases(root)
    if fails:
        control_fails(root, "probe", None)
    else:
        with pytest.raises(AssertionError):
            control_fails(root, "probe", None)


def test_routine_without_file_is_named(tmp_path):
    root = str(tmp_path)
    _copy(root)
    _add_probe(root, "no_such_routine")
    with pytest.raises(harness.HarnessError,
                       match="routines/no_such_routine.py"):
        harness.parts(root)


def test_every_named_part_resolves():
    parts = harness.parts()
    bench = harness.benchmark()
    assert set(parts["cells"]) == {w["name"] for w in bench["workloads"]}
    for name, c in parts["cells"].items():
        assert "setup_s" in c["end_to_end"] and len(c["end_to_end"]) >= 2
        assert c["per_layer"], name
