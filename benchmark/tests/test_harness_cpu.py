"""CPU rehearsal of the whole run path at tiny sizes, and the refusals."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, run_cell

CELLS = [w["name"] for w in json.load(
    open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_on_cpu(tiny_root, capsys, cell):
    rc, res = run_cell(tiny_root, cell, capsys=capsys)
    assert rc == 0 and res is not None
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert "setup_s" in res["metrics"]
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) >= {"residual", "gap"}


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_per_layer(tiny_root, capsys, cell):
    rc, res = run_cell(tiny_root, cell, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


def test_refuses_cpu_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_refuses_unknown_workload(capsys):
    import run

    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1",
                     "--trace", "0"], allow_cpu=True) == 2


def test_refuses_directory_without_benchmark(tmp_path):
    """Only BENCHMARK.json's parts are missing: no result, non-zero."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    os.makedirs(tmp_path / "benchmark")
    for f in ("run.py", "harness.py"):
        with open(os.path.join(ROOT, "benchmark", f)) as src:
            (tmp_path / "benchmark" / f).write_text(src.read())
    p = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120, cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_same_seed_same_operands():
    import numpy as np

    import gen
    import jax.numpy as jnp

    for dt in (np.float32, np.float64):
        k = gen.key(3_000_000_019, 5)
        host = gen.spd(np, k, 96, np.dtype(dt))
        dev = np.asarray(gen.spd(jnp, k, 96, jnp.dtype(dt)))
        assert host.dtype == dev.dtype and np.array_equal(host, dev)
        assert np.array_equal(host, host.T)
    assert gen.key(1, 0) != gen.key(1 + 2**32, 0)
    assert json.dumps(gen.key(2**40 + 3, 7))  # seeds past 32 bits fold in


def test_memory_peak_counts_reserved_program_bytes():
    """The fullest chip's peak: buffers in use plus the bytes reserved
    for loaded programs (their temporaries)."""
    import harness

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devs = [Dev({"peak_bytes_in_use": 5, "peak_bytes_reserved": 7}),
            Dev({"peak_bytes_in_use": 9}), Dev(None)]
    assert harness.memory_peak(devs) == 12
    assert harness.memory_peak([Dev(None)]) == 0
