"""The benchmark's own tests: ``pytest benchmark/tests`` on the CPU.

They rehearse the harness on a copy of the benchmark whose cells are cut
to tiny sizes (``tiny_root``); on the CPU the harness is allowed past its
check for a chip only through ``run.main(allow_cpu=True)``.
"""

import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

import harness  # noqa: E402

#: the CPU rehearsal's cut of every config: n and nb at most these; nrhs,
#: dtype and grid as the config states them
CUT_N, CUT_NB = 256, 64


def make_tiny(dst: str, root: str = ROOT) -> str:
    """A copy of ``root``'s BENCHMARK.json and benchmark/ with every config
    cut to a tiny size and a roof for the CPU, so the whole run path works
    here."""
    shutil.copy(os.path.join(root, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(root, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for entry in harness.benchmark(dst)["configs"]:
        p = os.path.join(dst, entry["file"])
        c = harness.load_json(p)
        c.update(n=min(int(c["n"]), CUT_N), nb=min(int(c["nb"]), CUT_NB))
        with open(p, "w") as f:
            json.dump(c, f)
    p = os.path.join(dst, "benchmark", "roofs.json")
    r = harness.load_json(p)
    r["kinds"]["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    with open(p, "w") as f:
        json.dump(r, f)
    return dst


def library_cells(root: str = ROOT) -> list:
    """Every cell whose traffic runs whole library solves: each gets the
    faults of test_faults, planted around its routine's ``call``."""
    return [w["name"] for w in harness.benchmark(root)["workloads"]
            if harness.Cell(w["name"], root).traffic["driver"]
            == "library_solve"]


def control_cases(root: str = ROOT) -> list:
    """(config, n) of the control test: every config of BENCHMARK.json,
    at each size its routine's ``CONTROL_N`` names, or once with n None
    where it names none and the chip readings stand in."""
    out = []
    for entry in harness.benchmark(root)["configs"]:
        c = harness.load_json(os.path.join(root, entry["file"]))
        ns = harness.routine(c["routine"], root).CONTROL_N
        out += [(entry["name"], n) for n in ns] or [(entry["name"], None)]
    return out


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny(str(tmp_path_factory.mktemp("tiny")))


def run_cell(root, cell, seed=3_000_000_019, seconds=1.5, trace=0,
             capsys=None):
    """run.main on the tiny copy; returns (rc, result dict or None)."""
    import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  allow_cpu=True, root=root)
    out = capsys.readouterr().out.strip().splitlines() if capsys else []
    last = json.loads(out[-1]) if out and out[-1].startswith("{") else None
    return rc, last
