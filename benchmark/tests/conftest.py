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

#: tiny sizes per config (n, nb)
TINY = {"hpl": (256, 64)}


def make_tiny(dst: str) -> str:
    """A copy of BENCHMARK.json and benchmark/ with every cell at a tiny
    size and a roof for the CPU, so the whole run path works here."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, (n, nb) in TINY.items():
        p = os.path.join(dst, "benchmark", "configs", f"{name}.json")
        c = json.load(open(p))
        c.update(n=n, nb=nb)
        json.dump(c, open(p, "w"))
    p = os.path.join(dst, "benchmark", "roofs.json")
    r = json.load(open(p))
    r["kinds"]["cpu"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    json.dump(r, open(p, "w"))
    return dst


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
