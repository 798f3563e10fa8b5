"""``correct`` comes out false: for the control (the reference one
precision below the configuration, in the program's place), and for the
run path driven with the timed path broken underneath, once per fault
the cell can have."""

import numpy as np
import pytest

import reference
import harness
from conftest import run_cell


@pytest.mark.parametrize("n", [512, 1024])
def test_control_is_not_correct(tiny_root, n):
    """The control at sizes a test run holds (the chip readings at the
    cell's own size are in PERF.md)."""
    cfg = harness.load_json(f"{tiny_root}/benchmark/configs/hpl.json")
    lim = reference.limits(cfg)
    fails = 0
    for seed in (11, 12, 13):
        A, B = reference.host_operands(cfg, n, cfg["nrhs"], seed, 0)
        X = np.asarray(reference.control_solve(cfg, A, B), np.float64)
        got = reference.numbers(cfg, A, X, B, reference.solve(A, B))
        fails += any(not got[m] <= lim[m] for m in lim)
    assert fails == 3


# -- faults planted in the program, the rest of a run as it stands -------


def _unchanged(orig):
    def fake(A, B, opts=None):
        out = orig(A, B, opts)
        return (B,) + tuple(out[1:])
    return fake


def _altered(orig):
    def fake(A, B, opts=None):
        out = orig(A, B, opts)
        X = out[0]
        data = X.data.at[(0,) * X.data.ndim].multiply(1 + 1e-3)
        return (X._with(data=data),) + tuple(out[1:])
    return fake


FAULTS = [
    ("hpl.f64.n8192", "gesv", _unchanged),
    ("hpl.f64.n8192", "gesv", _altered),
]


@pytest.mark.parametrize("cell,routine,fault", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, _r, f in FAULTS])
def test_library_fault_is_not_correct(tiny_root, capsys, monkeypatch, cell,
                                      routine, fault):
    import slate_tpu as st

    monkeypatch.setattr(st, routine, fault(getattr(st, routine)))
    rc, res = run_cell(tiny_root, cell, seconds=0.5, capsys=capsys)
    assert rc == 0 and res["correct"] is False and res["failed"] > 0
