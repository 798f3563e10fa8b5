"""``correct`` comes out false: for the control (the reference one
precision below the configuration, in the program's place), and for the
run path driven with the timed path broken underneath, once per fault
the cell can have."""

import numpy as np
import pytest

import harness
import reference
from conftest import control_cases, library_cells, run_cell

CONTROLS = control_cases()


def control_fails(root, config, n):
    """Every reading of ``config``'s control fails one of its limits."""
    entry = {c["name"]: c for c in harness.benchmark(root)["configs"]}
    cfg = harness.load_json(f"{root}/{entry[config]['file']}")
    mod = harness.routine(cfg["routine"], root)
    lim = reference.limits(cfg)
    if n is None:
        readings = list(mod.CONTROL_CHIP)
        assert len(readings) >= 3
    else:
        readings = []
        for seed in (11, 12, 13):
            A, B = reference.host_operands(cfg, n, cfg["nrhs"], seed, 0)
            X = np.asarray(mod.control(cfg, A, B), np.float64)
            readings.append(
                reference.numbers(cfg, A, X, B, reference.solve(A, B)))
    for got in readings:
        assert any(not got[m] <= lim[m] for m in got if m in lim), got


@pytest.mark.parametrize("config,n", CONTROLS,
                         ids=[f"{c}-{n or 'chip'}" for c, n in CONTROLS])
def test_control_is_not_correct(config, n):
    """The control of every config, as its routine's file says to check
    it: run on the CPU at each of its ``CONTROL_N``, or, where it has
    none, its ``CONTROL_CHIP`` readings at the cell's own size."""
    control_fails(harness.ROOT, config, n)


# -- faults planted in the program, the rest of a run as it stands -------


def _unchanged(call):
    def fake(st, A, B, nb, opts):
        return B
    return fake


def _altered(call):
    def fake(st, A, B, nb, opts):
        X = call(st, A, B, nb, opts)
        return X.at[(0,) * X.ndim].multiply(1 + 1e-3)
    return fake


FAULT_KINDS = (_unchanged, _altered)


def plant(monkeypatch, fault):
    """Every routine file the run loads gets ``fault`` around its
    ``call``: the fault follows ``routines/<routine>.py``."""
    real = harness.routine

    def routine(name, root=harness.ROOT):
        mod = real(name, root)
        mod.call = fault(mod.call)
        return mod

    monkeypatch.setattr(harness, "routine", routine)


def fault_fails(root, cell, fault, monkeypatch, capsys):
    plant(monkeypatch, fault)
    rc, res = run_cell(root, cell, seconds=0.5, capsys=capsys)
    monkeypatch.undo()
    assert rc == 0 and res["correct"] is False and res["failed"] > 0


PLANTED = [(c, f) for c in library_cells() for f in FAULT_KINDS]


@pytest.mark.parametrize("cell,fault", PLANTED,
                         ids=[f"{c}-{f.__name__}" for c, f in PLANTED])
def test_library_fault_is_not_correct(tiny_root, capsys, monkeypatch, cell,
                                      fault):
    fault_fails(tiny_root, cell, fault, monkeypatch, capsys)
