"""Device busy ms per traced solve in the ``getrf.panel`` phase (the LU panel: roll, mask, panel_lu and the step permutation;
benchmark/scopes.py)."""

import scopes


def read(run):
    return scopes.ms_per_solve(run, ("getrf.panel",))
