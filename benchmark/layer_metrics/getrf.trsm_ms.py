"""Device busy ms per traced solve in the ``getrf.trsm`` phase (the LU U-row triangular solve;
benchmark/scopes.py)."""

import scopes


def read(run):
    return scopes.ms_per_solve(run, ("getrf.trsm",))
