"""Device busy ms per traced solve in the ``getrf.swap`` phase (the LU row exchange and the panel write-back;
benchmark/scopes.py)."""

import scopes


def read(run):
    return scopes.ms_per_solve(run, ("getrf.swap",))
