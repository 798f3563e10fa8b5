"""Device busy ms per traced solve in the ``getrf.update`` phase (the LU trailing update;
benchmark/scopes.py)."""

import scopes


def read(run):
    return scopes.ms_per_solve(run, ("getrf.update",))
