"""Share of device busy time in ops under no phase scope: mostly the
drivers' tile/global copies and padding.  The coverage check of the
phase metrics: a high reading means the phases miss work
(benchmark/scopes.py)."""

import scopes


def read(run):
    return scopes.unscoped_share(run)
