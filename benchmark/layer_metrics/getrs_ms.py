"""Device busy ms per traced solve in the LU solve: the ``getrs.permute``,
``getrs.trsm_lower`` and ``getrs.trsm_upper`` phases
(benchmark/scopes.py)."""

import scopes

PHASES = ("getrs.permute", "getrs.trsm_lower", "getrs.trsm_upper")


def read(run):
    return scopes.ms_per_solve(run, PHASES)
