"""The plain reference and the comparison that decides ``correct``.  The
control (the reference computed one precision below the configuration)
is each routine's own, in ``routines/<routine>.py``.

Nothing here imports the program.  The reference rebuilds every operand
from the seed (``gen``), solves it in float64 with numpy's LAPACK, and
compares the program's X in two numbers:

``residual``
    the backward error by the configuration's own rule: HPL's scaled
    residual ||Ax - b||_inf / (eps (||A||_inf ||x||_inf + ||b||_inf) N)
    (limit 16, HPL 2.3), or the SLATE tester's ||B - AX||_1 /
    (||A||_1 ||X||_1 n) (limit 3 eps, test_posv).  The configuration
    states the limit.
``gap``
    max |X - X_ref| / max |X_ref| against the reference's solution; its
    limit lies between the program's readings and the control's
    (PERF.md gives both).
"""

from __future__ import annotations

import numpy as np

import gen

EPS = {"float64": 2.0**-52, "float32": 2.0**-23}
#: HPL 2.3 takes eps from dlamch('Epsilon'), the unit round-off 2^-53
HPL_EPS = 2.0**-53


def host_operands(config: dict, n: int, nrhs: int, seed: int, k: int):
    """Operand pair ``k`` of a run, rebuilt on the host in the
    configuration's dtype and returned in float64 (exact)."""
    dt = np.dtype(config["dtype"])
    ka, kb = gen.key(seed, 2 * k), gen.key(seed, 2 * k + 1)
    if config["matrix"] == "hpl_uniform":
        A = gen.general(np, ka, n, n, dt)
    elif config["matrix"] == "tester_spd":
        A = gen.spd(np, ka, n, dt)
    else:
        raise ValueError(f"unknown matrix construction {config['matrix']!r}")
    B = gen.general(np, kb, n, nrhs, dt)
    return A.astype(np.float64), B.astype(np.float64)


def solve(A, B):
    """The plain reference: LAPACK gesv in float64."""
    return np.linalg.solve(A, B)


def residual(rule: str, dtype: str, A, X, B) -> float:
    X = np.asarray(X, np.float64)
    R = B - A @ X
    if rule == "hpl":
        n = A.shape[0]
        inf = lambda M: float(np.abs(M).sum(axis=1).max())  # noqa: E731
        return inf(R) / (HPL_EPS * (inf(A) * inf(X) + inf(B)) * n)
    if rule == "tester":
        one = lambda M: float(np.abs(M).sum(axis=0).max())  # noqa: E731
        return one(R) / (one(A) * one(X) * A.shape[1]) / EPS[dtype]
    raise ValueError(f"unknown residual rule {rule!r}")


def gap(X, X_ref) -> float:
    X = np.asarray(X, np.float64)
    return float(np.abs(X - X_ref).max() / np.abs(X_ref).max())


def numbers(config: dict, A, X, B, X_ref) -> dict:
    """The two compared numbers of one answer (NaN-safe: a NaN reads as
    infinite, and fails every limit)."""
    out = {
        "residual": residual(config["residual_rule"], config["dtype"], A, X, B),
        "gap": gap(X, X_ref),
    }
    return {k: (v if np.isfinite(v) else float("inf")) for k, v in out.items()}


def limits(config: dict) -> dict:
    """The residual limit is the configuration's own (in units of its
    eps for the tester rule); the gap limit is set from the readings in
    PERF.md."""
    return {"residual": float(config["residual_limit"]),
            "gap": float(config["gap_limit"])}


def judge(worst: dict, lim: dict) -> list:
    """[(name, value, limit, ok)] in a fixed order."""
    return [(k, worst[k], lim[k], bool(worst[k] <= lim[k])) for k in lim]

