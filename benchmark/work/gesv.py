"""Model work of an LU solve with partial pivoting, A (n x n) X = B
(n x nrhs): the operations the routine needs, whatever schedule runs it.

Operations: LAPACK Working Note 41 (getrf 2/3 n^3 - 1/2 n^2 + 5/6 n for
m = n, getrs 2 n^2 nrhs), with getrf taken as HPL 2.3 takes it,
2/3 n^3 - 1/2 n^2, so that nrhs = 1 gives HPL's own count
2/3 n^3 + 3/2 n^2.

Bytes: the least a solve must move through HBM: read A and write its
factor once, read B and write X once.
"""


def ops(n: int, nrhs: int) -> float:
    return 2.0 / 3.0 * n**3 - 0.5 * n**2 + 2.0 * n**2 * nrhs


def bytes_moved(n: int, nrhs: int, itemsize: int) -> float:
    return float(itemsize) * (2.0 * n * n + 2.0 * n * nrhs)
