"""Model work of a Cholesky solve, A (n x n, SPD, lower triangle) X = B
(n x nrhs): the operations the routine needs, whatever schedule runs it.

Operations: LAPACK Working Note 41, potrf n^3/3 + n^2/2 + n/6 (its
n^3/6 + n^2/2 + n/3 multiplications plus n^3/6 - n/6 additions) and
potrs 2 n^2 nrhs.

Bytes: the least a solve must move through HBM: read the lower
triangle of A and write L once, read B and write X once.
"""


def ops(n: int, nrhs: int) -> float:
    return n**3 / 3.0 + n**2 / 2.0 + n / 6.0 + 2.0 * n**2 * nrhs


def bytes_moved(n: int, nrhs: int, itemsize: int) -> float:
    return float(itemsize) * (n * (n + 1.0) + 2.0 * n * nrhs)
