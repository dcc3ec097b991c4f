"""Squares (4-cycles, chords allowed).

Closed walks of length 4 are the 8 traversals of each 4-cycle, the 2m
back-and-forth walks on one edge, and the 4 * C(d, 2) walks through two
neighbours of a vertex of degree d:
    C4 = (trace(A^4) - 2m - 4 * sum_v C(d_v, 2)) / 8,
with trace(A^4) the sum of squares of A^2's entries (A is symmetric).
"""
from __future__ import annotations

import numpy as np

from . import adjacency


def count(n: int, edges: np.ndarray) -> int:
    a = adjacency(n, edges)
    a2 = a @ a
    walks = int((a2.data * a2.data).sum())
    d = np.asarray(a.sum(axis=1)).ravel()
    m = int(d.sum()) // 2
    pairs = int((d * (d - 1) // 2).sum())
    num = walks - 2 * m - 4 * pairs
    if num % 8:
        raise ArithmeticError(f"4-cycle walk count {num} is not a multiple "
                              f"of 8")
    return num // 8
