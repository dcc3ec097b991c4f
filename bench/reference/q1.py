"""Triangles: trace(A^3) / 6, taken as the sum of A^2 * A over edges."""
from __future__ import annotations

import numpy as np

from . import adjacency


def count(n: int, edges: np.ndarray) -> int:
    a = adjacency(n, edges)
    return int((a @ a).multiply(a).sum()) // 6
