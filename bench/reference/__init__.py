"""Plain reference counts, one module per pattern (``reference/<pattern>.py``
with ``count(n, edges) -> int``), found by the pattern's name in the
traffic mix.

The guarantee every count keeps: the exact number of non-induced
embeddings of the pattern in the simple undirected graph that ``edges``
spans (self loops dropped, duplicates merged), counted once per vertex
set and edge set, that is up to the pattern's automorphisms.  Nothing
here imports the program under test.
"""
from __future__ import annotations

import importlib

import numpy as np
import scipy.sparse as sp


def adjacency(n: int, edges: np.ndarray) -> sp.csr_matrix:
    """Symmetric 0/1 int64 adjacency of the simple graph ``edges`` spans."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[e[:, 0] != e[:, 1]]
    lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
    key = np.unique(lo * n + hi)
    u, v = key // n, key % n
    ones = np.ones(2 * key.shape[0], dtype=np.int64)
    return sp.csr_matrix((ones, (np.concatenate([u, v]),
                                 np.concatenate([v, u]))), shape=(n, n))


def counter(pattern: str):
    """``count`` of ``reference/<pattern>.py``."""
    return importlib.import_module(f"{__name__}.{pattern}").count
