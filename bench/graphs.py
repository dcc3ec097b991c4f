"""The benchmark's own graph generators, each the published construction
of a benchmark graph family.

``kronecker_edges`` is the Graph 500 specification's Kronecker generator
(its reference ``kronecker_generator``, step for step): ``edgefactor *
2**scale`` edge tuples, each bit of each end drawn with the initiator
probabilities A=0.57, B=0.19, C=0.19, D=0.05.  ``delaunay_edges`` is the
DIMACS10 ``delaunay_n<k>`` construction: the Delaunay triangulation of
``2**k`` points drawn uniformly from the unit square.

``make_edges`` draws a configuration's graph from its fixed generator
seed and shuffles the order of its edge tuples by the run's ``--seed``:
every seed gets the same graph, and so the same counts, waves and
compiled shapes, from an edge list in another order.  (A relabelling of
the vertices by the run's seed would move the engine's work: on the
Kronecker graph at scale 11 its waves per query ranged 35-41 over four
relabellings.)
"""
from __future__ import annotations

import numpy as np

# Graph 500 initiator probabilities (D = 1 - A - B - C = 0.05)
KRONECKER_ABC = (0.57, 0.19, 0.19)


def kronecker_edges(scale: int, edgefactor: int,
                    seed: int) -> tuple[int, np.ndarray]:
    """The Graph 500 Kronecker generator: the raw ``(edgefactor *
    2**scale, 2)`` tuple list, self loops and repeats included as the
    specification leaves them, its vertices renamed by a permutation drawn
    after the bits from the same ``seed``."""
    n = 1 << scale
    m = edgefactor * n
    a, b, c = KRONECKER_ABC
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    rng = np.random.default_rng(seed)
    ij = np.zeros((2, m), dtype=np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    return n, rng.permutation(n)[ij.T]


def delaunay_edges(k: int, seed: int) -> tuple[int, np.ndarray]:
    """The edges of the Delaunay triangulation of ``2**k`` uniform random
    points in the unit square (each triangle's three sides; shared sides
    repeat).  A vertex is named by its point's place in the draw, which
    is random with respect to its position."""
    from scipy.spatial import Delaunay

    n = 1 << k
    pts = np.random.default_rng(seed).random((n, 2))
    tri = Delaunay(pts).simplices.astype(np.int64)
    return n, np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]],
                              tri[:, [0, 2]]])


GENERATORS = {"kronecker": kronecker_edges, "delaunay": delaunay_edges}


def make_edges(generator: dict, seed: int) -> tuple[int, np.ndarray]:
    """The configuration's graph (``generator``: ``kind`` plus the
    generator's keyword arguments, its own ``seed`` among them), its edge
    tuples shuffled by the run's ``seed`` (any whole number; negative ones
    wrap to 64 bits)."""
    kw = dict(generator)
    n, edges = GENERATORS[kw.pop("kind")](**kw)
    order = np.random.default_rng(seed % (1 << 64)).permutation(len(edges))
    return n, edges[order]
