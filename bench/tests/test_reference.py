"""The reference counts against brute force, and the generators against
the constructions they copy."""
import itertools

import numpy as np
import pytest

from bench import graphs
from bench.reference import adjacency, counter


def _brute(n, edges, pattern):
    """Embeddings counted once per (vertex set, edge set): every injective
    map that keeps the pattern's edges, over the automorphisms."""
    a = adjacency(n, edges).toarray().astype(bool)
    k = 1 + max(max(e) for e in pattern)
    hits = sum(all(a[m[u], m[v]] for u, v in pattern)
               for m in itertools.permutations(range(n), k))
    aut = sum(all(((p[u], p[v]) in pattern or (p[v], p[u]) in pattern)
                  for u, v in pattern)
              for p in itertools.permutations(range(k)))
    assert hits % aut == 0
    return hits // aut


PATTERNS = {"q1": [(0, 1), (1, 2), (0, 2)],
            "q2": [(0, 1), (1, 2), (2, 3), (0, 3)]}


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("trial", range(6))
def test_reference_equals_brute_force(pattern, trial):
    rng = np.random.default_rng(trial)
    n = 9
    edges = rng.integers(0, n, size=(int(rng.integers(8, 30)), 2))
    assert counter(pattern)(n, edges) == _brute(n, edges,
                                                 PATTERNS[pattern])


def test_reference_known_graphs():
    k5 = np.array(list(itertools.combinations(range(5), 2)))
    assert counter("q1")(5, k5) == 10          # C(5, 3)
    assert counter("q2")(5, k5) == 15          # 3 per 4-set, C(5, 4) sets
    side = 6
    idx = np.arange(side * side).reshape(side, side)
    grid = np.concatenate([
        np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], 1),
        np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], 1)])
    assert counter("q2")(side * side, grid) == (side - 1) ** 2
    assert counter("q1")(side * side, grid) == 0


def test_kronecker_draws_the_initiator_probabilities():
    """Graph 500's generator: edgefactor * 2**scale tuples over 2**scale
    vertices, each bit pair (i, j) of the unpermuted ends drawn as
    (0, 0), (0, 1), (1, 0), (1, 1) with probability A, B, C, D."""
    scale, ef = 10, 16
    n, e = graphs.kronecker_edges(scale, ef, seed=3)
    assert n == 1 << scale and e.shape == (ef * n, 2)
    assert e.min() >= 0 and e.max() < n
    rng = np.random.default_rng(3)
    for _ in range(scale):                 # the bits, then the permutation
        rng.random(ef * n), rng.random(ef * n)
    perm = rng.permutation(n)
    inv = np.argsort(perm)
    raw = inv[e]
    a, b, c = graphs.KRONECKER_ABC
    for bit in range(scale):
        i, j = (raw[:, 0] >> bit) & 1, (raw[:, 1] >> bit) & 1
        freq = [np.mean((i == x) & (j == y)) for x, y in
                ((0, 0), (0, 1), (1, 0), (1, 1))]
        np.testing.assert_allclose(freq, [a, b, c, 1 - a - b - c],
                                   atol=0.01)
    np.testing.assert_array_equal(graphs.kronecker_edges(scale, ef, 3)[1], e)


def test_delaunay_is_a_triangulation():
    """Each triangle's sides once each: 3n - 3 - h edges, h the points on
    the convex hull (Euler's formula for a triangulated point set)."""
    from scipy.spatial import ConvexHull

    k, seed = 9, 4
    n, e = graphs.delaunay_edges(k, seed)
    a = adjacency(n, e)
    pts = np.random.default_rng(seed).random((n, 2))
    h = len(ConvexHull(pts).vertices)
    assert a.nnz // 2 == 3 * n - 3 - h
    assert np.diff(a.indptr).min() >= 2


@pytest.mark.parametrize("kind,kw", [
    ("kronecker", dict(scale=8, edgefactor=16, seed=1)),
    ("delaunay", dict(k=8, seed=1))])
def test_the_seed_shuffles_the_edge_list_only(kind, kw):
    gen = dict(kw, kind=kind)
    n, base = graphs.make_edges(gen, 0)
    for seed in (1, 2 ** 31 + 5, 3 * 10 ** 9, -7):
        n2, e = graphs.make_edges(gen, seed)
        assert n2 == n and e.shape == base.shape
        assert not np.array_equal(e, base)
        assert (adjacency(n, e) != adjacency(n, base)).nnz == 0
    np.testing.assert_array_equal(graphs.make_edges(gen, 9)[1],
                                  graphs.make_edges(gen, 9)[1])
