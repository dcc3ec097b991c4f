"""Graph substrate: CSR, partitioning, border distance, sampler."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                       # hermetic container: vendored fallback
    from _hypothesis_compat import given, settings, strategies as st

from repro.graph import (Graph, edge_cut, erdos_graph, icosahedral_mesh,
                         partition, powerlaw_graph, road_graph,
                         sample_capacities, sample_neighbors)


def test_csr_sorted_dedup():
    g = Graph.from_edges(5, [(0, 1), (1, 0), (0, 1), (2, 3), (3, 3)])
    assert g.n_edges == 2
    assert g.has_edge(1, 0) and not g.has_edge(3, 3)
    assert list(g.neighbors(0)) == [1]


@given(st.integers(2, 6), st.integers(10, 80))
@settings(max_examples=20, deadline=None)
def test_property_partition_preserves_graph(ndev, n):
    g = erdos_graph(n, 4.0, seed=n)
    pg = partition(g, ndev, method="bfs")
    # every original edge exists post-renumber, and degree is preserved
    assert pg.n_real == g.n
    for u in range(0, g.n, max(g.n // 10, 1)):
        nu = pg.old2new[u]
        assert set(pg.new2old[pg.neighbors(nu)]) == set(g.neighbors(u))
    # ownership map: every real vertex owned by exactly its block
    own = pg.old2new[np.arange(g.n)] // pg.stride
    assert own.min() >= 0 and own.max() < ndev


def test_border_distance_definition():
    g = road_graph(100, seed=0)
    pg = partition(g, 4, method="block")
    # Definition 1: BD==0 iff border vertex
    for t in range(4):
        nl = int(pg.n_local[t])
        bd = pg.border_dist[t, :nl]
        br = pg.border[t, :nl]
        assert np.all((bd == 0) == br)
        # BFS property: any vertex at BD=d has a neighbor at BD>=d-1
        for i in range(nl):
            if bd[i] > 0 and bd[i] < (1 << 29):
                nbrs = pg.neighbors(t * pg.stride + i)
                local = nbrs[nbrs // pg.stride == t] - t * pg.stride
                assert (bd[local].min() == bd[i] - 1)


def test_bfs_partition_cuts_fewer_edges_than_hash():
    g = road_graph(400, seed=0)
    from repro.graph.partition import assign_bfs, assign_hash
    cut_bfs = edge_cut(g, assign_bfs(g, 4))
    cut_hash = edge_cut(g, assign_hash(g, 4))
    assert cut_bfs < cut_hash


def test_sampler_shapes_and_validity():
    g = powerlaw_graph(300, 6, seed=2)
    rng = np.random.default_rng(0)
    seeds = rng.choice(g.n, 16, replace=False)
    sub = sample_neighbors(g, seeds, (5, 3), rng)
    mn, me = sample_capacities(16, (5, 3))
    assert sub.nodes.shape == (mn,) and sub.edge_src.shape == (me,)
    ne = int(sub.edge_mask.sum())
    # every sampled edge is a real graph edge
    for i in range(0, ne, max(ne // 20, 1)):
        u = int(sub.nodes[sub.edge_src[i]])
        v = int(sub.nodes[sub.edge_dst[i]])
        assert g.has_edge(u, v)


def test_icosahedral_multimesh_counts():
    for r in (0, 1, 2):
        v, e = icosahedral_mesh(r)
        assert v.shape[0] == 10 * 4 ** r + 2
        assert e.shape[0] == 30 * sum(4 ** i for i in range(r + 1))
        np.testing.assert_allclose(np.linalg.norm(v, axis=1), 1.0, rtol=1e-5)


# sha256 of (indptr, indices) as generated before powerlaw_graph's pool
# became preallocated: the O(n·m) generator must draw the same graphs
POWERLAW_PINS = {
    "dblp_bench": (
        "467c18098fcb3c29ca3189d84b4b1eecc010cb844aeb2053692b5323c207ac3b",
        "09d4ff3d67093f56ab7f2be3836e0158491b6e4e540e212a43f290a6dbab7123"),
    "livejournal_bench": (
        "133ffaa69deaa714e80450ff22255ad3ffe700f1e209ba01094a5ad88289e792",
        "0f9dbd009508d4387f62a178a1e122e34d4668a3c72238aa7ae508162495edd4"),
    "uk2002_bench": (
        "c0de9d2a4f47862c1c84d2fa35194971bf0a7074cc458c3872a8833be8adaadd",
        "c5734096d39ee847913b1fa79191773b0493f111fb0fe235dc80b576b60fb023"),
}


@pytest.mark.parametrize("name", sorted(POWERLAW_PINS))
def test_powerlaw_graph_pinned(name):
    import hashlib

    from repro.graph import load_dataset

    g = load_dataset(name)
    assert g.indptr.dtype == np.int64 and g.indices.dtype == np.int32
    got = (hashlib.sha256(g.indptr.tobytes()).hexdigest(),
           hashlib.sha256(g.indices.tobytes()).hexdigest())
    assert got == POWERLAW_PINS[name]
