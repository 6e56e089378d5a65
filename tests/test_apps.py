"""Graph applications vs independent oracles (scipy/networkx), all engine
modes (hybrid / forced-SC / forced-DC) and the Pallas path."""
import numpy as np
import pytest
import scipy.sparse.csgraph as csg

from repro.apps import (bfs, connected_components, nibble, pagerank, sssp)
from repro.graph import build_layout, from_edges, grid2d, rmat, to_scipy


@pytest.fixture(scope="module")
def g_rmat():
    g = rmat(9, 8, seed=1)
    return g, build_layout(g, k=8, edge_tile=64, msg_tile=32)


@pytest.fixture(scope="module")
def g_weighted():
    g = rmat(9, 8, seed=2, weighted=True)
    return g, build_layout(g, k=8, edge_tile=64, msg_tile=32)


def _bfs_ref(g, src):
    d = csg.shortest_path(to_scipy(g), method="D", unweighted=True,
                          indices=src)
    return np.where(np.isinf(d), -1, d).astype(int)


@pytest.mark.parametrize("mode,pallas", [("hybrid", False), ("sc", False),
                                         ("dc", False), ("hybrid", True)])
def test_bfs(g_rmat, mode, pallas):
    g, L = g_rmat
    src = int(np.argmax(g.out_degrees()))
    res = bfs(L, source=src, mode=mode, use_pallas=pallas)
    assert np.array_equal(res["level"], _bfs_ref(g, src))
    # parents form a valid BFS tree: parent level = level - 1
    lv, par = res["level"], res["parent"]
    reached = (lv > 0)
    assert np.all(lv[par[reached]] == lv[reached] - 1)


def test_bfs_grid_large_diameter():
    g = grid2d(17, 13)
    L = build_layout(g, k=4, edge_tile=32, msg_tile=16)
    res = bfs(L, source=0)
    assert np.array_equal(res["level"], _bfs_ref(g, 0))


@pytest.mark.parametrize("mode,pallas", [("hybrid", False), ("sc", False),
                                         ("dc", False), ("hybrid", True)])
def test_sssp(g_weighted, mode, pallas):
    g, L = g_weighted
    src = int(np.argmax(g.out_degrees()))
    res = sssp(L, source=src, mode=mode, use_pallas=pallas)
    ref = csg.shortest_path(to_scipy(g), method="D", indices=src)
    fin = ~np.isinf(ref)
    assert np.array_equal(np.isinf(res["dist"]), ~fin)
    np.testing.assert_allclose(res["dist"][fin], ref[fin], atol=1e-5)


def _pr_ref(g, iters, d=0.85):
    """LDBC Graphalytics PageRank: the rank of vertices without
    out-edges is spread over every vertex."""
    x = np.full(g.n, 1.0 / g.n)
    P = to_scipy(g)
    outdeg = g.out_degrees()
    for _ in range(iters):
        c = np.where(outdeg > 0, x / np.maximum(outdeg, 1), 0.0)
        x = (1 - d) / g.n + d * (P.T @ c + x[outdeg == 0].sum() / g.n)
    return x


@pytest.mark.parametrize("fused", [True, False])
def test_pagerank(g_rmat, fused):
    g, L = g_rmat
    pr = pagerank(L, iters=10, fused=fused)["pr"]
    np.testing.assert_allclose(pr, _pr_ref(g, 10), atol=1e-6)


def test_pagerank_pallas(g_rmat):
    g, L = g_rmat
    pr = pagerank(L, iters=5, fused=False, use_pallas=True)["pr"]
    np.testing.assert_allclose(pr, _pr_ref(g, 5), atol=1e-5)


def test_connected_components(g_rmat):
    g, _ = g_rmat
    # symmetrize -> weakly connected components
    src = np.repeat(np.arange(g.n), g.out_degrees())
    gs = from_edges(np.concatenate([src, g.indices]),
                    np.concatenate([g.indices, src]), n=g.n, dedup=True)
    L = build_layout(gs, k=8, edge_tile=64, msg_tile=32)
    ours = connected_components(L)["label"]
    ncc, ref = csg.connected_components(to_scipy(gs), directed=False)
    for comp in range(ncc):
        assert len(np.unique(ours[ref == comp])) == 1
    assert len(np.unique(ours)) == ncc


def test_nibble_selective_continuity(g_rmat):
    """Nibble's defining properties (paper Alg. 3/4): probability mass is
    conserved below 1, support stays local, and initFunc keeps seeds active
    across iterations independent of gather updates."""
    g, L = g_rmat
    seed = int(np.argmax(g.out_degrees()))
    res = nibble(L, seeds=[seed], eps=1e-3, max_iters=30)
    pr = res["pr"]
    assert 0 < pr.sum() <= 1.0 + 1e-5
    assert pr[seed] > 0
    # support must be within the BFS-reachable set of the seed
    lv = _bfs_ref(g, seed)
    assert np.all(pr[lv < 0] == 0)


def test_nibble_work_efficiency(g_rmat):
    """Iterations touch ~local neighborhoods: modeled bytes stay far below
    one full-graph DC sweep (the paper's theoretical-efficiency claim)."""
    g, L = g_rmat
    seed = int(np.argmax(g.out_degrees()))
    res = nibble(L, seeds=[seed], eps=5e-3, max_iters=30, mode="hybrid")
    total = sum(s.dc_bytes + s.sc_bytes for s in res["stats"])
    full_sweep = float(L.dc_cost_bytes().sum())
    assert total < full_sweep
