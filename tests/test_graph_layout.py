"""Layout construction invariants + hypothesis round-trip properties."""
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st

from repro.graph import (build_layout, from_edges, grid2d, ring, rmat, star,
                         uniform_random)
from repro.core.cost import CostModel


def _check_layout(g, L):
    # gather tiles are destination-major (paper: read bin[:][p'] columns)
    assert np.all(np.diff(L.tile_dst_part) >= 0)
    assert L.tile_first.sum() == len(np.unique(L.tile_dst_part))
    v = L.edge_valid
    assert v.sum() == g.m
    # (src, dst) multiset reconstructed from the dc_bin layout
    gsrc = L.png_src[L.msg_slot[v]]
    recon = sorted(zip(gsrc.tolist(), L.edge_dst[v].tolist()))
    orig = sorted(zip(np.repeat(np.arange(g.n), g.out_degrees()).tolist(),
                      g.indices.tolist()))
    assert recon == orig
    # local ids consistent with tile partition metadata
    sp = L.tile_src_part.repeat(L.edge_tile)[v]
    dp = L.tile_dst_part.repeat(L.edge_tile)[v]
    assert np.all(gsrc == sp * L.q + L.edge_src_local[v])
    assert np.all(L.edge_dst[v] == dp * L.q + L.edge_dst_local[v])
    # PNG slots: one per unique (src, dst-partition) pair
    real_slots = L.png_src < L.n_pad
    pairs = set()
    for s, d in zip(gsrc.tolist(), (L.edge_dst[v] // L.q).tolist()):
        pairs.add((s, d))
    assert real_slots.sum() == len(pairs)
    # per-partition Eq.1 constants
    assert L.part_edges.sum() == g.m
    assert L.part_msgs.sum() == real_slots.sum()


@pytest.mark.parametrize("maker", [
    lambda: rmat(8, 8, seed=1),
    lambda: uniform_random(100, 700, seed=2),
    lambda: ring(37),
    lambda: star(50),
    lambda: grid2d(9, 7),
])
@pytest.mark.parametrize("k", [1, 4, 8])
def test_layout_invariants(maker, k):
    g = maker()
    L = build_layout(g, k=k, edge_tile=16, msg_tile=8)
    _check_layout(g, L)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_layout_roundtrip_random(data):
    n = data.draw(st.integers(2, 60))
    m = data.draw(st.integers(1, 300))
    seed = data.draw(st.integers(0, 10_000))
    rng = np.random.default_rng(seed)
    g = from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n=n,
                   dedup=True)
    k = data.draw(st.sampled_from([1, 2, 4, 7]))
    L = build_layout(g, k=min(k, n), edge_tile=8, msg_tile=8)
    _check_layout(g, L)


def test_cost_model_mode_choice():
    g = rmat(8, 8, seed=3)
    L = build_layout(g, k=8, edge_tile=16, msg_tile=8)
    cm = CostModel.from_layout(L)
    k = L.k
    # no active edges anywhere -> nothing runs DC
    none = cm.choose_dc(np.zeros(k), np.zeros(k, bool))
    assert not none.any()
    # everything active -> dense partitions choose DC (paper: PageRank)
    all_dc = cm.choose_dc(L.part_edges, L.part_edges > 0)
    assert all_dc[L.part_edges > 0].all()
    b = cm.bytes_for(all_dc, L.part_edges, L.part_edges > 0)
    assert b["sc_bytes"] == 0 and b["dc_bytes"] > 0


def test_shard_gather_side_in_destination_order():
    """DistEngine's fused DC step folds each device's gather-side stream
    without sorting it: shard_layout keeps it in destination order (the
    sentinel ``nv`` and the padding last) and drops no edge."""
    from repro.graph.shard import shard_layout
    g = rmat(9, 8, seed=3)
    L = build_layout(g, k=8, edge_tile=64, msg_tile=32)
    SL = shard_layout(L, 4)
    dl, ok = SL.in_dst_local, SL.in_valid
    assert np.all(np.diff(dl, axis=1) >= 0)
    assert np.all(dl[ok] < SL.nv) and np.all(dl[~ok] == SL.nv)
    glob = dl + (np.arange(SL.D) * SL.nv)[:, None]
    assert np.array_equal(np.sort(glob[ok]),
                          np.sort(L.edge_dst[L.edge_valid]))
