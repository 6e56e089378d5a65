"""Both generators' edge counts against their formulas at small n."""
import math

import numpy as np
import pytest

from bench.harness import BENCH, load_module

kron = load_module(BENCH / "generators" / "kronecker.py")
rgg = load_module(BENCH / "generators" / "rgg.py")


def test_kronecker_draws_edge_factor_times_n():
    cfg = {"scale": 10, "edge_factor": 16, "a": 0.57, "b": 0.19,
           "c": 0.19}
    n, src, dst = kron.edges(cfg, 3)
    assert n == 1024
    assert len(src) == len(dst) == kron.expected_edges(cfg) == 16 * 1024
    assert src.min() >= 0 and max(src.max(), dst.max()) < n
    # quadrant A (both bits 0) is taken with probability a at each level:
    # the share of edges whose top bits are both 0 is near a
    top = 1 << 9
    share = np.mean((src < top) & (dst < top))
    assert abs(share - 0.57) < 4 * math.sqrt(0.57 * 0.43 / len(src))


def test_kronecker_same_key_same_edges():
    cfg = {"scale": 8, "edge_factor": 4, "a": 0.57, "b": 0.19, "c": 0.19}
    a = kron.edges(cfg, 7)
    b = kron.edges(cfg, 7)
    c = kron.edges(cfg, 8)
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
    assert not np.array_equal(a[1], c[1])


@pytest.mark.parametrize("scale", [12, 14])
def test_rgg_edge_count_matches_formula(scale):
    cfg = {"scale": scale, "radius_coeff": 0.55}
    n, u, v = rgg.edges(cfg, 1)
    want = rgg.expected_edges(cfg)
    # pair counts of a random geometric graph spread about like a
    # Poisson count of the same mean, within a few of its deviations
    assert abs(len(u) - want) < 5 * math.sqrt(want)


def test_rgg_pairs_are_closer_than_r_once_each():
    cfg = {"scale": 10, "radius_coeff": 0.55}
    n, u, v = rgg.edges(cfg, 2)
    r = rgg.radius(cfg)
    pts = np.random.default_rng(2).random((n, 2))
    d = np.linalg.norm(pts[u] - pts[v], axis=1)
    assert (d < r).all() and (u != v).all()
    keys = np.minimum(u, v).astype(np.int64) * n + np.maximum(u, v)
    assert len(np.unique(keys)) == len(keys)
    # every pair closer than r is found (brute force at n = 1024)
    dd = np.linalg.norm(pts[:, None] - pts[None], axis=2)
    assert int(np.triu(dd < r, 1).sum()) == len(u)
