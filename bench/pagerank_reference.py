"""The plain reference for PageRank cells: LDBC Graphalytics' PageRank in
float64, sharing no code with the system under test.

The graph is built with ``scipy.sparse`` straight from the benchmark's
generated edge list by the rule the system's ingest follows: both
directions of every edge, duplicates merged, a self loop kept once.
Graphalytics' definition (its specification's PageRank section), for a
fixed number of iterations and damping ``d``::

    PR_0(v) = 1/n
    PR_i(v) = (1-d)/n + d * sum_{u->v} PR_{i-1}(u)/|out(u)|
                      + (d/n) * sum_{w in D} PR_{i-1}(w)

``D`` being the dangling vertices (no out-edge).  :func:`compare` holds an
answer to the guarantees the configuration states
(``bench/configs/graphalytics-graph500-22.json``), with the limits
``rank_rtol`` and ``mass_tol`` of the traffic file
(``bench/traffic/pagerank.json``):

* ``rank``: every vertex's rank within a relative ``rank_rtol`` of the
  reference's, or ``rank_rtol`` / n where the rank is near 0;
* ``mass``: the ranks, summed in float64, within ``mass_tol`` of 1.
"""
from __future__ import annotations

import numpy as np


class Graph:
    """Undirected graph from an edge list, self loops kept once."""

    def __init__(self, n: int, u, v):
        import scipy.sparse as sp
        u, v = np.asarray(u), np.asarray(v)
        ones = np.ones(2 * len(u), np.float64)
        a = sp.coo_matrix((ones, (np.concatenate([u, v]),
                                  np.concatenate([v, u]))),
                          shape=(n, n)).tocsr()
        a.sum_duplicates()
        a.data[:] = 1.0
        self.n = n
        self.csr = a
        self.deg = np.diff(a.indptr).astype(np.float64)

    def edges(self) -> int:
        """Undirected edges, self loops left out: the ``m`` of TEPS, as
        LDBC Graphalytics and Graph500 count a graph's edges."""
        loops = int(np.count_nonzero(self.csr.diagonal()))
        return (self.csr.nnz - loops) // 2

    def pagerank(self, iters: int, damping: float, dangling: bool = True,
                 dtype=np.float64) -> np.ndarray:
        """``iters`` iterations from ``1/n``; ``dangling=False`` leaves the
        dangling term out (the rank of vertices without edges leaks).
        ``dtype`` is the precision the ranks and the shares are kept in
        (each rounded to it as it is made; the sums run in float64)."""
        n, deg = self.n, self.deg
        out = deg > 0

        def rnd(a):
            return np.asarray(a, dtype).astype(np.float64)

        x = rnd(np.full(n, 1.0 / n))
        for _ in range(iters):
            share = rnd(np.divide(x, deg, out=np.zeros(n), where=out))
            lost = x[~out].sum() if dangling else 0.0
            # the matrix is symmetric: in-edges are out-edges
            x = rnd((1.0 - damping + damping * lost) / n + damping * (
                self.csr @ share))
        return x


def compare(got, want, rank_rtol: float, mass_tol: float) -> dict:
    """Counts of broken guarantees: ``{"rank": ..., "mass": ...}``, both
    0 for a correct answer."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    tol = np.maximum(rank_rtol * np.abs(want), rank_rtol / len(want))
    bad = ~(np.abs(got - want) <= tol)          # NaN counts as off
    mass = abs(got.sum() - 1.0)
    return {"rank": int(bad.sum()), "mass": int(not mass <= mass_tol)}


def max_rel_err(got, want) -> float:
    """The largest relative error over the vertices (the floor of
    :func:`compare` as its denominator near 0)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    den = np.maximum(np.abs(want), 1.0 / len(want))
    return float((np.abs(got - want) / den).max())
