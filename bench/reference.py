"""The plain reference for BFS cells: a graph and a BFS that share no
code with the system under test.

The graph is built with ``scipy.sparse`` straight from the benchmark's
generated edge list (self loops dropped, duplicates merged, both
directions), the search is ``scipy.sparse.csgraph.breadth_first_order``,
and levels come from its predecessor tree by pointer jumping (a BFS tree
from a queue holds shortest hop counts).

:func:`compare` holds an answer to the guarantees the configurations
state, as Graph500's BFS validation does, plus exact levels:

* ``level``: every vertex's level equals the reference's (-1 where
  unreachable);
* ``parent``: the root is its own parent, exactly the reachable vertices
  have a parent, and each other parent is joined to its child by an edge
  and lies one level above it.
"""
from __future__ import annotations

import numpy as np


class Graph:
    """Undirected simple graph from an edge list, for reference BFS."""

    def __init__(self, n: int, u, v):
        import scipy.sparse as sp
        u = np.asarray(u, np.int64)
        v = np.asarray(v, np.int64)
        keep = u != v
        u, v = u[keep], v[keep]
        ones = np.ones(2 * len(u), np.int8)
        a = sp.coo_matrix((ones, (np.concatenate([u, v]),
                                  np.concatenate([v, u]))),
                          shape=(n, n)).tocsr()
        a.sum_duplicates()
        a.sort_indices()
        a.data[:] = 1
        self.n = n
        self.csr = a
        self.deg = np.diff(a.indptr).astype(np.int64)
        self._keys = None

    def bfs(self, root: int):
        """``(level, parent)`` int32 arrays; -1 where unreachable, and
        the root is its own parent."""
        from scipy.sparse.csgraph import breadth_first_order
        order, pred = breadth_first_order(self.csr, root, directed=True,
                                          return_predecessors=True)
        parent = np.full(self.n, -1, np.int64)
        parent[order] = pred[order]
        parent[root] = root
        # pointer jumping: depth[v] = hops from v to the root
        depth = np.zeros(self.n, np.int64)
        depth[order] = 1
        depth[root] = 0
        up = np.where(parent >= 0, parent, np.arange(self.n))
        while True:
            step = depth[up]
            if not step[order].any():
                break
            depth += step
            up = up[up]
        level = np.full(self.n, -1, np.int64)
        level[order] = depth[order]
        return level.astype(np.int32), parent.astype(np.int32)

    def has_edges(self, a, b) -> np.ndarray:
        """Whether each ``(a[i], b[i])`` is an edge (ids in range)."""
        if self._keys is None:
            # rows hold sorted columns, so row-major keys are sorted
            rows = np.repeat(np.arange(self.n, dtype=np.int64), self.deg)
            self._keys = rows * self.n + self.csr.indices
        key = np.asarray(a, np.int64) * self.n + np.asarray(b, np.int64)
        # sorted needles walk the keys in one direction: far fewer misses
        order = np.argsort(key)
        pos = np.minimum(np.searchsorted(self._keys, key[order]),
                         len(self._keys) - 1)
        out = np.empty(len(key), bool)
        out[order] = self._keys[pos] == key[order]
        return out

    def component_edges(self, level) -> int:
        """Undirected edges with both ends among the vertices ``level``
        reaches: Graph500's count for one search (self loops dropped,
        duplicates merged, as LDBC Graphalytics counts edges)."""
        return int(self.deg[np.asarray(level) >= 0].sum()) // 2


def compare(graph: Graph, root: int, level, parent, want_level,
            want_parent=None) -> dict:
    """Counts of vertices at which an answer breaks each guarantee:
    ``{"level": ..., "parent": ...}``, both 0 for a correct answer."""
    level = np.asarray(level, np.int64)
    parent = np.asarray(parent, np.int64)
    want = np.asarray(want_level, np.int64)
    bad_level = int((level != want).sum())
    reach = want >= 0
    bad = np.zeros(graph.n, bool)
    bad |= (parent >= 0) != reach
    bad[root] |= parent[root] != root
    v = np.nonzero(reach)[0]
    v = v[v != root]
    p = parent[v]
    ok = (p >= 0) & (p < graph.n)
    pl = np.where(ok, want[np.clip(p, 0, graph.n - 1)], -2)
    ok &= pl == want[v] - 1
    ok[ok] = graph.has_edges(p[ok], v[ok])
    bad[v] |= ~ok
    return {"level": bad_level, "parent": int(bad.sum())}
