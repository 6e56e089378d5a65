"""The plain reference against a textbook BFS, and the comparison
against answers broken by hand."""
from collections import deque

import numpy as np

from bench import reference


def _graph():
    rng = np.random.default_rng(0)
    n = 300
    u = rng.integers(0, n, 900)
    v = rng.integers(0, n, 900)
    u[:5] = v[:5]                       # self loops are dropped
    return n, u, v


def _textbook(n, u, v, root):
    adj = [set() for _ in range(n)]
    for a, b in zip(u, v):
        if a != b:
            adj[a].add(b)
            adj[b].add(a)
    level = [-1] * n
    level[root] = 0
    q = deque([root])
    while q:
        x = q.popleft()
        for y in adj[x]:
            if level[y] < 0:
                level[y] = level[x] + 1
                q.append(y)
    return np.array(level)


def test_levels_match_textbook_bfs():
    n, u, v = _graph()
    g = reference.Graph(n, u, v)
    for root in (0, 17, 299):
        level, parent = g.bfs(root)
        assert np.array_equal(level, _textbook(n, u, v, root))
        assert reference.compare(g, root, level, parent, level) == {
            "level": 0, "parent": 0}


def test_compare_counts_broken_answers():
    n, u, v = _graph()
    g = reference.Graph(n, u, v)
    level, parent = g.bfs(0)
    far = int(np.argmax(level))
    lv = level.copy()
    lv[far] += 1
    assert reference.compare(g, 0, lv, parent, level)["level"] == 1
    pa = parent.copy()
    pa[far] = far                       # no edge to itself
    assert reference.compare(g, 0, level, pa, level)["parent"] == 1
    un = np.nonzero(level < 0)[0]
    if len(un):
        pa = parent.copy()
        pa[un[0]] = 0                   # a parent for an unreached vertex
        assert reference.compare(g, 0, level, pa, level)["parent"] == 1


def test_component_edges_counts_undirected_simple_edges():
    # triangle with a duplicate, a self loop and a separate edge
    u = np.array([0, 1, 2, 1, 3, 4])
    v = np.array([1, 2, 0, 0, 3, 5])
    g = reference.Graph(6, u, v)
    level, _ = g.bfs(0)
    assert g.component_edges(level) == 3
