"""Paper Figs. 7/8: weak scaling — problem size grows with the resource.

Single-host analogue: rmat scale sweep at fixed engine config; reported
per-edge processing rate for BFS (frontier-driven) and PageRank (DC mode),
which is the flat-line the paper's weak-scaling argues for.
"""
from __future__ import annotations

import numpy as np

from repro.apps import bfs, pagerank
from repro.graph import rmat

from .common import emit, layout_for, timed


def run(scales=(10, 11, 12, 13)):
    rows = []
    for s in scales:
        g = rmat(s, 16, seed=1)
        L = layout_for(g)
        src = int(np.argmax(g.out_degrees()))
        t_bfs = timed(lambda: bfs(L, src, mode="hybrid"), repeat=2)
        t_pr = timed(lambda: pagerank(L, iters=5), repeat=2) / 5
        rows.append((f"rmat{s}", g.m, f"{t_bfs*1e3:.0f}",
                     f"{g.m/t_bfs/1e6:.1f}", f"{t_pr*1e3:.0f}",
                     f"{g.m/t_pr/1e6:.1f}"))
    emit(rows, ["graph", "edges", "bfs_ms", "bfs_Medges_s",
                "pr_iter_ms", "pr_Medges_s"])
    return rows


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
