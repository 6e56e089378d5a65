"""Paper Fig. 4: execution time of GPOP vs baseline frameworks.

Columns: GPOP (hybrid), GPOP_SC, GPOP_DC, and the baseline stand-ins
(vc_push ~ Ligra push, vc_pull/ec ~ Ligra pull & X-Stream, spmv ~ GraphMat)
for BFS / PageRank / SSSP / CC / Nibble.  Times are single-host CPU
wall-clock (the cross-implementation *ratios* are the reproduction target;
absolute numbers are CPU-bound).
"""
from __future__ import annotations

import numpy as np

from repro.apps import bfs, connected_components, nibble, pagerank, sssp
from repro.baselines import vc
from repro.graph import rmat

from .common import emit, graphs, layout_for, symmetrize, timed


def run(scale=None):
    from .common import DEFAULT_SCALE
    scale = scale or DEFAULT_SCALE
    rows = []
    for name, g in graphs(scale).items():
        L = layout_for(g)
        src = int(np.argmax(g.out_degrees()))

        rows.append((name, "bfs", "gpop",
                     timed(lambda: bfs(L, src, mode="hybrid"))))
        rows.append((name, "bfs", "gpop_sc",
                     timed(lambda: bfs(L, src, mode="sc"))))
        rows.append((name, "bfs", "gpop_dc",
                     timed(lambda: bfs(L, src, mode="dc"))))
        rows.append((name, "bfs", "vc_push",
                     timed(lambda: vc.bfs_push(g, src))))
        rows.append((name, "bfs", "vc_pull",
                     timed(lambda: vc.bfs_pull(g, src))))

        rows.append((name, "pagerank", "gpop",
                     timed(lambda: pagerank(L, iters=10))))
        rows.append((name, "pagerank", "spmv",
                     timed(lambda: vc.pagerank_spmv(g, iters=10))))

        gs = symmetrize(g)
        Ls = layout_for(gs)
        rows.append((name, "cc", "gpop",
                     timed(lambda: connected_components(Ls))))
        rows.append((name, "cc", "ec_stream",
                     timed(lambda: vc.cc_ec(gs))))

        rows.append((name, "nibble", "gpop",
                     timed(lambda: nibble(L, seeds=[src], eps=1e-3,
                                          max_iters=30))))

    gw = rmat(scale, 16, seed=1, weighted=True)
    Lw = layout_for(gw)
    srcw = int(np.argmax(gw.out_degrees()))
    rows.append((f"rmat{scale}", "sssp", "gpop",
                 timed(lambda: sssp(Lw, srcw, mode="hybrid"))))
    rows.append((f"rmat{scale}", "sssp", "vc_push",
                 timed(lambda: vc.sssp_push(gw, srcw))))

    emit([(g_, a, i, f"{t*1e3:.1f}") for g_, a, i, t in rows],
         ["graph", "algorithm", "impl", "ms"])
    return rows


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
