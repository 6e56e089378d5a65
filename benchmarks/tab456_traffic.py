"""Paper Tables 4-6: cache misses -> modeled DRAM/HBM traffic.

No hardware counters exist on this CPU stand-in, so the analog is the
engine's Eq. 1 bytes model (per-iteration, per-mode — the same quantity the
paper's L2-miss tables proxy) for GPOP, vs the structural traffic of each
baseline: vc_push reads E_a edges + random vertex values (a full cache line
per touched vertex - the paper's Fig. 1 point), pull/spmv stream all E edges
every iteration.
"""
from __future__ import annotations

import numpy as np

from repro.apps import bfs, connected_components, pagerank, sssp
from repro.graph import rmat

from .common import emit, graphs, layout_for, symmetrize

D_I = D_V = 4
CACHE_LINE = 64         # the paper's random-access penalty unit


def _gpop_bytes(stats):
    return sum(s.dc_bytes + s.sc_bytes for s in stats)


def _push_bytes(stats_iters_eactive, stats_iters_nactive):
    # per active edge: edge read + random read-modify-write of dst value
    return sum(e * (D_I + 2 * CACHE_LINE) for e in stats_iters_eactive)


def run(scale=None):
    from .common import DEFAULT_SCALE
    scale = scale or DEFAULT_SCALE
    rows = []
    for name, g in graphs(scale).items():
        L = layout_for(g)
        src = int(np.argmax(g.out_degrees()))

        # --- PageRank (table 4): 10 iterations, all vertices active ---
        iters = 10
        gpop = float(L.dc_cost_bytes().sum()) * iters
        spmv = iters * (g.m * (D_I + D_V) + g.m * CACHE_LINE)  # random x[]
        rows.append((name, "pagerank", f"{gpop/1e6:.1f}",
                     f"{spmv/1e6:.1f}", f"{spmv/gpop:.2f}"))

        # --- CC / label prop (table 5) ---
        gs = symmetrize(g)
        Ls = layout_for(gs)
        r = connected_components(Ls)
        gpop = _gpop_bytes(r["stats"])
        ec = sum(1 for _ in r["stats"]) * (gs.m * (D_I + D_V)
                                           + gs.m * CACHE_LINE)
        rows.append((name, "cc", f"{gpop/1e6:.1f}", f"{ec/1e6:.1f}",
                     f"{ec/gpop:.2f}"))

    # --- SSSP (table 6) ---
    gw = rmat(scale, 16, seed=1, weighted=True)
    Lw = layout_for(gw)
    srcw = int(np.argmax(gw.out_degrees()))
    r = sssp(Lw, srcw, mode="hybrid")
    gpop = _gpop_bytes(r["stats"])
    push = _push_bytes([s.e_active for s in r["stats"]], None)
    rows.append((f"rmat{scale}", "sssp", f"{gpop/1e6:.1f}",
                 f"{push/1e6:.1f}", f"{push/gpop:.2f}"))

    emit(rows, ["graph", "algorithm", "gpop_MB", "baseline_MB", "ratio"])
    return rows


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
