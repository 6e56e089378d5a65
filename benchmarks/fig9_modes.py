"""Paper Fig. 9: per-iteration execution time of GPOP vs GPOP_SC vs GPOP_DC.

The reproduction target is the crossover structure: SC wins on sparse
frontiers, DC wins on dense ones, and the hybrid engine tracks the lower
envelope via the Eq. 1 per-partition decision.  Reported per iteration:
wall time, modeled bytes, and the mode split.
"""
from __future__ import annotations

import numpy as np

from repro.apps import bfs, connected_components, sssp
from repro.graph import rmat

from .common import emit, layout_for, symmetrize


def run(scale=None):
    from .common import DEFAULT_SCALE
    scale = scale or DEFAULT_SCALE
    g = rmat(scale, 16, seed=1)
    L = layout_for(g)
    src = int(np.argmax(g.out_degrees()))
    rows = []
    for mode in ("hybrid", "sc", "dc"):
        stats = bfs(L, src, mode=mode)["stats"]
        for s in stats:
            rows.append(("bfs", mode, s.it, s.n_active, s.e_active,
                         s.dc_parts, s.sc_parts,
                         f"{(s.dc_bytes + s.sc_bytes)/1e6:.2f}",
                         f"{s.wall_s*1e3:.1f}"))
    gs = symmetrize(g)
    Ls = layout_for(gs)
    for mode in ("hybrid", "sc", "dc"):
        stats = connected_components(Ls, mode=mode)["stats"]
        for s in stats:
            rows.append(("cc", mode, s.it, s.n_active, s.e_active,
                         s.dc_parts, s.sc_parts,
                         f"{(s.dc_bytes + s.sc_bytes)/1e6:.2f}",
                         f"{s.wall_s*1e3:.1f}"))
    emit(rows, ["algorithm", "mode", "iter", "n_active", "e_active",
                "dc_parts", "sc_parts", "modeled_MB", "wall_ms"])

    # validation of the analytical model (paper §6.2.3): hybrid's modeled
    # bytes never exceed either pure mode's bytes
    for alg, Lx, runner in (("bfs", L, lambda m: bfs(L, src, mode=m)),
                            ):
        by = {m: sum(s.dc_bytes + s.sc_bytes for s in runner(m)["stats"])
              for m in ("hybrid", "sc", "dc")}
        assert by["hybrid"] <= min(by["sc"], by["dc"]) * 1.001, by
        print(f"# {alg}: hybrid bytes {by['hybrid']/1e6:.1f}MB <= "
              f"min(SC {by['sc']/1e6:.1f}, DC {by['dc']/1e6:.1f}) OK")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
