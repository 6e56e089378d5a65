"""Property test (hypothesis): the distributed per-partition dual-mode step
(``mode='hybrid_pp'``) equals the pure ``dc`` and pure ``sc`` runs across
random graphs AND random multi-vertex frontiers, for BFS and CC.

The parity is mode-only (no oracle): all three modes execute the same
vertex program over the same sharded layout, so any divergence is a bug in
the per-partition stream split / dual-fold combine of
:func:`repro.dist.engine.build_hybrid_step`.

Runs in ONE subprocess (the 4 virtual devices must be fixed before jax
initializes; the parent test process stays single-device) with hypothesis
driving the example loop inside it — a @given-wrapped function is directly
callable, so the property executes entirely in the child.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_hybrid_pp_parity_random_graphs_and_frontiers():
    code = textwrap.dedent("""
    import jax
    import numpy as np
    from hypothesis import given, settings, strategies as st

    from jax.sharding import AxisType
    from repro.dist.engine import DistEngine
    from repro.graph import build_layout, from_edges
    from repro.graph.shard import shard_layout
    from repro.apps.bfs import bfs_program
    from repro.apps.cc import cc_program

    D = 4
    mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))

    def run_app(app, SL, N, frontier, mode):
        if app == "bfs":
            prog = bfs_program()
            src = np.where(frontier)[0].astype(np.int32)
            parent = np.full(N, -1, np.int32); parent[src] = src
            level = np.full(N, -1, np.int32); level[src] = 0
            vid = np.arange(N, dtype=np.uint32)
            state = {"parent": parent, "level": level, "vid": vid}
            keys = ("parent", "level")
        else:
            prog = cc_program()
            state = {"label": np.arange(N, dtype=np.uint32)}
            keys = ("label",)
        eng = DistEngine(SL, prog, mesh, mode=mode)
        st_out, _, stats = eng.run(state, frontier)
        return {k: np.asarray(st_out[k]) for k in keys}, stats

    @settings(max_examples=5, deadline=None, derandomize=True)
    @given(st.data())
    def prop(data):
        n = data.draw(st.integers(8, 96))
        m = data.draw(st.integers(4, 512))
        seed = data.draw(st.integers(0, 10**6))
        rng = np.random.default_rng(seed)
        g = from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n=n,
                       dedup=True)
        L = build_layout(g, k=8, edge_tile=16, msg_tile=8)
        SL = shard_layout(L, D)
        N = D * SL.nv
        # random multi-vertex frontier (>=1 active real vertex)
        p_act = data.draw(st.sampled_from([0.05, 0.3, 0.8]))
        frontier = np.zeros(N, bool)
        frontier[:g.n] = rng.random(g.n) < p_act
        if not frontier.any():
            frontier[rng.integers(0, g.n)] = True
        for app in ("bfs", "cc"):
            ref, _ = run_app(app, SL, N, frontier, "dc")
            sc, _ = run_app(app, SL, N, frontier, "sc")
            hy, _ = run_app(app, SL, N, frontier, "hybrid_pp")
            for k in ref:
                assert np.array_equal(sc[k], ref[k]), (app, k, "sc", seed)
                assert np.array_equal(hy[k], ref[k]), \\
                    (app, k, "hybrid_pp", seed)

    prop()
    print("OK")
    """)
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
