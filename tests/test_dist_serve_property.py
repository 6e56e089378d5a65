"""Property tests (hypothesis, 4 virtual devices): distributed batched
execution equals per-query distributed runs.

  * ``DistEngine.run_batched`` ≡ B sequential ``DistEngine.run`` calls in
    mode='dc' — BFS and SSSP, random graphs, random multi-source batches,
    with and without the compressed wire.  The parity must hold per wire
    config: both paths perform identical per-lane math, so results are
    bit-identical even when bf16 rounds SSSP distances.
  * ``wire_bf16`` exactness for id-monoids: BFS carries uint32 vertex ids
    (< 2**24 here), the bf16 cast never engages, so the compressed engine
    matches the uncompressed one bit-for-bit.
  * a DistEngine-backed :class:`repro.serve.GraphQueryServer` answers a
    drained batch identically to the single-device server.

Runs in ONE subprocess (virtual devices must be fixed before jax
initializes; the parent test process stays single-device) with hypothesis
driving the example loop inside it.
"""
import os
import subprocess
import sys
import textwrap

import pytest

pytest.importorskip("hypothesis")  # optional dev dep (requirements-dev.txt)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


COMMON = """
import jax
import numpy as np
from jax.sharding import AxisType
from repro.dist.engine import DistEngine
from repro.graph import build_layout, from_edges
from repro.graph.shard import shard_layout

D = 4
mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))

def random_sharded(data, st, weighted):
    n = data.draw(st.integers(8, 96))
    m = data.draw(st.integers(4, 512))
    seed = data.draw(st.integers(0, 10**6))
    rng = np.random.default_rng(seed)
    w = rng.random(m).astype(np.float32) if weighted else None
    g = from_edges(rng.integers(0, n, m), rng.integers(0, n, m), n=n,
                   dedup=True, weights=w)
    L = build_layout(g, k=8, edge_tile=16, msg_tile=8)
    return shard_layout(L, D), g.n, rng, data.draw(st.integers(2, 8))
"""


@pytest.mark.slow
def test_dist_run_batched_equals_per_query_runs():
    _run(COMMON + textwrap.dedent("""
    from hypothesis import given, settings, strategies as st
    from repro.apps.bfs import bfs_program
    from repro.apps.sssp import sssp_program

    def states_for(app, N, sources):
        if app == "bfs":
            B = len(sources)
            parent = np.full((B, N), -1, np.int32)
            level = np.full((B, N), -1, np.int32)
            vid = np.broadcast_to(np.arange(N, dtype=np.uint32),
                                  (B, N)).copy()
            for i, s in enumerate(sources):
                parent[i, s] = s; level[i, s] = 0
            return {"parent": parent, "level": level, "vid": vid}
        dist = np.full((len(sources), N), np.inf, np.float32)
        for i, s in enumerate(sources):
            dist[i, s] = 0.0
        return {"dist": dist}

    @settings(max_examples=5, deadline=None, derandomize=True)
    @given(st.data())
    def prop(data):
        for app, weighted in (("bfs", False), ("sssp", True)):
            SL, n, rng, B = random_sharded(data, st, weighted)
            N = D * SL.nv
            prog = bfs_program() if app == "bfs" else sssp_program()
            sources = rng.integers(0, n, B)
            fr = np.zeros((B, N), bool)
            fr[np.arange(B), sources] = True
            for wire in (False, True):
                eng = DistEngine(SL, prog, mesh, mode="dc",
                                 wire_bf16=wire)
                states = states_for(app, N, sources)
                bat, _, _ = eng.run_batched(
                    {k: v.copy() for k, v in states.items()}, fr)
                for i in range(B):
                    seq, _, _ = eng.run(
                        {k: v[i].copy() for k, v in states.items()}, fr[i])
                    for k in seq:
                        same = np.array_equal(np.asarray(bat[k][i]),
                                              np.asarray(seq[k]))
                        assert same, (app, wire, k, i)
    prop()
    print("OK")
    """))


@pytest.mark.slow
def test_wire_bf16_exact_for_id_monoids():
    _run(COMMON + textwrap.dedent("""
    from hypothesis import given, settings, strategies as st
    from repro.apps.bfs import bfs_program

    @settings(max_examples=5, deadline=None, derandomize=True)
    @given(st.data())
    def prop(data):
        SL, n, rng, B = random_sharded(data, st, False)
        N = D * SL.nv
        assert N < 2**24          # ids fit a bf16 mantissa trivially
        sources = rng.integers(0, n, B)
        fr = np.zeros((B, N), bool)
        fr[np.arange(B), sources] = True
        outs = {}
        for wire in (False, True):
            eng = DistEngine(SL, bfs_program(), mesh, mode="dc",
                             wire_bf16=wire)
            # uint32 monoid: the bf16 cast must never engage
            assert eng.wire_compressed is False
            parent = np.full((B, N), -1, np.int32)
            level = np.full((B, N), -1, np.int32)
            vid = np.broadcast_to(np.arange(N, dtype=np.uint32),
                                  (B, N)).copy()
            for i, s in enumerate(sources):
                parent[i, s] = s; level[i, s] = 0
            stb, _, _ = eng.run_batched(
                {"parent": parent, "level": level, "vid": vid}, fr)
            outs[wire] = {k: np.asarray(stb[k]) for k in ("parent",
                                                          "level")}
        for k in outs[False]:
            assert np.array_equal(outs[False][k], outs[True][k]), k
    prop()
    print("OK")
    """))


@pytest.mark.slow
def test_graph_server_dist_backed_matches_single_device():
    _run(COMMON + textwrap.dedent("""
    from repro.graph import rmat
    from repro.apps.bfs import bfs
    from repro.serve import GraphQuery, GraphQueryServer

    g = rmat(8, 8, seed=11, weighted=True)
    L = build_layout(g, k=8, edge_tile=32, msg_tile=16)
    SL = shard_layout(L, D)
    srv = GraphQueryServer(L, mode="dc", sharded=SL, mesh=mesh,
                           wire_bf16=True)
    sources = [int(s) for s in np.linspace(0, g.n - 1, 12).astype(int)]
    for i, s in enumerate(sources):
        srv.submit(GraphQuery(i, "bfs", {"source": s}))
    srv.submit(GraphQuery(90, "sssp", {"source": sources[0]}))
    done = srv.run()
    assert len(done) == len(sources) + 1
    assert type(srv._engines["bfs"]).__name__ == "DistEngine"
    for q in done:
        if q.app != "bfs":
            continue
        seq = bfs(L, source=q.params["source"], backend="ref")
        assert np.array_equal(q.result["level"], seq["level"]), q.qid
        assert np.array_equal(q.result["parent"], seq["parent"]), q.qid
    print("OK")
    """))
