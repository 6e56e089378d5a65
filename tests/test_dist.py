"""Distributed PPM engine on 8 virtual host devices (subprocess: the device
count must be fixed before jax initializes, and the main test process stays
single-device)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


COMMON = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import AxisType
from repro.graph import rmat, build_layout, to_scipy
from repro.graph.shard import shard_layout
from repro.dist.engine import DistEngine
import scipy.sparse.csgraph as csg
D = 8
mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))
g = rmat(10, 8, seed=1)
L = build_layout(g, k=16, edge_tile=64, msg_tile=32)
SL = shard_layout(L, D)
src = int(np.argmax(g.out_degrees()))
N = D * SL.nv
"""


@pytest.mark.slow
def test_dist_bfs_hybrid():
    out = _run(COMMON + """
from repro.apps.bfs import bfs_program
prog = bfs_program()
parent = np.full(N, -1, np.int32); parent[src] = src
level = np.full(N, -1, np.int32); level[src] = 0
vid = np.arange(N, dtype=np.uint32)
frontier = np.zeros(N, bool); frontier[src] = True
eng = DistEngine(SL, prog, mesh, mode="hybrid")
# the CI dist lane pins the fold backend via REPRO_KERNEL_BACKEND; the
# engine must honour it (BFS's min/uint32 monoid lowers on every backend)
import os
want = os.environ.get("REPRO_KERNEL_BACKEND")
if want:
    assert eng.backend_name == want, eng.backend_name
state, _, stats = eng.run({"parent": parent, "level": level, "vid": vid},
                          frontier)
lv = np.asarray(state["level"])[:g.n]
d = csg.shortest_path(to_scipy(g), method="D", unweighted=True, indices=src)
ref = np.where(np.isinf(d), -1, d).astype(int)
assert np.array_equal(lv, ref), "dist bfs mismatch"
modes = {s["mode"] for s in stats}
assert modes == {"sc", "dc"}, f"hybrid should use both modes: {modes}"
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_dist_pagerank_dc_and_sssp_sc():
    out = _run(COMMON + """
from repro.apps.pagerank import pagerank_program
from repro.apps.sssp import sssp_program
import scipy.sparse as sp

prog = pagerank_program(g.n)
pr0 = np.zeros(N, np.float32); pr0[:g.n] = 1.0/g.n
deg = np.zeros(N, np.float32); deg[:L.n_pad] = L.deg
frontier = np.zeros(N, bool); frontier[:g.n] = True
eng = DistEngine(SL, prog, mesh, mode="dc")
state, _, _ = eng.run({"pr": pr0, "deg": deg}, frontier, max_iters=5,
                      until_empty=False)
pr = np.asarray(state["pr"])[:g.n]
x = np.full(g.n, 1.0/g.n); outdeg = g.out_degrees(); P = to_scipy(g)
for _ in range(5):
    x = 0.15/g.n + 0.85*(P.T@np.where(outdeg>0, x/np.maximum(outdeg,1), 0.0)
                         + x[outdeg == 0].sum()/g.n)
assert np.abs(pr-x).max() < 1e-5, "dist pagerank mismatch"

gw = rmat(9, 8, seed=2, weighted=True)
Lw = build_layout(gw, k=16, edge_tile=64, msg_tile=32)
SLw = shard_layout(Lw, D)
s2 = int(np.argmax(gw.out_degrees()))
Nw = D * SLw.nv
dist0 = np.full(Nw, np.inf, np.float32); dist0[s2] = 0
frontier = np.zeros(Nw, bool); frontier[s2] = True
eng = DistEngine(SLw, sssp_program(), mesh, mode="sc")
state, _, _ = eng.run({"dist": dist0}, frontier)
ours = np.asarray(state["dist"])[:gw.n]
d2 = csg.shortest_path(to_scipy(gw), method="D", indices=s2)
fin = ~np.isinf(d2)
assert np.allclose(ours[fin], d2[fin], atol=1e-5)
assert np.array_equal(np.isinf(ours), ~fin)
print("OK")
""")
    assert "OK" in out


GRAPHALYTICS = """
from repro.apps.pagerank import pagerank_program
from repro.graph.csr import from_edges, symmetrize
# a symmetrized Kronecker graph over all 2^10 ids: self loops kept once,
# unreached ids isolated (dangling)
g0 = rmat(10, 8, seed=7, dedup=False)
g = symmetrize(from_edges(np.repeat(np.arange(g0.n), g0.out_degrees()),
                          g0.indices, g0.n))
deg = g.out_degrees()
assert (deg == 0).any() and (np.repeat(np.arange(g.n), deg) == g.indices).any()
L = build_layout(g, k=16, edge_tile=64, msg_tile=32)
SL = shard_layout(L, D)
N = D * SL.nv
src = np.repeat(np.arange(g.n), deg)
def ref(x, iters):
    for _ in range(iters):
        share = np.divide(x, deg, out=np.zeros(g.n), where=deg > 0)
        acc = np.bincount(g.indices, weights=share[src], minlength=g.n)
        x = 0.15 / g.n + 0.85 * (acc + x[deg == 0].sum() / g.n)
    return x
def check(got, want):
    got = np.asarray(got, np.float64)[:g.n]
    assert np.all(np.abs(got - want) <= np.maximum(1e-4 * want, 1e-4 / g.n))
    assert abs(got.sum() - 1.0) <= 1e-4
x0 = np.full(g.n, 1.0 / g.n)
pr0 = np.zeros(N, np.float32); pr0[:g.n] = x0
degf = np.zeros(N, np.float32); degf[:L.n_pad] = L.deg
frontier = np.zeros(N, bool); frontier[:g.n] = True
"""


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["dc", "sc", "hybrid_pp", "batched"])
def test_dist_pagerank_graphalytics(mode):
    """LDBC Graphalytics PageRank (dangling rank spread over every
    vertex) through every DistEngine step: the aggregate is a psum of
    the shards' sums over their real vertices."""
    out = _run(COMMON + GRAPHALYTICS + f"""
mode = {mode!r}
eng = DistEngine(SL, pagerank_program(g.n), mesh,
                 mode="dc" if mode == "batched" else mode)
if mode == "batched":
    warm = np.random.default_rng(0).random(g.n); warm /= warm.sum()
    pr = np.stack([pr0, pr0]); pr[1, :g.n] = warm
    st, _, _ = eng.run_batched({{"pr": pr, "deg": np.stack([degf, degf])}},
                               np.stack([frontier, frontier]), max_iters=6,
                               until_empty=False)
    check(np.asarray(st["pr"])[0], ref(x0, 6))
    check(np.asarray(st["pr"])[1], ref(warm, 6))
else:
    st, _, _ = eng.run({{"pr": pr0, "deg": degf}}, frontier, max_iters=6,
                       until_empty=False)
    check(st["pr"], ref(x0, 6))
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_dist_equals_single_device_engine():
    """Distributed and single-device engines agree bit-for-bit on BFS."""
    out = _run(COMMON + """
from repro.apps.bfs import bfs_program
from repro.apps import bfs as bfs_single
prog = bfs_program()
parent = np.full(N, -1, np.int32); parent[src] = src
level = np.full(N, -1, np.int32); level[src] = 0
vid = np.arange(N, dtype=np.uint32)
frontier = np.zeros(N, bool); frontier[src] = True
eng = DistEngine(SL, prog, mesh, mode="sc")
state, _, _ = eng.run({"parent": parent, "level": level, "vid": vid},
                      frontier)
res1 = np.asarray(state["parent"])[:g.n]
res2 = bfs_single(L, source=src, mode="sc")["parent"]
assert np.array_equal(res1, res2), "engines disagree"
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_dist_hybrid_per_partition():
    """Per-partition dual mode at pod granularity: correct BFS AND at least
    one iteration mixing DC and SC partitions (paper Fig. 9 behaviour)."""
    out = _run(COMMON + """
from repro.apps.bfs import bfs_program
prog = bfs_program()
parent = np.full(N, -1, np.int32); parent[src] = src
level = np.full(N, -1, np.int32); level[src] = 0
vid = np.arange(N, dtype=np.uint32)
frontier = np.zeros(N, bool); frontier[src] = True
eng = DistEngine(SL, prog, mesh, mode="hybrid_pp")
state, _, stats = eng.run({"parent": parent, "level": level, "vid": vid},
                          frontier)
lv = np.asarray(state["level"])[:g.n]
d = csg.shortest_path(to_scipy(g), method="D", unweighted=True, indices=src)
ref = np.where(np.isinf(d), -1, d).astype(int)
assert np.array_equal(lv, ref), "hybrid_pp bfs mismatch"
assert any(s["dc_parts"] > 0 and s["sc_parts"] > 0 for s in stats), \
    "expected an iteration with mixed per-partition modes"
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_dist_equivalence_random_graphs():
    """Property: all three distributed modes equal the single-device engine
    on random graphs (one subprocess, several seeds)."""
    out = _run("""
import numpy as np, jax
from jax.sharding import AxisType
from repro.graph import uniform_random, build_layout
from repro.graph.shard import shard_layout
from repro.dist.engine import DistEngine
from repro.apps.bfs import bfs_program
from repro.apps import bfs as bfs_single

D = 8
mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))
for seed in (3, 17, 91):
    g = uniform_random(300, 2500, seed=seed)
    L = build_layout(g, k=16, edge_tile=32, msg_tile=16)
    SL = shard_layout(L, D)
    N = D * SL.nv
    src = int(np.argmax(g.out_degrees()))
    ref = bfs_single(L, source=src, mode="hybrid")["parent"]
    for mode in ("dc", "sc", "hybrid_pp"):
        prog = bfs_program()
        parent = np.full(N, -1, np.int32); parent[src] = src
        level = np.full(N, -1, np.int32); level[src] = 0
        vid = np.arange(N, dtype=np.uint32)
        f = np.zeros(N, bool); f[src] = True
        eng = DistEngine(SL, prog, mesh, mode=mode)
        st, _, _ = eng.run({"parent": parent, "level": level, "vid": vid}, f)
        got = np.asarray(st["parent"])[:g.n]
        assert np.array_equal(got, ref), (seed, mode)
print("OK")
""")
    assert "OK" in out
