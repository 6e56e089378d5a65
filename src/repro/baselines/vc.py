"""Vertex-centric and edge-centric baselines (the paper's comparison
targets, reimplemented in JAX):

  vc_push   - Ligra-style frontier-driven push (work ~ E_a, random writes;
              the atomic-update pattern becomes segment folds here)
  vc_pull   - Ligra-style pull direction (work ~ E every iteration)
  ec_stream - X-Stream-style unordered edge streaming (work ~ E)
  spmv      - GraphMat-style masked sparse-matrix-vector product (work ~ E
              + O(V) frontier handling)

Each provides bfs/pagerank/sssp/cc so benchmarks/fig4 can compare against
GPOP on identical inputs.  None of them partition: the memory-access pattern
is the whole point of the contrast.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.csr import Graph


def _prep(g: Graph):
    src = np.repeat(np.arange(g.n, dtype=np.int32),
                    g.out_degrees().astype(np.int64))
    return {
        "src": jnp.asarray(src),
        "dst": jnp.asarray(g.indices),
        "w": jnp.asarray(g.weights) if g.weights is not None else None,
        "n": g.n, "m": g.m,
        "deg": jnp.asarray(g.out_degrees().astype(np.float32)),
    }


# ---------------------------------------------------------------------------
# BFS
# ---------------------------------------------------------------------------

def _bfs_engine(g: Graph, source: int, order: str):
    """order: 'push' (frontier mask on src), 'pull'/'ec' (all edges)."""
    E = _prep(g)
    n = E["n"]

    @jax.jit
    def step(level, active, it):
        if order == "push":
            live = active[E["src"]]
        else:
            live = level[E["src"]] >= 0
        cand = jnp.where(live, E["src"], n)
        acc = jax.ops.segment_min(
            jnp.where(live, level[E["src"]], 2**30),
            E["dst"], num_segments=n + 1)[:n]
        hit = (acc < 2**30) & (level < 0)
        level = jnp.where(hit, it + 1, level)
        return level, hit

    level = jnp.full((n,), -1, jnp.int32).at[source].set(0)
    active = jnp.zeros((n,), bool).at[source].set(True)
    for it in range(n):
        level, active = step(level, active, jnp.int32(it))
        if int(active.sum()) == 0:
            break
    return np.asarray(level)


def bfs_push(g, source):
    return _bfs_engine(g, source, "push")


def bfs_pull(g, source):
    return _bfs_engine(g, source, "pull")


def bfs_ec(g, source):
    return _bfs_engine(g, source, "ec")


# ---------------------------------------------------------------------------
# PageRank (SpMV-style: GraphMat)
# ---------------------------------------------------------------------------

def pagerank_spmv(g: Graph, iters: int = 10, damping: float = 0.85):
    """LDBC Graphalytics PageRank (:mod:`repro.apps.pagerank`): the rank
    of vertices without out-edges is spread over every vertex."""
    E = _prep(g)
    n = E["n"]

    @jax.jit
    def run(pr):
        def body(_, pr):
            contrib = jnp.where(E["deg"] > 0, pr / E["deg"], 0.0)
            dangling = jnp.sum(jnp.where(E["deg"] > 0, 0.0, pr))
            acc = jax.ops.segment_sum(contrib[E["src"]], E["dst"],
                                      num_segments=n)
            return (1 - damping + damping * dangling) / n + damping * acc
        return jax.lax.fori_loop(0, iters, body, pr)

    pr = run(jnp.full((n,), 1.0 / n, jnp.float32))
    return np.asarray(pr)


# ---------------------------------------------------------------------------
# SSSP (Bellman-Ford, push and full-edge variants)
# ---------------------------------------------------------------------------

def sssp_push(g: Graph, source: int):
    E = _prep(g)
    n = E["n"]

    @jax.jit
    def step(dist, active):
        live = active[E["src"]]
        relax = jnp.where(live, dist[E["src"]] + E["w"], jnp.inf)
        acc = jax.ops.segment_min(relax, E["dst"], num_segments=n + 1)[:n]
        better = acc < dist
        return jnp.where(better, acc, dist), better

    dist = jnp.full((n,), jnp.inf, jnp.float32).at[source].set(0.0)
    active = jnp.zeros((n,), bool).at[source].set(True)
    for _ in range(n):
        dist, active = step(dist, active)
        if int(active.sum()) == 0:
            break
    return np.asarray(dist)


# ---------------------------------------------------------------------------
# Connected components (label propagation over all edges: EC style)
# ---------------------------------------------------------------------------

def cc_ec(g: Graph):
    E = _prep(g)
    n = E["n"]

    @jax.jit
    def step(label):
        acc = jax.ops.segment_min(label[E["src"]], E["dst"],
                                  num_segments=n + 1)[:n]
        new = jnp.minimum(label, acc)
        return new, jnp.any(new != label)

    label = jnp.arange(n, dtype=jnp.uint32)
    for _ in range(n):
        label, changed = step(label)
        if not bool(changed):
            break
    return np.asarray(label)


def timed(fn, *args, repeat: int = 1, **kw):
    fn(*args, **kw)                      # warmup/compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    return (time.perf_counter() - t0) / repeat, out
