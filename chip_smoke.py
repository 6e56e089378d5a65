#!/usr/bin/env python3
"""Run the graph engine's main path once on a TPU and check every answer.

    python3 chip_smoke.py                 # one chip: BFS, PageRank, serving
    python3 chip_smoke.py --chips 4       # four chips: DistEngine only

The graph is Graph500's Kronecker generator (A/B/C = 0.57/0.19/0.19, edge
factor 16), symmetrized, at scale 22: the scale of LDBC
Graphalytics' ``graph500-22``, four scales below Graph500's smallest
class (toy, scale 26).  It is generated from ``--seed`` on every run.

Phases on one chip, each through the library's normal entry points and
each checked against a plain reference that shares no code with the
engine:

  (a) BFS (``repro.apps.bfs``, hybrid DC/SC) from 4 roots of nonzero
      degree: levels equal ``scipy.sparse.csgraph``'s, and the parents
      pass Graph500's tree rules;
  (b) PageRank (``repro.apps.pagerank``), 10 iterations of the DC path:
      equal to a float64 numpy power iteration within 1e-5 and 1e-4
      relative per vertex, 1e-5 relative in l1;
  (c) serving: ``GraphQueryServer`` answers 8 Zipf-drawn BFS queries in
      one batched run (its hybrid engine steps the lanes in lockstep,
      each through its own DC/SC split); each answer equals a
      per-source ``bfs``.

``--chips 4`` runs only the distributed phase: ``DistEngine`` BFS and 10
PageRank iterations over ``shard_layout(L, 4)`` on a 4-device mesh,
against the same references, with each device's peak memory.

Every kernel must resolve to the TPU table
(``repro.backend.registry.TPU_DEFAULTS``) and none may run in interpret
mode.  Any failed check raises, so the script exits non-zero and prints
no result.  There is no CPU fallback: without a TPU it exits non-zero
at once.  The last line of a passing run is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

SCALE = 22
EDGE_FACTOR = 16
# explicit tile geometry: a tuning cache in the checkout must not change
# what runs (edge/msg tiles are whole 1024-element TPU tiles)
GEOMETRY = dict(k=32, edge_tile=1024, msg_tile=1024, fold_tile=256,
                fold_q=256)
BFS_ROOTS = 4
PR_ITERS = 10
# PageRank against the float64 reference: every vertex within PR_ATOL
# and within PR_RTOL of its own rank (the mean rank at scale 22 is 2.4e-7,
# so the absolute bound alone would hold only the hubs), and the whole
# vector within PR_L1 in relative l1.  Float32 folds stay near 3e-6 per
# vertex and 3e-7 in l1 at scale 17.
PR_ATOL = 1e-5
PR_RTOL = 1e-4
PR_L1 = 1e-5
SERVE_QUERIES = 8
ZIPF_A = 1.2


class CheckFailed(AssertionError):
    """A phase's result disagrees with its reference."""


def require(ok, msg: str):
    """Fail the run unless ``ok`` (an ``assert`` would vanish under -O)."""
    if not ok:
        raise CheckFailed(msg)


T0 = time.perf_counter()


def log(tag: str, **fields):
    """One tagged JSON line; ``t`` is host seconds since the script
    started."""
    fields["t"] = time.perf_counter() - T0
    print(f"[{tag}] " + json.dumps(fields, default=str), flush=True)


def timed(fn, *args, **kw):
    """``fn(*args, **kw)`` with its host-clock seconds; every result the
    phases time is a host numpy array or ends in ``block_until_ready``."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


# ----------------------------------------------------------------------
# graph, references
# ----------------------------------------------------------------------

def build(scale: int, seed: int, geometry=GEOMETRY):
    """Symmetrized Kronecker graph and its layout, with host seconds."""
    from repro.graph import build_layout
    from repro.graph.csr import rmat, symmetrize
    t0 = time.perf_counter()
    g = symmetrize(rmat(scale, EDGE_FACTOR, seed=seed))
    t1 = time.perf_counter()
    L = build_layout(g, **geometry)
    t2 = time.perf_counter()
    return g, L, {"generate_s": t1 - t0, "layout_s": t2 - t1}


def pick_roots(g, count: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [int(r) for r in rng.choice(np.nonzero(g.out_degrees())[0],
                                       count, replace=False)]


def zipf_sources(g, count: int, seed: int):
    """``count`` sources drawn Zipf(``ZIPF_A``) over a seeded ranking of
    the vertices of nonzero degree: a few hot sources repeat."""
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    pool = rng.permutation(np.nonzero(g.out_degrees())[0])
    ranks = rng.zipf(ZIPF_A, count) - 1
    return [int(pool[r % len(pool)]) for r in ranks]


def ref_levels(g, roots):
    """BFS levels from each root by ``scipy.sparse.csgraph`` (-1 where
    unreachable)."""
    import numpy as np
    from scipy.sparse.csgraph import shortest_path

    from repro.graph import to_scipy
    d = shortest_path(to_scipy(g), method="D", unweighted=True,
                      indices=roots)
    return np.where(np.isinf(d), -1, d).astype(np.int32)


def edge_keys(g):
    """Sorted ``src * n + dst`` keys of every edge (CSR rows are sorted)."""
    import numpy as np
    src = np.repeat(np.arange(g.n, dtype=np.int64), g.out_degrees())
    return src * g.n + g.indices


def check_bfs_tree(g, keys, root: int, level, parent, want_level):
    """Graph500's BFS validation, plus levels equal to the reference:
    every reachable vertex has a parent, every parent edge exists, and a
    parent's level is one less than its child's."""
    import numpy as np
    require(np.array_equal(level, want_level),
            f"root {root}: levels differ from scipy at "
            f"{int((level != want_level).sum())} vertices")
    reach = want_level >= 0
    require(parent[root] == root, f"root {root}: parent[root]={parent[root]}")
    require(np.array_equal(parent >= 0, reach),
            f"root {root}: parent set differs from the reachable set")
    v = np.nonzero(reach)[0]
    v = v[v != root]
    p = parent[v].astype(np.int64)
    require((level[p] == level[v] - 1).all(),
            f"root {root}: a parent is not one level above its child")
    k = p * g.n + v
    pos = np.minimum(np.searchsorted(keys, k), len(keys) - 1)
    require((keys[pos] == k).all(), f"root {root}: a parent edge is missing")
    return int(reach.sum())


def ref_pagerank(g, iters: int, damping: float = 0.85):
    """Float64 power iteration with the app's semantics (LDBC
    Graphalytics): every vertex sends rank/out-degree along its
    out-edges, and the rank of vertices without out-edges is spread over
    every vertex; rank' = (1-d)/n + d*(sum + dangling/n)."""
    import numpy as np

    from repro.graph import to_scipy
    AT = to_scipy(g).T.tocsr().astype(np.float64)
    deg = g.out_degrees().astype(np.float64)
    pr = np.full(g.n, 1.0 / g.n)
    for _ in range(iters):
        share = np.divide(pr, deg, out=np.zeros_like(pr), where=deg > 0)
        dangling = pr[deg == 0].sum()
        pr = (1.0 - damping) / g.n + damping * (AT @ share + dangling / g.n)
    return pr


def check_pagerank(got, want, tag: str):
    import numpy as np
    err = np.abs(np.asarray(got, np.float64) - want)
    worst = float(err.max())
    rel = float(err.sum() / np.abs(want).sum())
    worst_rel = float((err / np.abs(want)).max())
    require(np.isfinite(got).all(), f"{tag}: non-finite ranks")
    require(worst <= PR_ATOL, f"{tag}: max |pr - ref| = {worst} > {PR_ATOL}")
    require(worst_rel <= PR_RTOL,
            f"{tag}: max |pr - ref| / ref = {worst_rel} > {PR_RTOL}")
    require(rel <= PR_L1, f"{tag}: l1 relative error {rel} > {PR_L1}")
    return {"max_abs_err": worst, "max_rel_err": worst_rel,
            "l1_rel_err": rel}


# ----------------------------------------------------------------------
# lowering checks
# ----------------------------------------------------------------------

def expected_backend(kernel: str, platform: str) -> str:
    from repro.backend import registry
    if platform == "tpu":
        return registry.TPU_DEFAULTS[kernel][0]
    return registry.default_backend_name(platform, kernel)


def check_lowerings(names: dict, kernels: dict, platform: str, tag: str):
    """``names``: kernel -> backend it resolved to; ``kernels``: kernel ->
    the object that ran.  Each must match the platform's table, and on a
    TPU none may be interpreted."""
    for k, obj in kernels.items():
        want = expected_backend(k, platform)
        require(names.get(k) == want,
                f"{tag}: kernel {k} resolved to {names.get(k)!r}, the "
                f"{platform} table says {want!r}")
        if platform == "tpu":
            require(not getattr(obj, "interpret", False),
                    f"{tag}: kernel {k} ran in interpret mode")
    log(f"{tag}.backends", **{k: names[k] for k in kernels})


def engine_kernels(eng, dc_only: bool = False) -> dict:
    """The kernels an :class:`~repro.core.engine.Engine` runs: the DC
    stream's and, unless ``dc_only``, the SC fold."""
    ran = {"fused_dc": eng._fused}
    if not dc_only:
        ran["fold"] = eng.kernels.fold
    return ran


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------

def phase_bfs(g, L, roots, want_levels, keys, platform: str):
    """(a) hybrid BFS through ``repro.apps.bfs`` from every root (the
    first pass, which compiles), then from the first root again (warm)."""
    from repro.apps.bfs import bfs, bfs_program
    from repro.core.engine import Engine
    eng = Engine(L, bfs_program(), mode="hybrid")
    check_lowerings(eng.backend_names, engine_kernels(eng), platform, "bfs")
    for pass_name, todo in (("first", roots), ("warm", roots[:1])):
        for i, r in enumerate(todo):
            res, sec = timed(bfs, L, r, engine=eng)
            reached = check_bfs_tree(g, keys, r, res["level"],
                                     res["parent"], want_levels[i])
            modes = [s.mode for s in res["stats"]]
            edges = int(g.out_degrees()[res["level"] >= 0].sum()) // 2
            log(f"bfs.{pass_name}", root=r, seconds=sec,
                supersteps=len(modes), reached=reached,
                dc_sc_parts=[[s.dc_parts, s.sc_parts] for s in res["stats"]],
                step_s=[s.wall_s for s in res["stats"]],
                modes={m: modes.count(m) for m in sorted(set(modes))},
                teps=edges / sec)
    return eng


def phase_pagerank(g, L, want, platform: str):
    """(b) 10 PageRank iterations on the fixed-iteration DC path."""
    from repro.apps.pagerank import pagerank, pagerank_program
    from repro.core.engine import Engine
    eng = Engine(L, pagerank_program(L.n), mode="dc")
    check_lowerings(eng.backend_names, engine_kernels(eng, dc_only=True),
                    platform, "pagerank")
    for pass_name in ("first", "warm"):
        res, sec = timed(pagerank, L, iters=PR_ITERS, engine=eng)
        errs = check_pagerank(res["pr"], want, f"pagerank.{pass_name}")
        log(f"pagerank.{pass_name}", seconds=sec, iters=PR_ITERS,
            edges_per_s=PR_ITERS * g.m / sec, **errs)


def phase_serve(L, sources, bfs_engine, platform: str):
    """(c) ``GraphQueryServer`` answers the Zipf-drawn BFS queries in one
    batched run (cold, then warm after clearing its result cache); every
    answer equals a per-source ``bfs`` on ``bfs_engine``."""
    import numpy as np

    from repro.apps.bfs import bfs
    from repro.serve import GraphQuery, GraphQueryServer, ServeConfig
    srv = GraphQueryServer(L, ServeConfig(max_batch=len(sources)))
    want, sec = timed(lambda: {s: bfs(L, s, engine=bfs_engine)
                               for s in set(sources)})
    log("serve.reference", seconds=sec, sources=len(want))
    for pass_name in ("first", "warm"):
        srv.clear_cache()
        for i, s in enumerate(sources):
            srv.submit(GraphQuery(qid=i, app="bfs", params={"source": s}))
        t0 = time.perf_counter()
        done = srv.run()
        sec = time.perf_counter() - t0
        require(len(done) == len(sources),
                f"serve: {len(done)} of {len(sources)} queries answered")
        for q in done:
            w = want[q.params["source"]]
            for f in ("level", "parent"):
                require(np.array_equal(np.asarray(q.result[f]), w[f]),
                        f"serve: query {q.qid} ({f}) differs from bfs")
        srv.done.clear()
        log(f"serve.{pass_name}", seconds=sec, queries=len(sources),
            distinct_sources=len(set(sources)), qps=len(sources) / sec)
    eng = srv._engines["bfs"]
    check_lowerings(eng.backend_names, engine_kernels(eng), platform,
                    "serve")


def phase_dist(g, L, roots, want_levels, keys, want_pr, devices,
               platform: str):
    """``DistEngine`` BFS (hybrid; every root, then the first again
    warm) and 10 PageRank iterations (DC) over ``shard_layout(L, D)`` on
    a ``D``-device mesh."""
    import jax
    import numpy as np

    from repro.apps.bfs import bfs_program
    from repro.apps.pagerank import pagerank_program
    from repro.dist.engine import DistEngine
    from repro.graph.shard import shard_layout
    D = len(devices)
    mesh = jax.make_mesh((D,), ("dev",), devices=devices)
    t0 = time.perf_counter()
    SL = shard_layout(L, D)
    log("dist.shard", devices=D, seconds=time.perf_counter() - t0,
        nv=SL.nv, slots_per_pair=SL.S, edges_per_device=int(SL.ne_d))
    N = D * SL.nv
    require(N == L.n_pad, f"dist: {D} x {SL.nv} vertices != {L.n_pad}")

    eng = DistEngine(SL, bfs_program(), mesh, mode="hybrid")
    names = {"fold": eng.backend_name, "fused_dc": eng.fused_backend_name}
    ran = {"fold": eng.fold, "fused_dc": eng.fused}
    check_lowerings(names, ran, platform, "dist.bfs")
    for pass_name, todo in (("first", roots), ("warm", roots[:1])):
        for i, r in enumerate(todo):
            parent = np.full(N, -1, np.int32)
            parent[r] = r
            level = np.full(N, -1, np.int32)
            level[r] = 0
            f = np.zeros(N, bool)
            f[r] = True
            st = {"parent": parent, "level": level,
                  "vid": np.arange(N, dtype=np.uint32)}
            (st, _, stats), sec = timed(eng.run, st, f)
            reached = check_bfs_tree(
                g, keys, r, np.asarray(st["level"])[:g.n],
                np.asarray(st["parent"])[:g.n], want_levels[i])
            modes = [s["mode"] for s in stats]
            log(f"dist.bfs.{pass_name}", root=r, seconds=sec,
                supersteps=len(stats), reached=reached,
                modes={m: modes.count(m) for m in sorted(set(modes))})
    log("dist.bfs.memory",
        peak_bytes_in_use=[peak_bytes(d) for d in devices])
    del eng
    gc.collect()

    eng = DistEngine(SL, pagerank_program(g.n), mesh, mode="dc")
    pr0 = np.zeros(N, np.float32)
    pr0[:L.n_pad] = 1.0 / g.n
    deg = np.zeros(N, np.float32)
    deg[:L.n_pad] = L.deg
    f = np.zeros(N, bool)
    f[:g.n] = True
    for pass_name in ("first", "warm"):
        (st, _, _), sec = timed(eng.run, {"pr": pr0, "deg": deg}, f,
                                max_iters=PR_ITERS, until_empty=False)
        errs = check_pagerank(np.asarray(st["pr"])[:g.n], want_pr,
                              f"dist.pagerank.{pass_name}")
        log(f"dist.pagerank.{pass_name}", seconds=sec, iters=PR_ITERS,
            **errs)
    log("dist.memory", peak_bytes_in_use=[peak_bytes(d) for d in devices])


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------------

def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the DistEngine phase on four chips")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {platform!r}); "
              "this check has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX finds {len(devices)}", file=sys.stderr)
        return 1
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.backend import registry
    from repro.compile_cache import setup_compile_cache

    dev = devices[0]
    log("device", platform=platform, kind=dev.device_kind,
        count=len(devices), jax=jax.__version__,
        compile_cache=setup_compile_cache())
    log("tpu_table", **{k: {"backend": b, "reason": why}
                        for k, (b, why) in registry.TPU_DEFAULTS.items()},
        monoids={m: {"backend": "ref", "reason": why}
                 for m, why in registry.TPU_REF_MONOIDS.items()})

    g, L, host = build(SCALE, args.seed)
    log("graph", source="Graph500 Kronecker A/B/C=0.57/0.19/0.19, edge "
        "factor 16, symmetrized", scale=SCALE,
        cut=f"scale {SCALE} = LDBC Graphalytics graph500-{SCALE}; "
            "Graph500's smallest class (toy) is scale 26",
        n=g.n, m=g.m, layout_edges=L.num_edges, layout_msgs=L.num_msgs,
        k=L.k, q=L.q, **host)
    roots = pick_roots(g, BFS_ROOTS, args.seed)
    t0 = time.perf_counter()
    keys = edge_keys(g)
    want_levels = ref_levels(g, roots)
    want_pr = ref_pagerank(g, PR_ITERS)
    log("reference", seconds=time.perf_counter() - t0, roots=roots)

    if args.chips > 1:
        phase_dist(g, L, roots, want_levels, keys, want_pr,
                   devices[:args.chips], platform)
    else:
        bfs_engine = phase_bfs(g, L, roots, want_levels, keys, platform)
        phase_pagerank(g, L, want_pr, platform)
        gc.collect()
        phase_serve(L, zipf_sources(g, SERVE_QUERIES, args.seed),
                    bfs_engine, platform)
        log("memory", peak_bytes_in_use=peak_bytes(dev))
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
