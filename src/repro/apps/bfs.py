"""Breadth-First Search (paper Alg. 5).

scatterFunc -> own id;  initFunc -> false (frontier rebuilt);
gatherFunc -> first-visit parent update (min-monoid: lowest-id parent wins,
a deterministic valid BFS tree);  filterFunc -> true.

:func:`bfs_seeded_program` is the warm-startable variant: the stock
program derives levels from the iteration counter (``level = it + 1``),
which is only correct from a cold frontier, so the serving tier's
landmark-seeded queries instead run a packed lexicographic
``(level, parent)`` min-monoid relaxation whose cold run is
bit-identical to stock BFS (see its docstring) and whose warm run is
exactly correct from any upper-bound seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monoid as M
from ..core.engine import Engine
from ..core.program import VertexProgram


def bfs_program() -> VertexProgram:
    def scatter_fn(state):
        return state["vid"]

    def apply_fn(state, acc, touched, it):
        unvisited = state["parent"] < 0
        hit = touched & unvisited
        parent = jnp.where(hit, acc.astype(jnp.int32), state["parent"])
        level = jnp.where(hit, it + 1, state["level"])
        return dict(state, parent=parent, level=level), hit

    return VertexProgram(name="bfs", monoid=M.min_(jnp.uint32),
                         scatter_fn=scatter_fn, apply_fn=apply_fn)


def bfs(layout, source: int, mode: str = "hybrid",
        use_pallas: bool = None, bw_ratio: float = 2.0,
        backend=None, engine: Engine = None, max_iters: int = None):
    n_pad = layout.n_pad
    parent = jnp.full((n_pad,), -1, jnp.int32).at[source].set(source)
    level = jnp.full((n_pad,), -1, jnp.int32).at[source].set(0)
    vid = jnp.arange(n_pad, dtype=jnp.uint32)
    frontier = np.zeros(n_pad, bool)
    frontier[source] = True
    eng = engine if engine is not None else Engine(
        layout, bfs_program(), mode=mode, backend=backend,
        use_pallas=use_pallas, bw_ratio=bw_ratio)
    state, _, stats = eng.run({"parent": parent, "level": level, "vid": vid},
                              frontier, max_iters=max_iters or n_pad)
    return {"parent": np.asarray(state["parent"])[:layout.n],
            "level": np.asarray(state["level"])[:layout.n],
            "stats": stats}


def bfs_multi(layout, sources, backend=None, engine: Engine = None,
              max_iters: int = None):
    """Batched multi-source BFS: one fused :meth:`Engine.run_batched`
    invocation answers ``len(sources)`` queries, bit-exact with per-source
    :func:`bfs` calls.  Row ``i`` of every result array belongs to
    ``sources[i]``.  ``engine`` may also be a
    :class:`repro.dist.engine.DistEngine` over a sharding of this layout
    (``D*nv == n_pad``: the global vertex space is identical), in which
    case the batch advances across the device mesh."""
    sources = np.atleast_1d(np.asarray(sources, dtype=np.int64))
    B, n_pad = len(sources), layout.n_pad
    lanes = jnp.arange(B)
    src = jnp.asarray(sources, jnp.int32)
    parent = jnp.full((B, n_pad), -1, jnp.int32).at[lanes, src].set(src)
    level = jnp.full((B, n_pad), -1, jnp.int32).at[lanes, src].set(0)
    vid = jnp.broadcast_to(jnp.arange(n_pad, dtype=jnp.uint32), (B, n_pad))
    frontier = np.zeros((B, n_pad), bool)
    frontier[np.arange(B), sources] = True
    eng = engine if engine is not None else Engine(
        layout, bfs_program(), mode="dc", backend=backend)
    states, _, stats = eng.run_batched(
        {"parent": parent, "level": level, "vid": vid}, frontier,
        max_iters=max_iters or n_pad)
    return {"parent": np.asarray(states["parent"])[:, :layout.n],
            "level": np.asarray(states["level"])[:, :layout.n],
            "stats": stats}


# ----------------------------------------------------------------------
# warm-startable BFS (landmark seeding)
# ----------------------------------------------------------------------

#: payload sentinel for "level known (or bounded), parent unknown" seeds —
#: any real parent message with an equal key beats it lexicographically
PARENT_SENTINEL = np.uint32(0xFFFFFFFF)


def bfs_seeded_program() -> VertexProgram:
    """BFS as a packed lexicographic ``(level, parent)`` relaxation.

    State holds one uint64 per vertex: ``(f32 level bits << 32) | parent``
    (:func:`repro.core.monoid.pack_key_payload`; unvisited = ``(inf,
    PARENT_SENTINEL)``).  Scatter sends ``(level + 1, own id)`` (identity
    for unvisited vertices, so they never pollute the fold); apply keeps
    the packed minimum and activates on any packed improvement.

    Cold equivalence with :func:`bfs_program` (bit-exact levels AND
    parents): from a cold frontier, a vertex at true level ``t`` first
    receives messages at iteration ``t-1``, all of them from in-neighbors
    at level ``t-1`` (deeper neighbors are still unvisited and scatter
    the identity; shallower ones send larger keys which lose the fold),
    so the packed min is ``(t, min id of the level-(t-1) in-neighbors)``
    — exactly the first-visit update of the stock program.

    Warm correctness: the packed order is a monotone min-monoid, so
    relaxation from any *upper-bound* initialization converges to the
    same least fixpoint as the cold run (see
    :mod:`repro.serve.cache` for the full argument).  Requires x64
    (uint64 packing) — run inside ``jax.enable_x64(True)``.
    """
    mono = M.min_with_payload()

    def scatter_fn(state):
        key, _ = M.unpack_key_payload(state["best"])
        msg = M.pack_key_payload(key + 1.0, state["vid"])
        return jnp.where(jnp.isfinite(key), msg, mono.identity)

    def apply_fn(state, acc, touched, it):
        better = touched & (acc < state["best"])
        best = jnp.where(better, acc, state["best"])
        return dict(state, best=best), better

    return VertexProgram(name="bfs_seeded", monoid=mono,
                         scatter_fn=scatter_fn, apply_fn=apply_fn)


def bfs_seeded_pack(level, parent):
    """Pack int level / parent vectors (−1 = unvisited) into the seeded
    program's uint64 state.  Needs an active x64 context."""
    level = jnp.asarray(level)
    visited = level >= 0
    key = jnp.where(visited, level.astype(jnp.float32), jnp.inf)
    payload = jnp.where(visited, jnp.asarray(parent).astype(jnp.uint32),
                        PARENT_SENTINEL)
    return M.pack_key_payload(key, payload)


def bfs_seeded_multi(layout, sources, engine: Engine = None,
                     max_iters: int = None, seeds=None, frontiers=None,
                     seed_levels=None, seed_parents=None):
    """Batched warm-startable BFS.  Without seeds this is a cold run
    of :func:`bfs_seeded_program`, bit-exact with :func:`bfs_multi`.

    ``seeds`` is an optional ``[B, n_pad]`` uint64 array of packed
    ``(level upper bound, parent)`` initializations (see
    :func:`bfs_seeded_pack`); lanes may mix seeded and cold entries.
    ``seed_levels`` / ``seed_parents`` (``[B, n_pad]`` int, −1 =
    unvisited / unknown parent) are the unpacked convenience form —
    packing needs an active x64 context, which only exists inside this
    function, so callers holding plain int vectors pass them here
    instead of calling :func:`bfs_seeded_pack` themselves.
    ``frontiers`` (``[B, n_pad]`` bool) must cover every vertex carrying
    a finite seed so stale bounds get relaxed; it defaults to the cold
    one-hot sources."""
    sources = np.atleast_1d(np.asarray(sources, dtype=np.int64))
    B, n_pad = len(sources), layout.n_pad
    with jax.enable_x64(True):
        src = jnp.asarray(sources, jnp.int32)
        lanes = jnp.arange(B)
        if seeds is not None:
            best = jnp.asarray(seeds, jnp.uint64)
        elif seed_levels is not None:
            best = bfs_seeded_pack(jnp.asarray(seed_levels),
                                   jnp.asarray(seed_parents))
        else:
            level = jnp.full((B, n_pad), -1, jnp.int32).at[lanes, src].set(0)
            best = bfs_seeded_pack(level, jnp.broadcast_to(src[:, None],
                                                           (B, n_pad)))
        vid = jnp.broadcast_to(jnp.arange(n_pad, dtype=jnp.uint32),
                               (B, n_pad))
        if frontiers is None:
            frontiers = np.zeros((B, n_pad), bool)
            frontiers[np.arange(B), sources] = True
        eng = engine if engine is not None else Engine(
            layout, bfs_seeded_program(), mode="dc")
        states, _, stats = eng.run_batched({"best": best, "vid": vid},
                                           frontiers,
                                           max_iters=max_iters or n_pad)
        key, payload = M.unpack_key_payload(states["best"])
        visited = jnp.isfinite(key)
        level = jnp.where(visited, key.astype(jnp.int32), -1)
        parent = jnp.where(visited, payload.astype(jnp.int32), -1)
        return {"parent": np.asarray(parent)[:, :layout.n],
                "level": np.asarray(level)[:, :layout.n],
                "stats": stats}
