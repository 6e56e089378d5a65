"""SSSP with parent tracking via the packed (distance, parent) min-monoid.

The paper's Alg. 8 tracks distances only; production SSSP wants the shortest
-path tree.  A lexicographic uint64 lattice — (f32 distance bits << 32) |
parent id — keeps the whole fold a pure ``min``, so the lock-free gather
contract is untouched.  Requires x64 (see monoid.min_with_payload).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import monoid as M
from ..core.engine import Engine
from ..core.program import VertexProgram


def sssp_parents_program() -> VertexProgram:
    mono = M.min_with_payload()

    def scatter_fn(state):
        # message key = my distance (weight added en route), payload = my id
        return M.pack_key_payload(state["dist"], state["vid"])

    def apply_weight(vals, w):
        key, payload = M.unpack_key_payload(vals)
        return M.pack_key_payload(key + w, payload)

    def apply_fn(state, acc, touched, it):
        key, parent = M.unpack_key_payload(acc)
        better = touched & (key < state["dist"])
        dist = jnp.where(better, key, state["dist"])
        par = jnp.where(better, parent.astype(jnp.int32), state["parent"])
        return dict(state, dist=dist, parent=par), better

    return VertexProgram(name="sssp_parents", monoid=mono,
                         scatter_fn=scatter_fn, apply_fn=apply_fn,
                         apply_weight=apply_weight)


def sssp_with_parents(layout, source: int, mode: str = "hybrid",
                      backend=None, engine: Engine = None,
                      max_iters: int = None):
    assert layout.weighted, "needs edge weights"
    with jax.enable_x64(True):
        n_pad = layout.n_pad
        dist = jnp.full((n_pad,), jnp.inf, jnp.float32).at[source].set(0.0)
        parent = jnp.full((n_pad,), -1, jnp.int32).at[source].set(source)
        vid = jnp.arange(n_pad, dtype=jnp.uint32)
        frontier = np.zeros(n_pad, bool)
        frontier[source] = True
        eng = engine if engine is not None else Engine(
            layout, sssp_parents_program(), mode=mode, backend=backend)
        state, _, stats = eng.run(
            {"dist": dist, "parent": parent, "vid": vid}, frontier,
            max_iters=max_iters or n_pad)
        return {"dist": np.asarray(state["dist"])[:layout.n],
                "parent": np.asarray(state["parent"])[:layout.n],
                "stats": stats}


def sssp_parents_multi(layout, sources, engine: Engine = None,
                       max_iters: int = None):
    """Batched multi-source SSSP with parent tracking (uint64 packed
    monoid, so the gather falls back to the ref kernels — still one fused
    vmapped invocation per iteration).  Row ``i`` belongs to
    ``sources[i]``.  A :class:`repro.dist.engine.DistEngine` works as
    ``engine`` too; its bf16 wire never engages for this monoid (uint64,
    not f32), so distributed results stay exact."""
    assert layout.weighted, "needs edge weights"
    with jax.enable_x64(True):
        sources = np.atleast_1d(np.asarray(sources, dtype=np.int64))
        B, n_pad = len(sources), layout.n_pad
        src = jnp.asarray(sources, jnp.int32)
        lanes = jnp.arange(B)
        dist = jnp.full((B, n_pad), jnp.inf, jnp.float32) \
            .at[lanes, src].set(0.0)
        parent = jnp.full((B, n_pad), -1, jnp.int32).at[lanes, src].set(src)
        vid = jnp.broadcast_to(jnp.arange(n_pad, dtype=jnp.uint32),
                               (B, n_pad))
        frontier = np.zeros((B, n_pad), bool)
        frontier[np.arange(B), sources] = True
        eng = engine if engine is not None else Engine(
            layout, sssp_parents_program(), mode="dc")
        states, _, stats = eng.run_batched(
            {"dist": dist, "parent": parent, "vid": vid}, frontier,
            max_iters=max_iters or n_pad)
        return {"dist": np.asarray(states["dist"])[:, :layout.n],
                "parent": np.asarray(states["parent"])[:, :layout.n],
                "stats": stats}
