"""PageRank (paper Alg. 6), as LDBC Graphalytics defines it.

    PR_0(v) = 1/n
    PR_i(v) = (1-d)/n + d * sum_{u->v} PR_{i-1}(u)/|out(u)|
                      + (d/n) * sum_{w in D} PR_{i-1}(w)

``D`` is the set of dangling vertices (no out-edge): their rank is spread
over every vertex, so the total stays 1.  scatterFunc -> rank/deg;
initFunc -> zero the rank, stay active; gatherFunc -> accumulate;
filterFunc -> damping plus the dangling term, whose sum is the program's
aggregate.  All vertices stay active every iteration, so the engine runs
the fully-fused DC path (paper §6.2.2: "PageRank always uses DC mode").
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..core import monoid as M
from ..core.engine import Engine
from ..core.program import VertexProgram


def pagerank_program(n: int, damping: float = 0.85) -> VertexProgram:
    base = (1.0 - damping) / n

    def scatter_fn(state):
        return jnp.where(state["deg"] > 0, state["pr"] / state["deg"], 0.0)

    def init_fn(state, it):
        return dict(state, pr=jnp.zeros_like(state["pr"])), \
            jnp.ones(state["pr"].shape, jnp.bool_)

    def apply_fn(state, acc, touched, it):
        return dict(state, pr=state["pr"] + acc), jnp.ones_like(touched)

    def dangling(state):
        return jnp.where(state["deg"] > 0, 0.0, state["pr"])

    def filter_fn(state, it, dangling_mass):
        teleport = base + damping * dangling_mass / n
        return dict(state, pr=teleport + damping * state["pr"]), \
            jnp.ones(state["pr"].shape, jnp.bool_)

    return VertexProgram(name="pagerank", monoid=M.add(jnp.float32),
                         scatter_fn=scatter_fn, apply_fn=apply_fn,
                         init_fn=init_fn, filter_fn=filter_fn,
                         aggregate=dangling)


def pagerank(layout, iters: int = 10, damping: float = 0.85,
             mode: str = "dc", fused: bool = True,
             use_pallas: bool = None, backend=None,
             engine: Engine = None, pr0=None):
    """``pr0=`` is the residual-restart path for dynamic graphs: pass the
    previous layout's converged ``[n]`` (or ``[n_pad]``) vector after a
    small delta and the damping contraction shrinks the *residual* —
    which a warm start leaves small — by ``damping`` each sweep, so the
    same fixpoint is reached in far fewer iterations than from the
    uniform cold init (the iteration itself is unchanged and the
    fixpoint is unique, so warm vs cold agree to the tolerance the
    iteration count buys)."""
    n_pad = layout.n_pad
    if pr0 is None:
        pr = jnp.full((n_pad,), 1.0 / layout.n, jnp.float32)
    else:
        warm = np.asarray(pr0, np.float32).reshape(-1)
        pr = np.full(n_pad, 1.0 / layout.n, np.float32)
        pr[:min(warm.size, n_pad)] = warm[:n_pad]
        pr = jnp.asarray(pr)
    deg = jnp.asarray(layout.deg.astype(np.float32))
    state0 = {"pr": pr, "deg": deg}
    frontier = np.zeros(n_pad, bool)
    frontier[:layout.n] = True
    eng = engine if engine is not None else Engine(
        layout, pagerank_program(layout.n, damping), mode=mode,
        backend=backend, use_pallas=use_pallas)
    if fused:
        state, _ = eng.run_fused(state0, frontier, iters)
        stats = []
    else:
        state, _, stats = eng.run(state0, frontier, max_iters=iters,
                                  until_empty=False)
    return {"pr": np.asarray(state["pr"])[:layout.n], "stats": stats}
