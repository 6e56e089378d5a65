"""Graph500 Kronecker edge list, drawn on the device.

Graph500's generator (the "Kronecker" generator of the Graph500
specification, after Chakrabarti et al.'s R-MAT): ``edge_factor * 2**scale``
directed edges; each of an edge's ``scale`` bit levels picks one quadrant
of the adjacency matrix with probabilities A, B, C and D = 1 - A - B - C.
Duplicates and self loops are kept here, as the specification's edge list
keeps them; the system's ingest removes them.

The quadrant draws compare 32-bit random words against integer
thresholds, so the edge list is the same on every backend for one key.
"""
from __future__ import annotations

import functools

import numpy as np

def _threshold(p: float) -> int:
    return min(int(round(p * 2.0 ** 32)), 2 ** 32 - 1)


@functools.partial(__import__("jax").jit, static_argnames=("scale", "m"))
def _draw(key, scale: int, m: int, ta, tab, tabc):
    import jax
    import jax.numpy as jnp

    def level(bit, carry):
        src, dst = carry
        r = jax.random.bits(jax.random.fold_in(key, bit), (m,), jnp.uint32)
        down = r >= tab
        right = ((r >= ta) & (r < tab)) | (r >= tabc)
        src = src | (down.astype(jnp.int32) << bit)
        dst = dst | (right.astype(jnp.int32) << bit)
        return src, dst

    zero = jnp.zeros((m,), jnp.int32)
    return jax.lax.fori_loop(0, scale, level, (zero, zero))


def edges(cfg: dict, structure_seed: int):
    """``(n, src, dst)``: the directed Kronecker edge list in structural
    vertex ids (int32 host arrays of ``edge_factor * 2**scale`` edges)."""
    import jax
    import jax.numpy as jnp
    scale, ef = int(cfg["scale"]), int(cfg["edge_factor"])
    a, b, c = float(cfg["a"]), float(cfg["b"]), float(cfg["c"])
    n, m = 1 << scale, ef << scale
    ts = [jnp.uint32(_threshold(p)) for p in (a, a + b, a + b + c)]
    src, dst = _draw(jax.random.key(structure_seed), scale, m, *ts)
    src, dst = jax.device_get((src, dst))
    return n, np.asarray(src), np.asarray(dst)


def expected_edges(cfg: dict) -> int:
    """Directed edges the generator draws: exactly ``edge_factor * n``."""
    return int(cfg["edge_factor"]) << int(cfg["scale"])
