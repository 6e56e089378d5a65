"""Pure-jnp oracle for the segmented monoid fold.

The semantic ground truth the fold tests check the Pallas kernels
against (interpret mode).  The ``ref`` backend's own kernels live in
:mod:`repro.kernels.ops` (``RefFold``, ``RefFusedDC``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _identity(monoid: str, dtype):
    if monoid == "add":
        return jnp.zeros((), dtype)
    if monoid == "min":
        return (jnp.array(jnp.inf, dtype)
                if jnp.issubdtype(dtype, jnp.floating)
                else jnp.array(jnp.iinfo(dtype).max, dtype))
    if monoid == "max":
        return (jnp.array(-jnp.inf, dtype)
                if jnp.issubdtype(dtype, jnp.floating)
                else jnp.array(jnp.iinfo(dtype).min, dtype))
    raise ValueError(monoid)


def segment_combine_ref(vals, valid, ids, num_segments, monoid="add"):
    """Monoid fold of valid messages by destination + touched flags."""
    ident = _identity(monoid, vals.dtype)
    vals = jnp.where(valid.astype(bool), vals, ident)
    if monoid == "add":
        acc = jax.ops.segment_sum(vals, ids, num_segments=num_segments)
    elif monoid == "min":
        acc = jax.ops.segment_min(vals, ids, num_segments=num_segments)
        acc = jnp.where(jnp.isinf(acc) if jnp.issubdtype(vals.dtype, jnp.floating)
                        else acc == ident, ident, acc)
    elif monoid == "max":
        acc = jax.ops.segment_max(vals, ids, num_segments=num_segments)
        acc = jnp.where(jnp.isinf(acc) if jnp.issubdtype(vals.dtype, jnp.floating)
                        else acc == ident, ident, acc)
    else:
        raise ValueError(monoid)
    touched = jax.ops.segment_max(valid.astype(jnp.int32), ids,
                                  num_segments=num_segments) > 0
    return acc, touched
