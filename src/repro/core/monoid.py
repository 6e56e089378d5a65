"""Gather-phase combine monoids.

The paper's ``gatherFunc`` is arbitrary sequential code executed under
exclusive partition ownership.  On TPU the fold must be an associative and
commutative monoid so it can be evaluated as a data-parallel segmented
reduction; all five applications evaluated in the paper (BFS, PageRank,
Label Propagation, SSSP, Nibble) use such monoids (min / add / first-visit).

``min_with_payload`` packs a (key, payload) pair into a single uint64 lattice
so that e.g. SSSP can keep distance *and* parent inside a pure ``min`` fold
(non-negative float32 keys have monotone bit patterns).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Monoid:
    name: str
    dtype: np.dtype
    identity: object                      # scalar identity element
    combine: Callable                     # (a, b) -> a*b  (assoc. + comm.)
    segment_fold: Callable                # (vals, ids, num_segments) -> acc

    def identity_array(self, shape):
        return jnp.full(shape, self.identity, dtype=self.dtype)


def _seg(fn):
    def fold(vals, ids, num_segments):
        return fn(vals, ids, num_segments=num_segments,
                  indices_are_sorted=False)
    return fold


def add(dtype=jnp.float32) -> Monoid:
    return Monoid("add", jnp.dtype(dtype), np.array(0, dtype),
                  lambda a, b: a + b, _seg(jax.ops.segment_sum))


def min_(dtype=jnp.uint32) -> Monoid:
    ident = (np.array(np.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
             else np.array(np.iinfo(dtype).max, dtype))
    return Monoid("min", jnp.dtype(dtype), ident,
                  jnp.minimum, _seg(jax.ops.segment_min))


def max_(dtype=jnp.uint32) -> Monoid:
    ident = (np.array(-np.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
             else np.array(np.iinfo(dtype).min, dtype))
    return Monoid("max", jnp.dtype(dtype), ident,
                  jnp.maximum, _seg(jax.ops.segment_max))


def or_() -> Monoid:
    return Monoid("or", jnp.dtype(jnp.uint32), np.uint32(0),
                  lambda a, b: a | b, _seg(jax.ops.segment_max))


def min_with_payload() -> Monoid:
    """min over packed uint64 = (f32-key bits << 32) | uint32 payload.

    Requires x64 (``jax.enable_x64(True)`` or JAX_ENABLE_X64);
    without it JAX silently truncates uint64 to uint32."""
    return Monoid("min_with_payload", jnp.dtype(jnp.uint64),
                  np.uint64(np.iinfo(np.uint64).max),
                  jnp.minimum, _seg(jax.ops.segment_min))


def pack_key_payload(key_f32, payload_u32):
    bits = jax.lax.bitcast_convert_type(key_f32.astype(jnp.float32),
                                        jnp.uint32)
    return (bits.astype(jnp.uint64) << np.uint64(32)) | \
        payload_u32.astype(jnp.uint64)


def unpack_key_payload(packed_u64):
    key = jax.lax.bitcast_convert_type(
        (packed_u64 >> np.uint64(32)).astype(jnp.uint32), jnp.float32)
    payload = (packed_u64 & np.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    return key, payload


REGISTRY = {
    "add": add, "min": min_, "max": max_, "or": or_,
    "min_with_payload": min_with_payload,
}
