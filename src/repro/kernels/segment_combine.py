"""Gather-phase segmented monoid fold — the PPM hot loop, as a Pallas kernel.

TPU mapping of the paper's Gather phase (§3.2):

  * the grid walks gather-order (destination-major) edge tiles — reading
    ``bin[:][p']`` column-wise exactly like the paper;
  * the destination partition's accumulator tile (``q`` vertices) stays
    resident in VMEM across all tiles of that partition — the paper's
    "vertex data of partition p fits the private cache";
  * tiles whose *source* partition has no active vertices are skipped with
    ``pl.when`` — grid-level predication is the TPU realization of the
    2-level active list (``binPartList``);
  * the per-tile destination block index comes from a scalar-prefetched
    ``tile_dst_part`` array (the static bin-grid geometry).

The ``add`` fold uses an MXU-friendly one-hot matmul; ``min``/``max`` use a
masked VPU reduce.  Outputs are (acc[k, q], touched[k, q]).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np


def _identity_val(monoid: str, dtype):
    if monoid == "add":
        return np.zeros((), dtype)
    if monoid == "min":
        return (np.array(np.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
                else np.array(np.iinfo(dtype).max, dtype))
    if monoid == "max":
        return (np.array(-np.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
                else np.array(np.iinfo(dtype).min, dtype))
    raise ValueError(monoid)


_SIGN = np.int32(-2**31)


def to_signed(x, monoid: str):
    """Order-preserving int32 view of a uint32 payload for the kernels.

    Mosaic has no reductions over unsigned integers.  Flipping the sign
    bit maps the unsigned order onto the signed one, so min/max commute
    with the view; add wraps identically on the plain bitcast.  Other
    dtypes pass through unchanged."""
    if x.dtype != jnp.uint32:
        return x
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits if monoid == "add" else bits ^ _SIGN


def from_signed(x, monoid: str, dtype):
    """Inverse of :func:`to_signed` back to ``dtype``."""
    if jnp.dtype(dtype) != jnp.uint32:
        return x
    if monoid != "add":
        x = x ^ _SIGN
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


#: edges per one-hot combine inside a grid step; the step's DMA block is
#: the layout's whole edge tile (a multiple of 1024 on TPU, where XLA
#: lays large 1-D arrays out in 1024-element tiles)
CHUNK = 256


def chunks(n: int):
    """Static ``pl.ds`` slices covering a block of ``n`` elements."""
    c = min(CHUNK, n)
    return [pl.ds(i, min(c, n - i)) for i in range(0, n, c)]


def _kernel(tile_dst_ref, tile_src_ref, tile_first_ref,   # scalar prefetch
            part_active_ref,                               # scalar prefetch
            vals_ref, dstl_ref,                            # VMEM in
            acc_ref, touched_ref,                          # VMEM out
            *, monoid: str, q: int):
    t = pl.program_id(0)
    ident = _identity_val(monoid, acc_ref.dtype)

    # first tile of this destination partition: initialize the accumulator
    @pl.when(tile_first_ref[t] > 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ident)
        touched_ref[...] = jnp.zeros_like(touched_ref)

    # 2-level active list: skip tiles whose source partition is inactive
    @pl.when(part_active_ref[tile_src_ref[t]] > 0)
    def _body():
        for part in chunks(vals_ref.shape[0]):
            vals = vals_ref[part]                           # [T]
            # invalid slots carry dst -1 (matches no column): Mosaic
            # cannot relayout a bool [T] vector into a [T, 1] column
            dstl = dstl_ref[part]                           # [T]
            cols = jax.lax.broadcasted_iota(jnp.int32, (vals.shape[0], q), 1)
            onehot = dstl[:, None] == cols                  # [T, q]
            if monoid == "add":
                if jnp.issubdtype(acc_ref.dtype, jnp.floating):
                    contrib = jnp.dot(
                        jnp.where(dstl >= 0, vals, 0).astype(
                            jnp.float32)[None, :],
                        onehot.astype(jnp.float32),
                        preferred_element_type=jnp.float32)[0]
                else:
                    # 32-bit integer state: the f32 MXU round trip
                    # truncates above 2**24, so fold on the VPU in the
                    # native dtype
                    masked = jnp.where(onehot, vals[:, None],
                                       jnp.zeros((), acc_ref.dtype))
                    contrib = jnp.sum(masked, axis=0)
                acc_ref[...] = acc_ref[...] \
                    + contrib.astype(acc_ref.dtype)[None, :]
            elif monoid == "min":
                masked = jnp.where(onehot, vals[:, None], ident)
                acc_ref[...] = jnp.minimum(acc_ref[...],
                                           jnp.min(masked, axis=0)[None, :])
            elif monoid == "max":
                masked = jnp.where(onehot, vals[:, None], ident)
                acc_ref[...] = jnp.maximum(acc_ref[...],
                                           jnp.max(masked, axis=0)[None, :])
            touched_ref[...] = jnp.maximum(
                touched_ref[...],
                jnp.max(onehot.astype(jnp.int32), axis=0)[None, :])


@functools.partial(jax.jit, static_argnames=("k", "q", "edge_tile", "monoid",
                                             "interpret"))
def segment_combine(edge_vals, edge_valid, edge_dst_local,
                    tile_dst_part, tile_src_part, tile_first,
                    part_active, *, k: int, q: int, edge_tile: int,
                    monoid: str = "add", interpret: bool = True):
    """Fold edge messages into per-partition accumulators.

    Args:
      edge_vals:      [NE] message value per edge (gather order).
      edge_valid:     [NE] int32 validity (pads & inactive-source slots = 0).
      edge_dst_local: [NE] int32 destination id within its partition.
      tile_dst_part / tile_src_part / tile_first: [NT] int32 tile geometry.
      part_active:    [k] int32 source-partition activity (gPartList).
    Returns:
      acc [k, q] monoid fold, touched [k, q] int32.
    """
    nt = tile_dst_part.shape[0]
    dtype = edge_vals.dtype
    vals = to_signed(edge_vals, monoid)
    dstl = jnp.where(edge_valid > 0, edge_dst_local.astype(jnp.int32), -1)
    # accumulator rows are [k, 1, q] so a (1, q) block spans the array's
    # last two dims whole — the (8, 128) block rule holds for any k, q
    acc_spec = pl.BlockSpec((None, 1, q),
                            lambda t, td, ts, tf, pa: (td[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((edge_tile,), lambda t, *pf: (t,)),
            pl.BlockSpec((edge_tile,), lambda t, *pf: (t,)),
        ],
        out_specs=[acc_spec, acc_spec],
    )
    acc, touched = pl.pallas_call(
        functools.partial(_kernel, monoid=monoid, q=q),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((k, 1, q), vals.dtype),
                   jax.ShapeDtypeStruct((k, 1, q), jnp.int32)],
        interpret=interpret,
    )(tile_dst_part, tile_src_part, tile_first.astype(jnp.int32),
      part_active.astype(jnp.int32), vals, dstl)
    return from_signed(acc.reshape(k, q), monoid, dtype), touched.reshape(k, q)
