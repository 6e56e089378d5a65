"""Fused partition-centric SpMV — the PageRank DC-mode inner loop.

This is the flagship kernel of the reproduction: one pass over the gather-
order (dst-major) dc_bin layout computes ``y[dst] += w * x[src]`` with BOTH
partition tiles VMEM-resident:

  * ``x`` tile of the *source* partition (block = tile_src_part[t]),
  * ``y`` accumulator tile of the *destination* partition
    (block = tile_dst_part[t], revisited consecutively in dst-major order).

On a CPU this is exactly the paper's cache-blocked PCPM loop ([17]); on TPU
the two q-vectors sit in VMEM and the edge stream is the only HBM traffic —
the layout's arithmetic-intensity shaping is the paper's contribution, the
MXU one-hot matmul is the TPU-native fold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .segment_combine import chunks


def _kernel(tile_dst_ref, tile_src_ref, tile_first_ref,   # scalar prefetch
            x_ref, srcl_ref, dstl_ref, w_ref,             # VMEM in
            y_ref, *, q: int, weighted: bool):
    t = pl.program_id(0)

    @pl.when(tile_first_ref[t] > 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    x = x_ref[...]                                         # [1, q]
    for part in chunks(srcl_ref.shape[0]):
        srcl = srcl_ref[part]                              # [T]
        dstl = dstl_ref[part]                              # [T], -1 = pad
        cols = jax.lax.broadcasted_iota(jnp.int32, (srcl.shape[0], q), 1)
        # one-hot select gather of the source values (Mosaic lowers no
        # arbitrary vector gather); pads select nothing and contribute 0
        vals = jnp.sum(jnp.where((srcl[:, None] == cols)
                                 & (dstl[:, None] >= 0), x, 0.0), axis=1)
        if weighted:
            vals = vals * w_ref[part]
        onehot = (dstl[:, None] == cols).astype(jnp.float32)
        y_ref[...] = y_ref[...] + jnp.dot(
            vals.astype(jnp.float32)[None, :], onehot,
            preferred_element_type=jnp.float32).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "q", "edge_tile",
                                             "weighted", "interpret"))
def spmv_block(x, edge_src_local, edge_dst_local, edge_valid, edge_w,
               tile_dst_part, tile_src_part, tile_first,
               *, k: int, q: int, edge_tile: int, weighted: bool = False,
               interpret: bool = True):
    """One partition-centric SpMV pass.  Returns y[k, q] = A^T x (+weights)."""
    nt = tile_dst_part.shape[0]
    if edge_w is None:
        edge_w = jnp.ones_like(x, shape=(edge_src_local.shape[0],))
    dstl = jnp.where(edge_valid > 0, edge_dst_local.astype(jnp.int32), -1)
    # [k, 1, q] rows: a (1, q) block spans the last two dims whole, which
    # satisfies the (8, 128) block rule for any k, q
    src_row = pl.BlockSpec((None, 1, q), lambda t, td, ts, tf: (ts[t], 0, 0))
    dst_row = pl.BlockSpec((None, 1, q), lambda t, td, ts, tf: (td[t], 0, 0))
    edge = pl.BlockSpec((edge_tile,), lambda t, *pf: (t,))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nt,),
        in_specs=[src_row, edge, edge, edge],
        out_specs=dst_row,
    )
    y = pl.pallas_call(
        functools.partial(_kernel, q=q, weighted=weighted),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, 1, q), x.dtype),
        interpret=interpret,
    )(tile_dst_part, tile_src_part, tile_first.astype(jnp.int32),
      x.reshape(k, 1, q), edge_src_local, dstl, edge_w)
    return y.reshape(k, q)
