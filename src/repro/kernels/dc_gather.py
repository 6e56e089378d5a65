"""Scatter-phase DC message materialization as a Pallas kernel.

The paper's DC Scatter streams the PNG layout of partition ``p`` and writes
*data-only* messages sequentially into the bin row (§3.3, Alg. 2).  Here the
grid walks message-slot tiles (row-major (p, p') order = writing ``bin[p][:]``
sequentially); the source partition's value tile and activity tile are
VMEM-resident (blocked by the scalar-prefetched ``png_tile_part``), and each
slot gathers its source's value — identity for inactive or padding slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .segment_combine import _identity_val, chunks, from_signed, to_signed


def _kernel(tile_part_ref,                       # scalar prefetch
            x_ref, act_ref, srcl_ref,             # VMEM in
            out_ref, *, ident):
    x = x_ref[...]                                # [1, q] partition values
    act = act_ref[...]                            # [1, q] partition activity
    q = x.shape[1]
    for part in chunks(srcl_ref.shape[0]):
        srcl = srcl_ref[part]                     # [T] local src, -1 = pad
        # one-hot select gather: Mosaic lowers no arbitrary vector gather
        # (only in-register 2-D ones), and exactly one column matches
        sel = srcl[:, None] == jax.lax.broadcasted_iota(
            jnp.int32, (srcl.shape[0], q), 1)
        vals = jnp.sum(jnp.where(sel, x, 0), axis=1)
        ok = jnp.sum(jnp.where(sel, act, 0), axis=1) > 0
        out_ref[part] = jnp.where(ok, vals, ident)


@functools.partial(jax.jit, static_argnames=("k", "q", "msg_tile", "monoid",
                                             "interpret"))
def dc_gather(x, active, png_src_local, png_valid, png_tile_part,
              *, k: int, q: int, msg_tile: int, monoid: str = "add",
              interpret: bool = True):
    """Materialize the DC message buffer.

    Args:
      x:              [k, q] per-vertex scatter values (already scatter_fn'd).
      active:         [k, q] int32 per-vertex activity.
      png_src_local:  [NM] int32 source id within its partition.
      png_valid:      [NM] int32 slot validity (0 on pads).
      png_tile_part:  [NTM] int32 source partition per slot tile.
    Returns:
      msg values [NM] (identity on invalid/inactive slots).
    """
    ntm = png_tile_part.shape[0]
    dtype = x.dtype
    # int32 views: the select gather sums, and Mosaic reduces no uint32
    x = to_signed(x, "add").reshape(k, 1, q)
    ident = np.asarray(_identity_val(monoid, dtype)).view(x.dtype)
    srcl = jnp.where(png_valid > 0, png_src_local.astype(jnp.int32), -1)
    # [k, 1, q] rows: a (1, q) block spans the last two dims whole, which
    # satisfies the (8, 128) block rule for any k, q
    row = pl.BlockSpec((None, 1, q), lambda t, tp: (tp[t], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ntm,),
        in_specs=[row, row, pl.BlockSpec((msg_tile,), lambda t, tp: (t,))],
        out_specs=pl.BlockSpec((msg_tile,), lambda t, tp: (t,)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, ident=ident),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((png_src_local.shape[0],), x.dtype),
        interpret=interpret,
    )(png_tile_part, x, active.astype(jnp.int32).reshape(k, 1, q), srcl)
    return from_signed(out, "add", dtype)
