"""Blocked segmented monoid fold — the Gather phase over a message stream.

This is the shard_map-side realization of the paper's Gather loop (§3.2,
Alg. 4): a device receives its bin column as one flat message stream
``(vals, valid, ids)`` and folds it into a ``[num_segments]`` accumulator
(``num_segments = nv + 1``: the device's vertices plus one overflow bin
that absorbs sentinel ids).  The paper's claim that this runs lock- and
atomic-free out of cache maps onto the kernel as:

  * the grid walks fixed-size VMEM blocks of the message stream
    (``fold_tile`` messages per step) — the bins are streamed sequentially,
    never random-accessed;
  * the accumulator block (``[1, num_segments_padded]``) stays resident in
    VMEM across *all* grid steps (the output block index is constant), so
    every partial combine is a register/VMEM operation — no scatter-add,
    no ``jax.ops.segment_*``, no atomics anywhere in the lowering;
  * block partials compose through the monoid because TPU grid steps
    execute sequentially over a revisited output block.

Because the fold is a plain ``pallas_call`` over per-shard arrays (no
collectives, no layout capture), it traces cleanly inside ``shard_map``
bodies — this is the kernel behind registry entry ``fold``.

All three monoids fold as masked VPU reduces over the one-hot block (an
MXU one-hot matmul would be faster for float adds but turns a single
non-finite message into NaN across every lane via inf*0, and truncates
int32 payloads above 2**24 through the f32 round trip).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_FOLD_TILE = 256
ENV_FOLD_TILE = "REPRO_FOLD_TILE"
# The one-hot combine materializes a [fold_tile, num_segments_padded]
# block per grid step, so compute and VMEM grow linearly in the segment
# count: 256 x 4096 x 4B = 4 MB keeps the block (plus the resident
# accumulator) inside a TPU core's ~16 MB VMEM.  Above the cap the
# FoldKernel wrapper (repro.kernels.ops) switches to the two-level
# blocked fold (repro.kernels.fold_two_level): per-bucket [q]-sized
# sub-accumulators whose VMEM footprint is bounded regardless of the
# segment count — still Pallas, still no segment/scatter ops.  The cap
# is therefore a *crossover point* between two Pallas lowerings, not a
# handoff to ref.
DEFAULT_FOLD_MAX_SEGMENTS = 4096
ENV_FOLD_MAX_SEGMENTS = "REPRO_FOLD_MAX_SEGMENTS"
_LANES = 128
_BLOCK = 1024


def _identity_val(monoid: str, dtype):
    """The monoid's identity as a numpy scalar of ``dtype``.  With
    :func:`to_signed` and :func:`from_signed` it is shared by both folds
    and :mod:`repro.kernels.fused_step`."""
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


def default_fold_tile() -> int:
    """Message-tile size for the blocked fold: the ``REPRO_FOLD_TILE``
    override if set, else the static default (autotune sweeps pass an
    explicit ``fold_tile`` instead)."""
    env = os.environ.get(ENV_FOLD_TILE)
    return int(env) if env else DEFAULT_FOLD_TILE


def max_fold_segments() -> int:
    """Largest segment count the *flat* blocked kernel will take on before
    the FoldKernel wrapper switches to the two-level blocked fold
    (``REPRO_FOLD_MAX_SEGMENTS`` overrides the static default)."""
    env = os.environ.get(ENV_FOLD_MAX_SEGMENTS)
    return int(env) if env else DEFAULT_FOLD_MAX_SEGMENTS


def dma_block(tile: int, n: int) -> int:
    """Messages per grid step for an ``n``-message stream: whole fold
    tiles, at least ``_BLOCK`` — or the whole stream when it is shorter.

    XLA lays a large 1-D array out in 1024-element tiles and Mosaic
    refuses a block that splits one, so the kernels fetch ``dma_block``
    messages per step and run the one-hot combine per ``tile`` of them.
    A block spanning the whole (padded) stream is always legal."""
    return min(tile * max(1, -(-_BLOCK // tile)),
               tile * max(1, -(-n // tile)))


def combine_tile(acc_ref, touched_ref, vals, onehot, *, monoid: str):
    """Fold one message tile into the resident ``[1, width]`` accumulator.

    ``onehot`` is the ``[T, width]`` message-to-column match.  Every
    monoid folds as a masked VPU reduce, NOT a one-hot MXU matmul: inf*0
    = NaN in a matmul would pollute every other segment's lane the moment
    one message diverges, where the ref fold confines it to its segment
    (and the f32 round trip truncates int32 payloads above 2**24)."""
    ident = _identity_val(monoid, acc_ref.dtype)
    if monoid == "add":
        masked = jnp.where(onehot, vals[:, None],
                           jnp.zeros((), acc_ref.dtype))
        acc_ref[...] = acc_ref[...] \
            + jnp.sum(masked, axis=0).astype(acc_ref.dtype)[None, :]
    elif monoid == "min":
        masked = jnp.where(onehot, vals[:, None], ident)
        acc_ref[...] = jnp.minimum(acc_ref[...],
                                   jnp.min(masked, axis=0)[None, :])
    elif monoid == "max":
        masked = jnp.where(onehot, vals[:, None], ident)
        acc_ref[...] = jnp.maximum(acc_ref[...],
                                   jnp.max(masked, axis=0)[None, :])
    touched_ref[...] = jnp.maximum(
        touched_ref[...], jnp.max(onehot.astype(jnp.int32), axis=0)[None, :])


def _kernel(vals_ref, ids_ref,                         # VMEM in (one block)
            acc_ref, touched_ref,                      # VMEM out (resident)
            *, monoid: str, nsp: int, tile: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref,
                                     _identity_val(monoid, acc_ref.dtype))
        touched_ref[...] = jnp.zeros_like(touched_ref)

    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, nsp), 1)
    for c in range(vals_ref.shape[0] // tile):
        part = pl.ds(c * tile, tile)
        # validity is folded into the ids (-1 matches no column): Mosaic
        # cannot relayout a bool [T] vector into a [T, 1] column
        combine_tile(acc_ref, touched_ref, vals_ref[part],
                     ids_ref[part][:, None] == cols, monoid=monoid)


@functools.partial(jax.jit, static_argnames=("num_segments", "monoid",
                                             "fold_tile", "interpret"))
def blocked_segment_fold(vals, valid, ids, num_segments: int, *,
                         monoid: str = "add", fold_tile: int = 256,
                         interpret: bool = True):
    """Segmented monoid fold of a message stream, blocked through VMEM.

    Args:
      vals:  [N] message value per slot.
      valid: [N] bool/int validity; invalid slots contribute nothing.
      ids:   [N] int32 segment id per slot.  Ids outside
             ``[0, num_segments)`` contribute nothing (the engines point
             sentinel slots at the overflow bin ``num_segments - 1``).
      num_segments: static segment count (engines pass ``nv + 1``).
      fold_tile: messages per one-hot combine (a grid step fetches
             :func:`dma_block` of them).
    Returns:
      acc [num_segments] monoid fold, touched [num_segments] bool.
    """
    n = vals.shape[0]
    blk = dma_block(fold_tile, n)
    nt = max(1, -(-n // blk))
    n_pad = nt * blk
    nsp = -(-num_segments // _LANES) * _LANES
    dtype = vals.dtype
    vals = to_signed(vals, monoid)
    ident = _identity_val(monoid, vals.dtype)
    ids = ids.astype(jnp.int32)
    ok = (valid > 0) & (ids >= 0) & (ids < num_segments)
    ids = jnp.pad(jnp.where(ok, ids, -1), (0, n_pad - n), constant_values=-1)
    vals = jnp.pad(vals, (0, n_pad - n), constant_values=ident)
    acc, touched = pl.pallas_call(
        functools.partial(_kernel, monoid=monoid, nsp=nsp, tile=fold_tile),
        grid=(nt,),
        in_specs=[pl.BlockSpec((blk,), lambda t: (t,)),
                  pl.BlockSpec((blk,), lambda t: (t,))],
        out_specs=[pl.BlockSpec((1, nsp), lambda t: (0, 0)),
                   pl.BlockSpec((1, nsp), lambda t: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, nsp), vals.dtype),
                   jax.ShapeDtypeStruct((1, nsp), jnp.int32)],
        interpret=interpret,
    )(vals, ids)
    return (from_signed(acc[0, :num_segments], monoid, dtype),
            touched[0, :num_segments] > 0)
