"""Two-level blocked segmented fold — the Gather phase past the VMEM cap.

The flat blocked fold (:mod:`repro.kernels.fold_block`) materializes a
``[fold_tile, num_segments_padded]`` one-hot block per grid step, so its
VMEM footprint grows linearly in the segment count and it stops being
lowerable past ``REPRO_FOLD_MAX_SEGMENTS``.  This kernel lifts that cap by
hierarchical accumulation ("Making Caches Work for Graph Analytics",
Zhang et al.): segment ids are split two-level into a *coarse bucket*
``id // q`` and an *offset within the bucket* ``id % q``:

  * the stream is first sorted by segment id (invalid slots last), so
    every message block (:func:`~repro.kernels.fold_block.dma_block`
    messages, one grid step) covers a contiguous bucket
    range and the (tile, bucket) pairs that hold any message number at
    most ``num_tiles + num_buckets``;
  * the grid is one axis over those pairs, in bucket order: the tile and
    the bucket of each step, whether it starts its bucket and whether it
    holds work come from scalar-prefetched (SMEM) arrays;
  * bucket ``b``'s ``[q]``-sized sub-accumulator is the output block,
    VMEM-resident over the consecutive steps of its bucket, and the
    one-hot combine is ``[fold_tile, q]`` — sized by the *bucket* width,
    not the segment count, so VMEM stays bounded for any
    ``num_segments``.  A bucket no message reaches gets one step that
    only initializes it.

Stage 2 — combining the per-bucket partials into the flat
``[num_segments]`` output — is a relayout, since buckets tile the segment
space disjointly.  No ``jax.ops.segment_*``, no scatter anywhere in the
lowering, so the kernel traces inside ``shard_map`` bodies just like the
flat fold (same registry contract, same monoids, same masked-VPU combine
— the MXU one-hot matmul stays off the table for the NaN/int-truncation
reasons documented in :mod:`repro.kernels.fold_block`).

``q`` need not divide the segment count, be a power of two, or be
lane-aligned (TPU-native callers should keep it a multiple of 128); the
bucket split uses real division, not a shift.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fold_block import (_identity_val, combine_tile, dma_block,
                         from_signed, to_signed)

# Bucket width of the two-level fold: how many consecutive segments one
# VMEM-resident sub-accumulator covers.  256 keeps the [fold_tile, q]
# one-hot block at flat-fold-default size (256 x 256 x 4B = 256 KB) while
# staying a lane multiple for the TPU path; the autotuner sweeps it
# jointly with fold_tile (Eq. 1's cost model predicts the interaction).
DEFAULT_FOLD_Q = 256
ENV_FOLD_Q = "REPRO_FOLD_Q"

#: SMEM bytes the scalar-prefetched work list (four int32 per grid step)
#: may take of a v5e core's 1 MiB
WORK_LIST_SMEM = 768 * 1024


def default_fold_q() -> int:
    """Bucket width for the two-level fold: the ``REPRO_FOLD_Q`` override
    if set, else the static default (autotune sweeps / layouts pass an
    explicit ``fold_q`` instead)."""
    env = os.environ.get(ENV_FOLD_Q)
    return int(env) if env else DEFAULT_FOLD_Q


def _kernel(wb_ref, wfirst_ref, wact_ref, wt_ref,      # SMEM work list
            vals_ref, ids_ref,                         # VMEM in (one block)
            acc_ref, touched_ref,                      # VMEM out (bucket)
            *, monoid: str, q: int, tile: int):
    w = pl.program_id(0)

    @pl.when(wfirst_ref[w] > 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref,
                                     _identity_val(monoid, acc_ref.dtype))
        touched_ref[...] = jnp.zeros_like(touched_ref)

    @pl.when(wact_ref[w] > 0)
    def _body():
        base = wb_ref[w] * q
        cols = jax.lax.broadcasted_iota(jnp.int32, (tile, q), 1)
        for c in range(vals_ref.shape[0] // tile):
            part = pl.ds(c * tile, tile)
            # offset inside bucket b; ids of other buckets and the
            # invalid sentinel fall outside [0, q) and match no column
            off = ids_ref[part] - base
            combine_tile(acc_ref, touched_ref, vals_ref[part],
                         off[:, None] == cols, monoid=monoid)


def bucket_work_list(ids_sorted, num_buckets: int, q: int, tile: int):
    """Static-length work list of a bucket-sorted, tile-padded id stream.

    Returns int32 arrays ``(bucket, first, active, tile)`` of length
    ``num_tiles + num_buckets``, in bucket order: one entry per (tile,
    bucket) pair that holds messages, one init-only entry per bucket no
    message reaches, and inactive padding at the end (parked on the last
    bucket, so no output block is ever revisited out of order).  Ids
    ``>= num_buckets * q`` are the invalid sentinel."""
    nb = num_buckets
    nt = ids_sorted.shape[0] // tile
    kb = ids_sorted.reshape(nt, tile) // q
    bmin = kb[:, 0]                       # sorted: first and last of a tile
    bmax = jnp.minimum(kb[:, -1], nb - 1)  # an all-invalid tile: bmin = nb
    b = jnp.arange(nb, dtype=jnp.int32)
    tlo = jnp.searchsorted(bmax, b, side="left").astype(jnp.int32)
    cnt = jnp.maximum(
        jnp.searchsorted(bmin, b, side="right").astype(jnp.int32) - tlo, 0)
    items = jnp.maximum(cnt, 1)
    start = jnp.cumsum(items) - items
    w = jnp.arange(nt + nb, dtype=jnp.int32)
    wb = jnp.clip(jnp.searchsorted(start, w, side="right") - 1,
                  0, nb - 1).astype(jnp.int32)
    j = w - start[wb]
    # inactive steps keep the previous tile index, so no block is fetched
    wt = jnp.clip(tlo[wb] + jnp.minimum(j, jnp.maximum(cnt[wb] - 1, 0)),
                  0, nt - 1)
    return (wb, (j == 0).astype(jnp.int32),
            (j < cnt[wb]).astype(jnp.int32), wt.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_segments", "monoid",
                                             "fold_tile", "fold_q",
                                             "interpret", "presorted"))
def two_level_segment_fold(vals, valid, ids, num_segments: int, *,
                           monoid: str = "add", fold_tile: int = 256,
                           fold_q: int = DEFAULT_FOLD_Q,
                           interpret: bool = True, presorted: bool = False):
    """Segmented monoid fold via per-bucket VMEM sub-accumulators.

    Same contract as :func:`repro.kernels.fold_block.blocked_segment_fold`
    (and registry kernel ``fold``):

      vals:  [N] message value per slot.
      valid: [N] bool/int validity; invalid slots contribute nothing.
      ids:   [N] int32 segment id per slot; ids outside
             ``[0, num_segments)`` contribute nothing (the engines point
             sentinel slots at the overflow bin ``num_segments - 1``).
      num_segments: static segment count (engines pass ``nv + 1``) — any
             size; VMEM use is bounded by ``fold_tile x fold_q``.
      fold_tile: messages per one-hot combine.
      fold_q: segments per coarse bucket (the sub-accumulator width).
      presorted: ``ids`` are already non-decreasing, whatever ``valid``
             says (a static stream in segment order, such as the DC
             stream sorted by destination once at layout time): the sort
             is skipped, the work list comes from ``ids`` as given, and
             invalid slots keep their place and match no column.
    Returns:
      acc [num_segments] monoid fold, touched [num_segments] bool.
    """
    n = vals.shape[0]
    q = int(fold_q)
    blk = dma_block(fold_tile, n)
    nb = max(1, -(-num_segments // q))
    # the work list lives in SMEM: at most ``chunk`` blocks per kernel
    # call, longer streams fold chunk by chunk
    chunk = WORK_LIST_SMEM // 16 - nb
    if chunk < 1:
        raise ValueError(f"{nb} buckets of {q} segments overflow the SMEM "
                         "work list; raise fold_q")
    nt = max(1, -(-n // blk))
    nc = -(-nt // chunk)
    nt = -(-nt // nc)                     # blocks per chunk
    n_pad = nc * nt * blk
    dtype = vals.dtype
    vals = to_signed(vals, monoid)
    ident = _identity_val(monoid, vals.dtype)
    sentinel = nb * q                     # sorts last, lands in no bucket
    ids = ids.astype(jnp.int32)
    ok = (valid > 0) & (ids >= 0) & (ids < num_segments)
    vals = jnp.pad(vals, (0, n_pad - n), constant_values=ident)
    if presorted:
        # the work list follows the stream's own order; an invalid slot
        # stays where it is with id -1, below every bucket's base
        order = jnp.pad(jnp.clip(ids, 0, sentinel), (0, n_pad - n),
                        constant_values=sentinel)
        ids = jnp.pad(jnp.where(ok, ids, -1), (0, n_pad - n),
                      constant_values=-1)
    else:
        ids = jnp.pad(jnp.where(ok, ids, sentinel), (0, n_pad - n),
                      constant_values=sentinel)
        # unstable: the fold does not care about order within a segment,
        # and a stable two-operand sort compiles ~5x slower on a TPU (90 s
        # at 2^25 messages, against 18 s)
        ids, vals = jax.lax.sort((ids, vals), num_keys=1, is_stable=False)
        order = ids

    tile_spec = pl.BlockSpec((blk,), lambda w, wb, wf, wa, wt: (wt[w],))
    acc_spec = pl.BlockSpec((None, 1, q),
                            lambda w, wb, wf, wa, wt: (wb[w], 0, 0))
    fold = pl.pallas_call(
        functools.partial(_kernel, monoid=monoid, q=q, tile=fold_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(nt + nb,),
            in_specs=[tile_spec, tile_spec],
            out_specs=[acc_spec, acc_spec]),
        out_shape=[jax.ShapeDtypeStruct((nb, 1, q), vals.dtype),
                   jax.ShapeDtypeStruct((nb, 1, q), jnp.int32)],
        interpret=interpret)

    def fold_chunk(order, ids, vals):
        return fold(*bucket_work_list(order, nb, q, blk), vals, ids)

    if nc == 1:
        acc, touched = fold_chunk(order, ids, vals)
    else:
        # each chunk of the sorted stream is sorted: fold one after
        # another and combine the partials
        combine = {"add": jnp.add, "min": jnp.minimum,
                   "max": jnp.maximum}[monoid]

        def step(carry, chunk):
            a, t = fold_chunk(*chunk)
            return (combine(carry[0], a), jnp.maximum(carry[1], t)), None

        init = (jnp.full((nb, 1, q), ident, vals.dtype),
                jnp.zeros((nb, 1, q), jnp.int32))
        (acc, touched), _ = jax.lax.scan(
            step, init, (order.reshape(nc, -1), ids.reshape(nc, -1),
                         vals.reshape(nc, -1)))
    # stage 2: buckets tile the segment space disjointly, so combining the
    # per-bucket partials into the flat output is a relayout, not a fold
    return (from_signed(acc.reshape(-1)[:num_segments], monoid, dtype),
            touched.reshape(-1)[:num_segments] > 0)
