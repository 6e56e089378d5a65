"""Fused DC step — the Gather phase without the bin buffer.

The composed DC lowering materializes every message twice: the Scatter
kernel writes the full ``[NM]`` bin buffer (values only, the paper's
pre-written ``dc_bin``), the slot gather re-reads it into a ``[NE]``
edge-value stream, and only then does the segmented fold collapse it into
the per-partition accumulators.

This step goes straight from the vertex-message table to the fold: XLA
gathers each edge's source value out of the ``[n_pad + 1]`` table
(identity sentinel last), the optional edge function (``apply_weight``)
runs on the gathered values, and the two-level Pallas fold
(:mod:`repro.kernels.fold_two_level`) folds them into the ``[fold_q]``
bucket sub-accumulators.  No ``[NM]`` buffer and no XLA scatter: on a
TPU the fold is the Mosaic kernel, and the only random access left is
the gather.

One gather per edge, not two.  The step gathers each edge's value from
the table with every invalid slot overwritten by the monoid identity,
and reads the edge's validity out of that value: an edge counts iff its
gathered bits differ from the identity's.  That holds unless a valid
slot itself holds the identity's bits, which an ``O(M)`` guard over the
table decides on the device; then a ``lax.cond`` gathers the validity
too.  Both paths give the same answer bit for bit, and the step returns
which one ran.  (BFS/CC send vertex ids, never ``0xFFFFFFFF``; the
engines mark vertices without out-edges invalid, so PageRank's ``0.0``
from them does not trip the guard.)

Mosaic lowers no vector gather from a table of this size inside a kernel
("Only 2D gather is supported"), which is why the gather stays in XLA.

A stream already in destination order (``presorted=True``: the
layout-bound kernel sorts its static DC stream by destination once) folds
without the fold's per-call sort.

Env: ``REPRO_FUSED=0`` opts the engines out of fused selection entirely
(they silently fall back to the composed scatter→fold path, which also
remains the path for SC/hybrid streams and unsupported backends).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .fold_block import default_fold_tile
from .fold_two_level import default_fold_q, two_level_segment_fold
from .segment_combine import _identity_val

ENV_FUSED = "REPRO_FUSED"


def fused_enabled() -> bool:
    """Engine-side opt-out: ``REPRO_FUSED=0`` disables fused DC selection
    (the composed scatter→fold path runs instead).  Default: enabled."""
    return os.environ.get(ENV_FUSED, "1") != "0"


def _bits(x):
    """``x``'s bit patterns as unsigned integers of the same width."""
    return jax.lax.bitcast_convert_type(
        x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


@functools.partial(jax.jit, static_argnames=("num_segments", "monoid",
                                             "edge_tile", "fold_q",
                                             "interpret", "apply_weight",
                                             "presorted"))
def fused_scatter_fold(table, table_valid, idx, edge_valid, dst,
                       num_segments: int, *, monoid: str = "add",
                       edge_tile: int = 256,
                       fold_q: int = None,
                       interpret: bool = True,
                       apply_weight=None, w=None, presorted: bool = False):
    """Gather-from-table + edge function + two-level segmented fold.

    Contract (registry kernel ``fused_dc``):

      table:       [M] 4-byte source value per table slot (the engines
                   pass the vertex message array + identity sentinel).
      table_valid: [M] bool/int; a slot's messages contribute nothing
                   when its source is invalid (inactive / non-DC).
      idx:         [NE] int32 table slot per edge (clamped into range;
                   out-of-range only ever occurs on invalid pad edges).
      edge_valid:  [NE] bool/int static structural validity per edge.
      dst:         [NE] int32 destination segment per edge; ids outside
                   ``[0, num_segments)`` contribute nothing.
      num_segments: static segment count (engines pass ``n_pad + 1`` /
                   ``nv + 1``; the overflow bin is the last segment).
      edge_tile:   messages per one-hot combine of the fold.
      apply_weight: optional static edge function ``f(vals, w)`` applied
                   to the gathered values (the composed path applies it
                   to the same inputs elementwise, so parity is exact).
      w:           [NE] edge weights; required iff apply_weight is set.
      presorted:   ``dst`` is non-decreasing (see
                   :func:`~repro.kernels.fold_two_level.two_level_segment_fold`).
    Returns:
      acc [num_segments] monoid fold, touched [num_segments] bool —
      an edge contributes iff ``table_valid[idx] & edge_valid`` — and
      one_gather, a bool scalar: no valid slot held the identity's bits,
      so the step read each edge's validity from its gathered value
      (before the edge function); otherwise it gathered the validity
      as well.
    """
    idx = jnp.clip(idx.astype(jnp.int32), 0, table.shape[0] - 1)
    table_valid = table_valid.astype(bool)
    ident = jnp.asarray(_identity_val(monoid, table.dtype))
    one_gather = ~jnp.any(table_valid & (_bits(table) == _bits(ident)))

    # an invalid slot's value is the identity, which the fold ignores
    vals = jnp.where(table_valid, table, ident)[idx]
    # the value gather stays outside the cond: XLA hoists a gather that
    # ends both branches out of a cond, and the one-gather branch would
    # then gather the values a second time for their validity
    valid = jax.lax.cond(one_gather,
                         lambda: _bits(vals) != _bits(ident),
                         lambda: table_valid[idx])
    valid = valid & (edge_valid > 0)
    if apply_weight is not None:
        vals = apply_weight(vals, w).astype(table.dtype)
    acc, touched = two_level_segment_fold(
        vals, valid, dst, int(num_segments), monoid=monoid,
        fold_tile=int(edge_tile) if edge_tile else default_fold_tile(),
        fold_q=int(fold_q) if fold_q else default_fold_q(),
        interpret=interpret, presorted=presorted)
    return acc, touched, one_gather


def ref_fused_scatter_fold(mono, table, table_valid, idx, edge_valid, dst,
                           num_segments: int, apply_weight=None, w=None):
    """Pure-jnp oracle with :func:`fused_scatter_fold`'s exact contract —
    what the ``ref`` backend registers for kernel ``fused_dc`` (and what
    the differential harness checks the Pallas lowering against)."""
    ns = int(num_segments)
    idx = jnp.clip(idx.astype(jnp.int32), 0, table.shape[0] - 1)
    vals = table[idx].astype(mono.dtype)
    valid = table_valid.astype(bool)[idx] & edge_valid.astype(bool)
    if apply_weight is not None:
        vals = apply_weight(vals, w).astype(mono.dtype)
    vals = jnp.where(valid, vals, mono.identity)
    ids = jnp.where(valid, dst.astype(jnp.int32), ns - 1)
    acc = mono.segment_fold(vals, ids, ns)
    touched = jax.ops.segment_max(valid.astype(jnp.int32), ids,
                                  num_segments=ns) > 0
    return acc, touched
