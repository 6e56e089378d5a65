"""Kernel-backend registry: one dispatch point for every PPM kernel call.

The paper's §6 evaluation pins GPOP's win on matching the blocking geometry
to the actual memory hierarchy: partitions sized so one partition's vertex
data lives in the private (L2) cache, bins streamed sequentially through
DRAM.  This package is the reproduction's analogue of that hardware match,
as a *backend* choice instead of a compile-time constant:

  ``ref``              pure ``jax.ops`` segment folds.  XLA:CPU fuses these
                       into the cache-friendly loops the paper's handwritten
                       OpenMP code realizes by construction — the right
                       default on CPU hosts, and the semantic oracle
                       everywhere (paper §6.1's "preprocessed once, verified
                       against a reference" discipline).
  ``pallas-interpret`` the Pallas kernel bodies executed by the interpreter.
                       Bit-level identical control flow to the TPU kernels,
                       ~100x slower than ``ref`` — a validation target, not a
                       performance point (the paper's single-thread sanity
                       runs play the same role).
  ``pallas-native``    Mosaic-compiled kernels (``interpret=False``) on TPU.
                       The paper's cache story transposed to VMEM: one
                       partition's ``q`` vertices stay VMEM-resident across
                       its bin column while edge tiles stream from HBM, so
                       DRAM→HBM and LLC→VMEM take the roles §6.2 measures.

Backends register per ``(platform, kernel, monoid, dtype)`` support;
:func:`repro.backend.registry.resolve` picks one from
``jax.default_backend()``, honours the ``REPRO_KERNEL_BACKEND`` override,
and falls back to ``ref`` per call when a lowering is unsupported (with a
warning on a TPU, where :data:`~repro.backend.registry.TPU_DEFAULTS`
declares each kernel's lowering — Mosaic for both ``fold`` and
``fused_dc`` — and :data:`~repro.backend.registry.TPU_REF_MONOIDS` sends
the packed uint64 monoid to ``ref`` for every kernel).  Tile
geometry (``edge_tile``/``msg_tile``/``fold_tile`` — the §3.1
partition-sizing rule) is swept empirically by
:mod:`repro.backend.tuning` and cached on disk.

Kernel ``fold`` — the blocked segmented fold
--------------------------------------------

``resolve("fold", monoid).segment_fold(monoid, tile=None, q=None)``
returns a callable with the contract::

    acc, touched = fold(vals, valid, ids, num_segments)

    vals  [N]   message value per slot (any 4-byte add/min/max dtype)
    valid [N]   bool/int; invalid slots contribute nothing
    ids   [N]   int32 segment per slot; ids outside [0, num_segments)
                contribute nothing (engines park sentinels in the
                overflow bin num_segments - 1)
    acc     [num_segments]  monoid fold (identity where untouched)
    touched [num_segments]  bool, True iff a valid message landed there

It is the Gather phase as a *stream* kernel: no layout binding, no
``jax.ops.segment_*``, no scatter in the lowering, so it traces inside
``shard_map`` bodies and is what ``DistEngine`` folds each device's
received bin column with (and the single-device engine its compacted SC
stream).  Unlike ``fused_dc`` it defaults to Pallas on all
platforms (``pallas-native`` on TPU, ``pallas-interpret`` elsewhere);
``REPRO_KERNEL_BACKEND=ref`` opts out.

The message-tile knob — how many stream slots one grid step folds from
VMEM — resolves in order: the ``tile=`` argument (engines pass the
layout's tuned ``fold_tile``), the ``REPRO_FOLD_TILE`` environment
variable, then the static default
(:data:`repro.kernels.fold_block.DEFAULT_FOLD_TILE`).  ``autotune()``
sweeps it jointly with ``edge_tile``/``msg_tile``.

The flat blocked combine keeps the whole ``[num_segments]`` accumulator
VMEM-resident, so past ``REPRO_FOLD_MAX_SEGMENTS`` segments (default
4096 — the point where one grid step's one-hot block outgrows a TPU
core's VMEM) the kernel switches to the *two-level* blocked fold
(:mod:`repro.kernels.fold_two_level`): segments are grouped into coarse
buckets of ``q`` (the ``q=`` argument — engines pass the layout's tuned
``fold_q`` — then ``REPRO_FOLD_Q``, then
:data:`repro.kernels.fold_two_level.DEFAULT_FOLD_Q`), each bucket's
``[q]``-sized sub-accumulator folds VMEM-resident over the message
stream with a ``[fold_tile, q]`` one-hot.  The stream is sorted by
segment first, so the grid walks only the (message block, bucket) pairs
that hold messages — at most blocks + buckets steps.  Both regimes are
Pallas lowerings with identical semantics; there is no silent handoff
to ``ref`` at any segment count — ``REPRO_KERNEL_BACKEND=ref`` is the
only way to get the ``jax.ops`` fold.  ``autotune()`` sweeps ``fold_q``
jointly with ``fold_tile`` via the over-cap ``fold2`` timing row.

Kernel ``fused_dc`` — the DC step
---------------------------------

The engines' one DC lowering (:mod:`repro.kernels.fused_step`): XLA
gathers each edge's source value straight from the message table, the
optional edge function runs on it, and the two-level Pallas fold folds
the stream into the ``[fold_q]`` sub-accumulators — no ``[NM]`` bin
buffer, no XLA scatter.  The layout-bound kernel keeps its static edge
stream in destination order, so its fold needs no per-call sort;
Mosaic lowers it, and it is the TPU default
(:data:`~repro.backend.registry.TPU_DEFAULTS`).  Its stream contract
mirrors the fold's::

    acc, touched, one_gather = fused(table, table_valid, idx, edge_valid,
                                     dst, num_segments[, w=,
                                     apply_weight=])

with an edge contributing iff ``table_valid[idx] & edge_valid``.  It
resolves like ``fold``: the Pallas backends lower {add,min,max} × 4-byte
f/i/u, ``ref`` every monoid, and any other combination falls back to
``ref`` per call (the platform default off a TPU is ``ref``).
``autotune()`` observes the fused grid's ``edge_tile × fold_q``
cross-product through the ``fused`` timing row, and the winners ride
the same cached :class:`~repro.backend.tuning.TileGeometry` the
layouts are built from.

Telemetry
---------

:func:`make_kernels` tags every kernel object it hands out with an
``_obs_scope`` of the form ``ppm.<kernel>.<backend>`` (e.g.
``ppm.fold.pallas-interpret``) — the tag is set on the object itself,
never a wrapper, so geometry introspection like ``kset.fold.q`` keeps
working.  Each kernel ``__call__`` enters that scope via
``repro.obs.tracing.kernel_scope`` (a ``jax.named_scope``: trace-time
metadata only, zero retraces and zero runtime cost), so ``jax.profiler``
captures — ``repro.obs.trace(path)`` starts one — attribute device time
to *which kernel under which backend*, the attribution the registry's
per-call ``ref`` fallback would otherwise blur.  ``REPRO_OBS=0``
degrades the scope to a ``nullcontext``.  See :mod:`repro.obs`.
"""
from __future__ import annotations

from .registry import (BACKENDS, TPU_DEFAULTS, TPU_REF_MONOIDS,
                       KernelBackend,
                       available_backends, default_backend_name,
                       make_kernels, resolve, supported)
from .tuning import (DEFAULT_GEOMETRY, TileGeometry, autotune,
                     resolve_geometry, tuned_layout)

__all__ = [
    "BACKENDS", "TPU_DEFAULTS", "TPU_REF_MONOIDS", "KernelBackend",
    "available_backends",
    "default_backend_name", "make_kernels", "resolve", "supported",
    "DEFAULT_GEOMETRY", "TileGeometry", "autotune", "resolve_geometry",
    "tuned_layout",
]
