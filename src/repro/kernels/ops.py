"""Engine-facing wrappers around the PPM kernels.

Two kernels, each with a Pallas lowering and a pure-jnp ``ref`` one of
the same call contract:

* ``fold`` — :class:`FoldKernel` (the blocked Pallas folds;
  ``interpret=True`` runs them on a CPU, ``interpret=False`` compiles
  them to Mosaic on a TPU) and :class:`RefFold`;
* ``fused_dc`` — the DC step of :mod:`repro.kernels.fused_step`, bound
  to a layout (:class:`FusedDCKernel`, :class:`RefFusedDC`) or fed a
  stream per call (:class:`FusedStreamKernel`, :class:`RefFusedStream`,
  the distributed engine's).

Engines do not pick between them directly: construct kernels through
:func:`repro.backend.registry.make_kernels` (or
:func:`~repro.backend.registry.resolve`), which resolves the backend
from the platform, the ``REPRO_KERNEL_BACKEND`` override, and
per-combination support.

Every layout-bound kernel keeps the layout arrays it reads in
:attr:`LayoutBound.arrays` and takes them back as an ``arrays=`` call
argument: the engines thread them into their jitted steps as arguments,
because jit embeds a closed-over array in the program as a constant.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import tracing as obs_tracing
from .fold_block import (blocked_segment_fold, default_fold_tile,
                         max_fold_segments)
from .fold_two_level import default_fold_q, two_level_segment_fold
from .fused_step import fused_scatter_fold, ref_fused_scatter_fold


class LayoutBound:
    """A kernel bound to one layout.

    :attr:`arrays` holds the device copies of the layout arrays the
    kernel reads, uploaded on first use, so a lowering the engine never
    calls costs no device memory.  ``__call__`` takes them back as
    ``arrays=`` (default: :attr:`arrays`); the engines pass them through
    their jitted steps as arguments — closed over, jit would embed every
    one of them in the compiled program as a constant."""

    def __init__(self, layout):
        self.L = layout
        self._arrays = None

    def host_arrays(self) -> dict:
        """The numpy arrays to upload (``None`` entries stay ``None``)."""
        raise NotImplementedError

    @property
    def arrays(self) -> dict:
        if self._arrays is None:
            self._arrays = {k: None if v is None else jnp.asarray(v)
                            for k, v in self.host_arrays().items()}
        return self._arrays

    def _args(self, arrays):
        return self.arrays if arrays is None else arrays


class FoldKernel:
    """Blocked Pallas segmented fold with the registry's ``fold`` contract.

    Layout-free (the segment count arrives per call): the distributed
    engine folds each device's received bin column under ``shard_map``,
    and the single-device engine folds the compacted SC stream.  The
    message-tile size comes from the tuning sweep (``tile=``), the
    ``REPRO_FOLD_TILE`` override, or the static default, in that order;
    the two-level bucket width resolves the same way (``q=`` /
    ``REPRO_FOLD_Q`` / static default).

    Segment-count regimes — both are Pallas lowerings:

      * ``num_segments <= REPRO_FOLD_MAX_SEGMENTS``: the flat blocked
        fold (one VMEM-resident ``[num_segments_padded]`` accumulator,
        :mod:`repro.kernels.fold_block`);
      * above the cap: the two-level blocked fold (per-bucket ``[q]``
        sub-accumulators, :mod:`repro.kernels.fold_two_level`), whose
        VMEM footprint is bounded by ``fold_tile x q`` for any segment
        count.

    The ref fold no longer rides along as a silent large-``num_segments``
    cliff; ``RefFold`` is what the explicit ``ref`` backend constructs.
    """

    def __init__(self, monoid_name: str, dtype, interpret: bool = True,
                 tile=None, q=None):
        self.monoid = monoid_name
        self.dtype = jnp.dtype(dtype)
        self.interpret = interpret
        self.tile = tile
        self.q = q

    def __call__(self, vals, valid, ids, num_segments):
        ns = int(num_segments)
        tile = int(self.tile) if self.tile else default_fold_tile()
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fold")):
            if ns > max_fold_segments():
                # the flat one-hot block would outgrow VMEM: fold through
                # the per-bucket sub-accumulators instead (still Pallas,
                # still no segment/scatter ops in the lowering)
                q = int(self.q) if self.q else default_fold_q()
                return two_level_segment_fold(
                    vals, valid, ids, ns, monoid=self.monoid,
                    fold_tile=tile, fold_q=q, interpret=self.interpret)
            return blocked_segment_fold(
                vals, valid, ids, ns, monoid=self.monoid,
                fold_tile=tile, interpret=self.interpret)


class RefFold:
    """Pure-jnp segmented fold with FoldKernel's exact call contract.

    Tightened over a bare ``Monoid.segment_fold``: invalid slots are
    masked to the identity *inside* the fold (callers need not pre-mask)
    and ``touched`` reports exactly the segments a valid message reached —
    the same semantics the blocked kernel realizes with its one-hot mask.
    """

    def __init__(self, monoid):
        self.monoid = monoid

    def __call__(self, vals, valid, ids, num_segments):
        mono = self.monoid
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fold.ref")):
            valid = valid.astype(bool)
            vals = jnp.where(valid, vals.astype(mono.dtype), mono.identity)
            acc = mono.segment_fold(vals, ids, num_segments)
            touched = jax.ops.segment_max(valid.astype(jnp.int32), ids,
                                          num_segments=num_segments) > 0
            return acc, touched


#: elements of one ``[lanes, edges]`` stream in a batched ref fold: lanes
#: fold in chunks of at most this many, one chunk after another, so a
#: batch never holds ``B`` edge-sized streams at once (8 lanes of a
#: 2^27-edge graph would need 4 GiB per stream)
LANE_CHUNK_ELEMS = 1 << 27


def _fold_lanes_flat(mono, lanes, xs, ns, width):
    """Fold a batch of lanes through one flattened ``lane * ns + id``
    segment space per chunk of lanes.  ``xs`` are the per-lane inputs,
    each ``[B, ...]``; ``lanes(*chunk)`` turns a chunk of them into the
    ``(vals, valid, ids)`` streams, each ``[lanes, width]`` (``ids`` may
    broadcast over lanes).  A chunk's flattened ids stay inside int32
    (segment ops silently drop out-of-range ids, and int64 is unavailable
    without x64), and its streams hold at most :data:`LANE_CHUNK_ELEMS`
    elements; several chunks run in a ``lax.map``, which reuses one
    chunk's memory for the next."""
    B = xs[0].shape[0]
    per = max(1, min((2**31 - 1) // ns, LANE_CHUNK_ELEMS // max(width, 1)))

    def fold(chunk):
        vals, valid, ids = lanes(*chunk)
        bc = vals.shape[0]
        fids = (jnp.arange(bc, dtype=jnp.int32)[:, None] * ns
                + jnp.broadcast_to(ids, (bc, width))).reshape(-1)
        acc = mono.segment_fold(vals.reshape(-1), fids, bc * ns)
        touched = jax.ops.segment_max(valid.astype(jnp.int32).reshape(-1),
                                      fids, num_segments=bc * ns) > 0
        return acc.reshape(bc, ns), touched.reshape(bc, ns)

    if per >= B:
        return fold(xs)
    nc = -(-B // per)
    chunks = [jnp.concatenate([x, jnp.broadcast_to(
        x[:1], (nc * per - B,) + x.shape[1:])]).reshape(
            (nc, per) + x.shape[1:]) for x in xs]
    acc, touched = jax.lax.map(fold, chunks)
    return (acc.reshape(nc * per, ns)[:B],
            touched.reshape(nc * per, ns)[:B])


def _edge_src_global(layout) -> np.ndarray:
    """Per-edge *global* source vertex of the gather-order edge stream.

    Every edge tile lies inside one ``(p', p)`` block, so the tile's
    source partition base plus the per-edge local offset recovers the
    global id — the static index the fused kernel gathers the message
    table with (clamped into the sentinel for pad tiles)."""
    base = np.repeat(layout.tile_src_part.astype(np.int64),
                     layout.edge_tile) * layout.q
    src = base + layout.edge_src_local.astype(np.int64)
    return np.clip(src, 0, layout.n_pad).astype(np.int32)


def _dst_order(layout) -> np.ndarray:
    """Permutation putting the gather-order edge stream in destination
    order (ties in stream order; pad edges, whose destination is the
    sentinel ``n_pad``, last), computed once per layout.  One sort of
    packed ``dst << 32 | position`` keys: a stable argsort of the same
    2^27 int32 keys takes ~8x longer in numpy."""
    order = layout.__dict__.get("_dst_order")
    if order is None:
        key = layout.edge_dst.astype(np.int64) << 32
        key |= np.arange(len(key), dtype=np.int64)
        key.sort()
        order = layout.__dict__["_dst_order"] = key & 0xFFFFFFFF
    return order


class FusedDCKernel(LayoutBound):
    """Fused DC step bound to a layout (registry ``fused_dc``).

    The engine's DC stream: each edge's source message is gathered
    straight from the ``[n_pad + 1]`` vertex table (identity sentinel
    last) and folded into the two-level ``[fold_q]`` sub-accumulators —
    no ``[NM]`` bin buffer (see :mod:`repro.kernels.fused_step`).  The
    static edge stream is kept in destination order, sorted once here,
    so the fold runs without a per-call sort.  A call returns ``(acc, touched,
    one_gather)``, the last whether the step gathered each edge once (see
    :func:`~repro.kernels.fused_step.fused_scatter_fold`).

    ``apply_weight`` is engine-configured (the registry does not see the
    program): :class:`repro.core.engine.Engine` sets the attribute once,
    before the step is traced, where the program has an edge function
    and the layout has weights.

    Under ``vmap`` (the batched engine path) the lanes run one after
    another through the unbatched step, so a batch holds one lane's edge
    streams at a time.
    """

    def __init__(self, layout, monoid_name: str, dtype,
                 interpret: bool = True):
        super().__init__(layout)
        self.monoid = monoid_name
        self.dtype = jnp.dtype(dtype)
        self.interpret = interpret
        self.n_pad = layout.n_pad
        self.fold_tile = layout.fold_tile
        self.fold_q = layout.fold_q
        self.apply_weight = None               # engine-configured
        call = jax.custom_batching.custom_vmap(self._single)
        call.def_vmap(self._vmap_rule)
        self._call = call

    def host_arrays(self):
        L = self.L
        order = _dst_order(L)
        return {"edge_src": _edge_src_global(L)[order],
                "edge_valid": L.edge_valid[order].astype(np.int32),
                "edge_dst": L.edge_dst[order],
                "edge_w": None if L.edge_w is None else L.edge_w[order]}

    def __call__(self, table, table_valid, arrays=None):
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fused_dc")):
            return self._call(table, table_valid, self._args(arrays))

    def _single(self, table, table_valid, A):
        aw = self.apply_weight
        return fused_scatter_fold(
            table, table_valid, A["edge_src"], A["edge_valid"],
            A["edge_dst"], self.n_pad + 1, monoid=self.monoid,
            edge_tile=self.fold_tile, fold_q=self.fold_q,
            interpret=self.interpret, apply_weight=aw,
            w=A["edge_w"] if aw is not None else None, presorted=True)

    def _vmap_rule(self, axis_size, in_batched, table, table_valid, A):
        tb, tvb, a_b = in_batched
        assert not any(jax.tree_util.tree_leaves(a_b)), \
            "layout arrays are shared by every lane"
        if not tb:
            table = jnp.broadcast_to(table, (axis_size,) + table.shape)
        if not tvb:
            table_valid = jnp.broadcast_to(
                table_valid, (axis_size,) + table_valid.shape)
        out = jax.lax.map(lambda lane: self._single(*lane, A),
                          (table, table_valid))
        return out, (True, True, True)


class RefFusedDC(LayoutBound):
    """Pure-jnp fused DC step with FusedDCKernel's exact call contract:
    one gather and one segmented fold.  It gathers value and validity
    apart on every call, so its ``one_gather`` is always false.

    The call carries a ``custom_vmap`` rule (the batched multi-source
    engine path): the table gather batches fine, but XLA's default
    scatter batching rule serializes the segment fold catastrophically
    on CPU (~100x), so batched lanes fold through a flattened
    ``lane * ns + dst`` segment space instead (:func:`_fold_lanes_flat`)
    — per-lane cost identical to the sequential fold, so batching only
    ever amortizes dispatch.
    """

    def __init__(self, layout, monoid):
        super().__init__(layout)
        self.monoid = monoid
        self.n_pad = layout.n_pad
        self.apply_weight = None               # engine-configured
        call = jax.custom_batching.custom_vmap(self._single)
        call.def_vmap(self._vmap_rule)
        self._call = call

    def host_arrays(self):
        L = self.L
        return {"edge_src": _edge_src_global(L), "edge_valid": L.edge_valid,
                "edge_dst": L.edge_dst, "edge_w": L.edge_w}

    def __call__(self, table, table_valid, arrays=None):
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fused_dc.ref")):
            return self._call(table, table_valid, self._args(arrays))

    def _single(self, table, table_valid, A):
        aw = self.apply_weight
        acc, touched = ref_fused_scatter_fold(
            self.monoid, table, table_valid, A["edge_src"],
            A["edge_valid"], A["edge_dst"], self.n_pad + 1,
            apply_weight=aw, w=A["edge_w"] if aw is not None else None)
        return acc, touched, jnp.bool_(False)

    def _vmap_rule(self, axis_size, in_batched, table, table_valid, A):
        tb, tvb, a_b = in_batched
        assert not any(jax.tree_util.tree_leaves(a_b)), \
            "layout arrays are shared by every lane"
        if not tb:
            table = jnp.broadcast_to(table, (axis_size,) + table.shape)
        if not tvb:
            table_valid = jnp.broadcast_to(
                table_valid, (axis_size,) + table_valid.shape)
        mono = self.monoid
        ns, src = self.n_pad + 1, A["edge_src"]

        def lanes(table, table_valid):
            vals = jnp.take(table, src, axis=1).astype(mono.dtype)
            valid = (jnp.take(table_valid.astype(bool), src, axis=1)
                     & A["edge_valid"][None, :])
            if self.apply_weight is not None:
                vals = self.apply_weight(
                    vals, A["edge_w"][None, :]).astype(mono.dtype)
            vals = jnp.where(valid, vals, mono.identity)
            return vals, valid, jnp.where(valid, A["edge_dst"][None, :],
                                          ns - 1)

        acc, touched = _fold_lanes_flat(mono, lanes, (table, table_valid),
                                        ns, src.shape[0])
        return ((acc, touched, jnp.zeros((axis_size,), jnp.bool_)),
                (True, True, True))


class FusedStreamKernel:
    """Layout-free fused gather→fold with the stream ``fused_dc`` contract.

    What :class:`FoldKernel` is to the fold, this is to the fused step:
    the distributed engine's gather side has no tile/partition structure
    on the receive table (``rv[slot]``), so the kernel takes the table,
    the slot indices and the static validity per call: an XLA slot
    gather, the edge function, then the two-level Pallas fold, which
    skips its sort when the caller passes ``presorted=True`` (a stream
    in destination order, as ``shard_layout`` keeps it).  Returns
    ``(acc, touched, one_gather)``, as :class:`FusedDCKernel` does.
    """

    def __init__(self, monoid_name: str, dtype, interpret: bool = True,
                 tile=None, q=None):
        self.monoid = monoid_name
        self.dtype = jnp.dtype(dtype)
        self.interpret = interpret
        self.tile = tile
        self.q = q

    def __call__(self, table, table_valid, idx, edge_valid, dst,
                 num_segments, w=None, apply_weight=None,
                 presorted: bool = False):
        tile = int(self.tile) if self.tile else default_fold_tile()
        q = int(self.q) if self.q else default_fold_q()
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fused_dc")):
            return fused_scatter_fold(
                table, table_valid, idx, edge_valid, dst,
                int(num_segments), monoid=self.monoid, edge_tile=tile,
                fold_q=q, interpret=self.interpret,
                apply_weight=apply_weight, w=w, presorted=presorted)


class RefFusedStream:
    """Pure-jnp stream fused step with FusedStreamKernel's call contract
    (``one_gather`` always false)."""

    def __init__(self, monoid):
        self.monoid = monoid

    def __call__(self, table, table_valid, idx, edge_valid, dst,
                 num_segments, w=None, apply_weight=None,
                 presorted: bool = False):
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fused_dc.ref")):
            acc, touched = ref_fused_scatter_fold(
                self.monoid, table, table_valid, idx, edge_valid, dst,
                int(num_segments), apply_weight=apply_weight, w=w)
        return acc, touched, jnp.bool_(False)


__all__ = ["LayoutBound", "FoldKernel", "RefFold", "FusedDCKernel",
           "RefFusedDC", "FusedStreamKernel", "RefFusedStream",
           "blocked_segment_fold", "two_level_segment_fold",
           "fused_scatter_fold", "ref_fused_scatter_fold"]
