"""Layout-bound jit wrappers around the PPM kernels.

``GatherKernel`` / ``ScatterKernel`` / ``SpmvKernel`` bind a
:class:`repro.graph.layout.Layout` once (moving the static bin-grid geometry
to device) and expose the engine-facing API over the Pallas bodies
(``interpret=True`` runs them on CPU for validation; ``interpret=False``
compiles to Mosaic on TPU).  ``RefGather`` / ``RefScatter`` / ``RefSpmv``
are the pure-jnp implementations of the *same* engine-facing API, built on
:mod:`repro.kernels.ref` — the semantic oracle and the fast CPU path.

Engines do not pick between them directly: construct kernels through
:func:`repro.backend.registry.make_kernels` (or the :func:`make_kernels`
convenience re-export below), which resolves the backend from the platform,
the ``REPRO_KERNEL_BACKEND`` override, and per-combination support.

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
from . import ref as kref
from .dc_gather import dc_gather
from .fold_block import (blocked_segment_fold, default_fold_tile,
                         max_fold_segments)
from .fold_two_level import default_fold_q, two_level_segment_fold
from .fused_step import (fused_enabled, fused_scatter_fold,
                         ref_fused_scatter_fold)
from .segment_combine import segment_combine, _identity_val
from .spmv_block import spmv_block


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


class GatherKernel(LayoutBound):
    """Gather-phase fold bound to a layout (acc + touched over [n_pad])."""

    def __init__(self, layout, monoid_name: str, dtype,
                 interpret: bool = True):
        super().__init__(layout)
        self.monoid = monoid_name
        self.dtype = jnp.dtype(dtype)
        self.interpret = interpret
        self.ident = _identity_val(monoid_name, self.dtype)

    def host_arrays(self):
        L = self.L
        return {"tile_dst_part": L.tile_dst_part,
                "tile_src_part": L.tile_src_part,
                "tile_first": L.tile_first.astype(np.int32),
                "edge_dst_local": L.edge_dst_local,
                "has_tiles": L.part_has_tiles.astype(np.int32)[:, None]}

    def __call__(self, edge_vals, edge_valid, part_active, arrays=None):
        L, A = self.L, self._args(arrays)
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.gather")):
            acc, touched = segment_combine(
                edge_vals, edge_valid, A["edge_dst_local"],
                A["tile_dst_part"], A["tile_src_part"], A["tile_first"],
                part_active, k=L.k, q=L.q, edge_tile=L.edge_tile,
                monoid=self.monoid, interpret=self.interpret)
            # destination partitions with no incoming tiles were never
            # visited
            acc = jnp.where(A["has_tiles"] > 0, acc, self.ident)
            touched = jnp.where(A["has_tiles"] > 0, touched, 0)
            return acc.reshape(-1), touched.reshape(-1) > 0


class ScatterKernel(LayoutBound):
    """DC scatter-phase message materialization bound to a layout."""

    def __init__(self, layout, monoid_name: str, dtype,
                 interpret: bool = True):
        super().__init__(layout)
        self.monoid = monoid_name
        self.dtype = jnp.dtype(dtype)
        self.interpret = interpret

    def host_arrays(self):
        L = self.L
        return {"png_src_local": L.png_src_local,
                "png_valid": (L.png_src < L.n_pad).astype(np.int32),
                "png_tile_part": L.png_tile_part}

    def __call__(self, x_flat, active_flat, arrays=None):
        L, A = self.L, self._args(arrays)
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.scatter")):
            return dc_gather(
                x_flat.reshape(L.k, L.q),
                active_flat.astype(jnp.int32).reshape(L.k, L.q),
                A["png_src_local"], A["png_valid"], A["png_tile_part"],
                k=L.k, q=L.q, msg_tile=L.msg_tile, monoid=self.monoid,
                interpret=self.interpret)


class SpmvKernel(LayoutBound):
    """Fused partition-centric SpMV bound to a layout (PageRank DC loop)."""

    def __init__(self, layout, interpret: bool = True, weighted=None):
        super().__init__(layout)
        self.interpret = interpret
        self.weighted = ((layout.weighted if weighted is None else weighted)
                         and layout.edge_w is not None)

    def host_arrays(self):
        L = self.L
        return {"edge_src_local": L.edge_src_local,
                "edge_dst_local": L.edge_dst_local,
                "edge_valid": L.edge_valid.astype(np.int32),
                "edge_w": L.edge_w if self.weighted else None,
                "tile_dst_part": L.tile_dst_part,
                "tile_src_part": L.tile_src_part,
                "tile_first": L.tile_first.astype(np.int32),
                "has_tiles": L.part_has_tiles.astype(np.int32)[:, None]}

    def __call__(self, x_flat, arrays=None):
        L, A = self.L, self._args(arrays)
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.spmv")):
            y = spmv_block(
                x_flat.reshape(L.k, L.q), A["edge_src_local"],
                A["edge_dst_local"], A["edge_valid"], A["edge_w"],
                A["tile_dst_part"], A["tile_src_part"], A["tile_first"],
                k=L.k, q=L.q, edge_tile=L.edge_tile,
                weighted=self.weighted, interpret=self.interpret)
            return jnp.where(A["has_tiles"] > 0, y, 0.0).reshape(-1)


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


class RefGather(LayoutBound):
    """Pure-jnp gather fold with GatherKernel's exact call contract.

    Unlike the raw :func:`repro.kernels.ref.segment_combine_ref` oracle it
    also applies the 2-level active list (tiles of inactive source
    partitions contribute nothing) and masks invalid slots to the monoid
    identity, so it is interchangeable with the Pallas kernels under the
    engine and under parity tests.

    The call carries a ``custom_vmap`` rule: under a leading query axis
    (the batched multi-source engine path) XLA's default scatter batching
    rule serializes catastrophically on CPU (~100x), so the batched fold
    instead runs the *unbatched* segment ops over a flattened
    ``lane * (n_pad+1) + dst`` segment space — per-lane cost identical to
    the sequential fold, so batching only ever amortizes dispatch.
    """

    def __init__(self, layout, monoid):
        super().__init__(layout)
        self.monoid = monoid
        self.n_pad = layout.n_pad
        call = jax.custom_batching.custom_vmap(self._single)
        call.def_vmap(self._vmap_rule)
        self._call = call

    def host_arrays(self):
        L = self.L
        # every edge tile lies inside one (p', p) block: per-edge source
        # partition is the tile's, repeated
        return {"edge_dst": L.edge_dst,
                "edge_src_part": np.repeat(L.tile_src_part, L.edge_tile)}

    def __call__(self, edge_vals, edge_valid, part_active, arrays=None):
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.gather.ref")):
            return self._call(edge_vals, edge_valid, part_active,
                              self._args(arrays))

    def _single(self, edge_vals, edge_valid, part_active, A):
        mono = self.monoid
        valid = (edge_valid.astype(bool)
                 & (part_active[A["edge_src_part"]] > 0))
        vals = jnp.where(valid, edge_vals.astype(mono.dtype), mono.identity)
        acc = mono.segment_fold(vals, A["edge_dst"], self.n_pad + 1)
        touched = jax.ops.segment_max(valid.astype(jnp.int32), A["edge_dst"],
                                      num_segments=self.n_pad + 1) > 0
        return acc[:self.n_pad], touched[:self.n_pad]

    def _vmap_rule(self, axis_size, in_batched, edge_vals, edge_valid,
                   part_active, A):
        ev_b, evd_b, pa_b, a_b = in_batched
        assert not any(jax.tree_util.tree_leaves(a_b)), \
            "layout arrays are shared by every lane"
        if not ev_b:
            edge_vals = jnp.broadcast_to(
                edge_vals, (axis_size,) + edge_vals.shape)
        if not evd_b:
            edge_valid = jnp.broadcast_to(
                edge_valid, (axis_size,) + edge_valid.shape)
        if not pa_b:
            part_active = jnp.broadcast_to(
                part_active, (axis_size,) + part_active.shape)
        mono = self.monoid
        edge_dst = A["edge_dst"]

        def lanes(vals, valid, active):
            valid = (valid.astype(bool)
                     & (jnp.take(active, A["edge_src_part"], axis=1) > 0))
            vals = jnp.where(valid, vals.astype(mono.dtype), mono.identity)
            return vals, valid, edge_dst[None, :]

        acc, touched = _fold_lanes_flat(
            mono, lanes, (edge_vals, edge_valid, part_active),
            self.n_pad + 1, edge_dst.shape[0])
        return (acc[:, :self.n_pad], touched[:, :self.n_pad]), (True, True)


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

    Replaces the composed scatter kernel + slot gather + gather fold of
    the DC stream: each edge's source message is gathered straight from
    the ``[n_pad + 1]`` vertex table (identity sentinel last) and folded
    into the two-level ``[fold_q]`` sub-accumulators — no ``[NM]`` bin
    buffer (see :mod:`repro.kernels.fused_step`).  The static edge stream
    is kept in destination order, sorted once here, so the fold runs
    without a per-call sort.

    ``apply_weight`` is engine-configured (the registry does not see the
    program): :class:`repro.core.engine.Engine` sets the attribute once,
    before the step is traced, under the same condition the composed
    path applies it.

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
        return out, (True, True)


class RefFusedDC(LayoutBound):
    """Pure-jnp fused DC step with FusedDCKernel's exact call contract —
    the composed oracle collapsed to one gather + one segmented fold.

    Carries the same ``custom_vmap`` rule as :class:`RefGather` (the
    batched multi-source engine path): the table gather batches fine,
    but the segment fold would hit XLA's catastrophic scatter batching
    on CPU, so batched lanes fold through a flattened
    ``lane * ns + dst`` segment space instead.
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
        return ref_fused_scatter_fold(
            self.monoid, table, table_valid, A["edge_src"],
            A["edge_valid"], A["edge_dst"], self.n_pad + 1,
            apply_weight=aw, w=A["edge_w"] if aw is not None else None)

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

        return (_fold_lanes_flat(mono, lanes, (table, table_valid), ns,
                                 src.shape[0]), (True, True))


class FusedStreamKernel:
    """Layout-free fused gather→fold with the stream ``fused_dc`` contract.

    What :class:`FoldKernel` is to the fold, this is to the fused step:
    the distributed engine's gather side has no tile/partition structure
    on the receive table (``rv[slot]``), so the kernel takes the table,
    the slot indices and the static validity per call: an XLA slot
    gather, the edge function, then the two-level Pallas fold, which
    skips its sort when the caller passes ``presorted=True`` (a stream
    in destination order, as ``shard_layout`` keeps it).
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
    """Pure-jnp stream fused step with FusedStreamKernel's call contract."""

    def __init__(self, monoid):
        self.monoid = monoid

    def __call__(self, table, table_valid, idx, edge_valid, dst,
                 num_segments, w=None, apply_weight=None,
                 presorted: bool = False):
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.fused_dc.ref")):
            return ref_fused_scatter_fold(
                self.monoid, table, table_valid, idx, edge_valid, dst,
                int(num_segments), apply_weight=apply_weight, w=w)


class RefScatter(LayoutBound):
    """Pure-jnp DC scatter with ScatterKernel's exact call contract."""

    def __init__(self, layout, monoid):
        super().__init__(layout)
        self.monoid = monoid
        self.n_pad = layout.n_pad

    def host_arrays(self):
        L = self.L
        return {"png_src": L.png_src, "png_valid": L.png_src < L.n_pad}

    def __call__(self, x_flat, active_flat, arrays=None):
        mono, A = self.monoid, self._args(arrays)
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.scatter.ref")):
            src = jnp.minimum(A["png_src"], self.n_pad - 1)
            ok = A["png_valid"] & (active_flat.astype(bool)[src])
            return jnp.where(ok, x_flat.astype(mono.dtype)[src],
                             mono.identity)


class RefSpmv(LayoutBound):
    """Pure-jnp partition-centric SpMV with SpmvKernel's call contract."""

    def __init__(self, layout, weighted=None):
        super().__init__(layout)
        self.n_pad = layout.n_pad
        self.weighted = ((layout.weighted if weighted is None else weighted)
                         and layout.edge_w is not None)

    def host_arrays(self):
        L = self.L
        return {"msg_slot": L.msg_slot, "png_src": L.png_src,
                "edge_dst": L.edge_dst, "edge_valid": L.edge_valid,
                "edge_w": L.edge_w if self.weighted else None}

    def __call__(self, x_flat, arrays=None):
        A = self._args(arrays)
        with obs_tracing.kernel_scope(
                getattr(self, "_obs_scope", "ppm.spmv.ref")):
            return kref.spmv_block_ref(
                x_flat, A["msg_slot"], A["png_src"], A["edge_dst"],
                A["edge_valid"], A["edge_w"], self.n_pad)


def make_kernels(layout, monoid, backend=None, platform=None,
                 with_spmv=False):
    """Construct the engine-facing kernel set through the backend registry."""
    from ..backend import registry
    return registry.make_kernels(layout, monoid, backend=backend,
                                 platform=platform, with_spmv=with_spmv)


__all__ = ["LayoutBound", "GatherKernel", "ScatterKernel", "SpmvKernel",
           "FoldKernel", "FusedDCKernel", "FusedStreamKernel", "RefGather", "RefScatter",
           "RefSpmv", "RefFold", "RefFusedDC", "RefFusedStream",
           "make_kernels", "segment_combine", "dc_gather", "spmv_block",
           "blocked_segment_fold", "two_level_segment_fold",
           "fused_scatter_fold", "ref_fused_scatter_fold", "fused_enabled",
           "kref"]
