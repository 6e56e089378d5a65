"""Distributed PPM engine: shard_map + all_to_all over the device mesh.

The BSP structure of the paper maps 1:1 onto collectives (DESIGN.md §2):

  Scatter (per device, local)   -> message buffer out[D, S] (DC) or
                                   ragged compaction (SC)
  barrier + bin exchange        -> all_to_all / ragged_all_to_all
  Gather (per device, local)    -> blocked segmented monoid fold over the
                                   statically resident dc_bin adjacency
                                   (registry kernel 'fold': the Pallas
                                   kernel of repro.kernels.fold_block by
                                   default — no jax.ops segment ops)

DC mode sends *values only* (+1 validity byte, see DESIGN.md); SC mode sends
(value, dst-id) pairs with wire bytes proportional to active edges.  Mode
selection: ``mode='hybrid'`` applies the aggregated Eq. 1 model per
iteration; ``mode='hybrid_pp'`` applies it per PARTITION (the paper's exact
granularity) and runs both streams in one superstep.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs
from ..backend import registry as kregistry
from ..core.engine import _run_batched_loop, _tree_where
from ..core.program import VertexProgram
from .sharding import graph_spec


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


# ----------------------------------------------------------------------
# wire compression: what actually crosses the all_to_all
# ----------------------------------------------------------------------

def _pack_bf16_pairs(vals, ident):
    """``[..., S]`` bf16 -> ``[..., ceil(S/2)]`` uint32 wire lanes.

    Two bf16 messages bitcast-packed per u32 lane: XLA sinks plain
    converts through collectives (cancelling the up/down-cast pair, so
    the wire stays f32 — observed on XLA:CPU); bitcasts cannot be
    cancelled, so the wire really carries half the bytes.  Odd ``S`` is
    padded with one identity column first (sliced off after the
    exchange by :func:`_unpack_bf16_pairs`)."""
    S = vals.shape[-1]
    if S % 2:
        pad = jnp.full(vals.shape[:-1] + (1,), ident, vals.dtype)
        vals = jnp.concatenate([vals, pad], axis=-1)
    pairs = vals.reshape(vals.shape[:-1] + ((S + 1) // 2, 2))
    return jax.lax.bitcast_convert_type(pairs, jnp.uint32)


def _unpack_bf16_pairs(packed, S):
    """Inverse of :func:`_pack_bf16_pairs`: ``[..., P]`` u32 -> ``[..., S]``
    bf16 (the odd-S identity pad column is discarded)."""
    v = jax.lax.bitcast_convert_type(packed, jnp.bfloat16)
    return v.reshape(v.shape[:-2] + (-1,))[..., :S]


def _pack_bits(flags):
    """``[..., S]`` bool -> ``[..., ceil(S/8)]`` uint8 frontier bitmap.

    Validity flags cross the wire 8x smaller than bool lanes (XLA sends
    one byte per bool).  The pack/unpack pair is shifts and masked sums,
    which the algebraic simplifier cannot cancel through the collective,
    so the wire really carries the packed bytes.  Byte ``j`` holds flags
    ``j, P + j, ..., 7P + j``: the bit axis is a major one, so the
    minor axis stays ``P`` long (a minor axis of 8 takes the TPU
    compiler tens of seconds to lay out at scale)."""
    S = flags.shape[-1]
    P = -(-S // 8)
    if 8 * P != S:
        pad = jnp.zeros(flags.shape[:-1] + (8 * P - S,), jnp.bool_)
        flags = jnp.concatenate([flags, pad], axis=-1)
    bits = flags.reshape(flags.shape[:-1] + (8, P)).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[:, None]
    return jnp.sum(bits * weights, axis=-2, dtype=jnp.uint8)


def _unpack_bits(packed, S):
    """Inverse of :func:`_pack_bits`: ``[..., P]`` u8 -> ``[..., S]`` bool."""
    bits = (packed[..., None, :]
            >> jnp.arange(8, dtype=jnp.uint8)[:, None]) & jnp.uint8(1)
    return bits.reshape(bits.shape[:-2] + (-1,))[..., :S] != 0


def dc_wire_bytes(meta: dict, value_itemsize: int, *,
                  compressed: bool = False, wire_bitmap: bool = True,
                  dense_frontier: bool = False, batch: int = 1) -> int:
    """Per-step, per-device all_to_all payload bytes of the DC bin
    exchange (values + validity flags), for benchmark/cost reporting.

    ``compressed`` means the bf16 wire is actually active (``wire_bf16``
    requested AND the monoid is f32); ``batch`` scales both payloads by
    the live lane width of a batched step."""
    S, D = meta["S"], meta["D"]
    if compressed:
        val = D * (S + (S % 2)) * 2          # u32 lanes, 2 bf16 each
    else:
        val = D * S * value_itemsize
    if dense_frontier:
        flags = 0
    else:
        flags = D * (-(-S // 8) if wire_bitmap else S)
    return batch * (val + flags)


def _fold_lanes(fold, vals, valid, ids, ns):
    """Per-lane segmented fold, unrolled over the lane axis at trace time.

    The registry folds have no vmap batching rule (XLA's default scatter
    batching serializes ~100x on CPU), and flattening lanes into one
    ``lane * ns + id`` segment space is QUADRATIC in B for the blocked
    fold — every message block carries a full ``[num_segments]`` partial
    accumulator, and both the block count and the segment count grow
    with B (measured 5x slower than B sequential folds at B=16).  The
    unroll keeps per-lane cost identical to the sequential fold —
    batching amortizes the collectives and host dispatch, never the fold
    math — at B extra traced ops per compiled step (bounded: one step
    per pow2 lane width ever compiles)."""
    accs, touch = [], []
    for i in range(vals.shape[0]):
        a, t = fold(vals[i], valid[i], ids[i], ns)
        accs.append(a)
        touch.append(t)
    return jnp.stack(accs), jnp.stack(touch)


def _aggregate(program: VertexProgram, state, meta: dict, axes,
               batched: bool = False):
    """The program's aggregate over the real vertices of every shard (a
    global id ``device * nv + local`` below ``n``): the local sum, then a
    ``psum`` over the vertex axes; per lane when ``batched``.  None for a
    program without one."""
    if program.aggregate is None:
        return None
    agg = jax.vmap(program.aggregate) if batched else program.aggregate
    vals = agg(state)                                         # [.., nv]
    nv = meta["nv"]
    gid = jax.lax.axis_index(axes) * nv + jnp.arange(nv)
    local = jnp.sum(jnp.where(gid < meta["n"], vals, 0), axis=-1)
    return jax.lax.psum(local, axes)


def _filter(program: VertexProgram, state, it, agg, vm=None):
    """:meth:`VertexProgram.filter`; ``vm`` maps it over a lane axis."""
    fn = program.filter if vm is None else vm(program.filter, (0, None, 0))
    return fn(state, it, agg)


def _resolve_fold(program: VertexProgram, backend=None, tile=None, q=None):
    """Shard-local segmented fold through the backend registry.

    Defaults to the blocked Pallas fold — Mosaic on TPU, interpreted
    elsewhere; :mod:`repro.kernels.fold_block` up to
    ``REPRO_FOLD_MAX_SEGMENTS`` per-device segments and the two-level
    :mod:`repro.kernels.fold_two_level` (bucket width ``q``) beyond —
    which traces cleanly inside the shard_map step bodies; the packed
    uint64 ``min_with_payload`` folds through ``ref`` (declared in the
    TPU table, a per-call fallback elsewhere)."""
    b = kregistry.resolve("fold", program.monoid, choice=backend)
    fold = b.segment_fold(program.monoid, tile=tile, q=q)
    return kregistry._tag_scope(fold, "fold", b.name), b.name


def _resolve_fused(program: VertexProgram, backend=None, tile=None, q=None):
    """Shard-local fused gather→fold (registry kernel ``fused_dc``), or
    ``(None, None)`` when the composed slot-gather + fold path should run.

    Mirrors :func:`_resolve_fold`'s selection (explicit ``backend=``, the
    ``REPRO_KERNEL_BACKEND`` env, platform default) but with the fused
    kernel's fallback rule: no per-call ``ref`` substitution — when
    ``REPRO_FUSED=0`` or the selected backend does not lower the
    ``(monoid, dtype)`` combination, the DC gather silently stays on the
    composed path (which also remains the SC/hybrid lowering)."""
    from ..kernels.fused_step import fused_enabled
    if not fused_enabled():
        return None, None
    mono = program.monoid
    platform = jax.default_backend()
    if backend is None:
        b = kregistry.BACKENDS[
            kregistry.default_backend_name(platform, "fused_dc",
                                           mono.name)]
    elif isinstance(backend, str):
        if backend not in kregistry.BACKENDS:
            raise ValueError(
                f"unknown backend {backend!r}; choose one of "
                f"{kregistry.available_backends()}")
        b = kregistry.BACKENDS[backend]
    else:
        b = backend
    if not b.supports(platform, "fused_dc", mono.name, mono.dtype):
        return None, None
    fk = b.fused_stream(mono, tile=tile, q=q)
    return kregistry._tag_scope(fk, "fused_dc", b.name), b.name


def build_dc_step(program: VertexProgram, meta: dict,
                  axis_names: Sequence[str], dense_frontier: bool = False,
                  wire_bf16: bool = False, wire_bitmap: bool = False,
                  fold=None, fused=None, batched: bool = False):
    """Destination-centric distributed iteration (per-device body).

    dense_frontier: the app keeps every vertex active every iteration
    (paper's PageRank) — the validity-flag exchange is constant and is
    skipped entirely, halving the small-payload side of the bin exchange.
    wire_bf16: cast f32 message values to bf16 on the wire (beyond-paper
    message compression; a no-op — hence exact — for the integer id
    monoids of BFS/CC, approximate for float accumulations).  Odd ``S``
    is handled by padding the packed lane to even length.
    wire_bitmap: exchange the validity flags as a packed frontier bitmap
    (8x smaller than bool lanes on the wire, bit-exact).
    batched: the body carries a leading query-lane axis — state/active
    arrive as ``[B, nv]`` shards, the bin exchange moves ``[B, D, S]`` in
    ONE collective per payload, and the gather folds every lane through a
    single flattened-segment-space fold (:func:`_fold_lanes`), so each
    scatter/all_to_all/fold launch is amortized across the whole batch.
    fused: a registry ``fused_dc`` stream kernel (:func:`_resolve_fused`);
    when set, the gather side skips the ``[NEd]`` slot-gathered
    edge-value stream entirely — the kernel gathers straight from the
    received bin table and folds in one launch.  ``None`` keeps the
    composed slot gather + fold."""
    mono = program.monoid
    nv, S, D = meta["nv"], meta["S"], meta["D"]
    weighted = meta["weighted"]
    axes = tuple(axis_names)
    compress = wire_bf16 and mono.dtype == jnp.float32
    fold = fold if fold is not None else _resolve_fold(program)[0]
    # wire dtype used end-to-end from scatter through the gather-side slot
    # lookup: adjacent up/down-cast pairs around the collective get
    # cancelled by XLA's algebraic simplifier (observed), so the narrow
    # dtype must live across the whole exchange
    wdt = jnp.bfloat16 if compress else mono.dtype
    # all_to_all split/concat axis: the [D] bin axis sits after the
    # optional lane axis
    dev_ax = 1 if batched else 0

    def vm(fn, in_axes):
        return jax.vmap(fn, in_axes=in_axes) if batched else fn

    def step(state, active, arrays, it):
        # state/active: [nv] shard ([B, nv] when batched); arrays:
        # per-device slices (leading 1)
        A = _squeeze0(arrays)
        lead = active.shape[:-1]                              # () or (B,)
        agg = _aggregate(program, state, meta, axes, batched)
        msgs = vm(program.scatter_fn, 0)(state).astype(wdt)
        ident = jnp.asarray(mono.identity, wdt)

        if program.init_fn is not None:
            st2, keep = vm(program.init_fn, (0, None))(state, it)
            state = _tree_where(active, st2, state)
            keep = keep & active
        else:
            keep = jnp.zeros(active.shape, jnp.bool_)

        # ---- scatter: fill the bin row (values only) ----
        srcl = A["out_src_local"]                             # [D, S]
        flag = A["out_valid"] & active[..., srcl]             # [.., D, S]
        out_vals = jnp.where(flag, msgs[..., srcl], ident)

        # ---- bin exchange (the BSP barrier) ----
        if compress:
            packed = _pack_bf16_pairs(out_vals, ident)
            recv_p = jax.lax.all_to_all(packed, axes, dev_ax, dev_ax)
            recv_vals = _unpack_bf16_pairs(recv_p, S)
        else:
            recv_vals = jax.lax.all_to_all(out_vals, axes, dev_ax, dev_ax)
        if dense_frontier:
            # validity is static (= out_valid of the sender); the receive
            # side's static in_valid already encodes it
            rf = jnp.ones(lead + (D * S + 1,), jnp.bool_) \
                .at[..., -1].set(False)
        else:
            if wire_bitmap:
                recv_pk = jax.lax.all_to_all(
                    _pack_bits(flag), axes, dev_ax, dev_ax)
                recv_flag = _unpack_bits(recv_pk, S)
            else:
                recv_flag = jax.lax.all_to_all(flag, axes, dev_ax, dev_ax)
            rf = jnp.concatenate(
                [recv_flag.reshape(lead + (D * S,)),
                 jnp.zeros(lead + (1,), jnp.bool_)], axis=-1)
        rv = jnp.concatenate(
            [recv_vals.reshape(lead + (D * S,)),
             jnp.full(lead + (1,), ident, wdt)], axis=-1)

        # ---- gather over the pre-written dc_bin ----
        if fused is not None:
            # fused lowering: the kernel gathers each edge's value from
            # the received bin table itself — no [NEd] edge-value stream.
            # The table is pre-cast off the wire dtype (the elementwise
            # cast commutes with the gather, so parity with the composed
            # ``rv[slot].astype`` is bit-exact); shard_layout keeps each
            # device's edges in destination order, so the fold skips
            # its sort
            table = rv.astype(mono.dtype)
            aw = (program.apply_weight
                  if program.apply_weight is not None and weighted
                  else None)
            w = A["in_w"] if aw is not None else None
            slot, evalid_s = A["in_msg_slot"], A["in_valid"]
            dst_s = A["in_dst_local"]
            if batched:
                # per-lane unroll, same rationale as _fold_lanes (the
                # static slot/validity/dst streams are shared)
                accs, touch = [], []
                for i in range(table.shape[0]):
                    a, t, _ = fused(table[i], rf[i], slot, evalid_s,
                                    dst_s, nv + 1, w=w, apply_weight=aw,
                                    presorted=True)
                    accs.append(a)
                    touch.append(t)
                acc, touched = jnp.stack(accs), jnp.stack(touch)
            else:
                acc, touched, _ = fused(table, rf, slot, evalid_s, dst_s,
                                        nv + 1, w=w, apply_weight=aw,
                                        presorted=True)
        else:
            slot = A["in_msg_slot"]
            ev = rv[..., slot].astype(mono.dtype)             # [.., NEd]
            evalid = rf[..., slot] & A["in_valid"]
            if program.apply_weight is not None and weighted:
                ev = vm(program.apply_weight, (0, None))(ev, A["in_w"])
            ev = jnp.where(evalid, ev, mono.identity)
            dst = jnp.where(evalid, A["in_dst_local"], nv)
            if batched:
                acc, touched = _fold_lanes(fold, ev, evalid, dst, nv + 1)
            else:
                acc, touched = fold(ev, evalid, dst, nv + 1)
        acc, touched = acc[..., :nv], touched[..., :nv]

        st3, activated = vm(program.apply_fn, (0, 0, 0, None))(
            state, acc, touched, it)
        state = _tree_where(touched, st3, state)
        new_active = keep | (activated & touched)
        if program.filter_fn is not None:
            st4, fkeep = _filter(program, state, it, agg, vm)
            state = _tree_where(new_active, st4, state)
            new_active = new_active & fkeep
        return state, new_active

    return step


def build_sc_step(program: VertexProgram, meta: dict,
                  axis_names: Sequence[str], ragged: bool = False,
                  fold=None):
    """Source-centric distributed iteration: per-destination compaction +
    ragged exchange.

    ``ragged=True`` uses ``lax.ragged_all_to_all`` (TPU backends — wire bytes
    truly proportional to the active edges).  ``ragged=False`` is the portable
    emulation: compacted per-pair capacity buffers over a dense ``all_to_all``
    with explicit counts (identical semantics; XLA:CPU has no ragged thunk).
    The Eq. 1 cost model prices the SC wire bytes as ragged either way, which
    is exact for the TPU target.
    """
    mono = program.monoid
    nv, D = meta["nv"], meta["D"]
    cap_in = meta["cap_in"]
    cap_pair = meta["cap_pair"]
    weighted = meta["weighted"]
    axes = tuple(axis_names)
    fold = fold if fold is not None else _resolve_fold(program)[0]

    def step(state, active, arrays, it):
        A = _squeeze0(arrays)
        agg = _aggregate(program, state, meta, axes)
        msgs = program.scatter_fn(state).astype(mono.dtype)
        ident = mono.identity
        ne_s = A["oe_src_local"].shape[0]

        if program.init_fn is not None:
            st2, keep = program.init_fn(state, it)
            state = _tree_where(active, st2, state)
            keep = keep & active
        else:
            keep = jnp.zeros((nv,), jnp.bool_)

        # ---- compact active out-edges per destination-device group ----
        act_e = A["oe_valid"] & active[A["oe_src_local"]]      # [NEs]
        vals_e = msgs[A["oe_src_local"]]
        if program.apply_weight is not None and weighted:
            vals_e = program.apply_weight(vals_e, A["oe_w"])
        goff = A["oe_group_off"].astype(jnp.int32)             # [D+1]
        c = jnp.cumsum(act_e.astype(jnp.int32))
        co = jnp.concatenate([jnp.zeros((1,), jnp.int32), c])
        tot_at = co[goff]                                      # [D+1]
        send_sizes = jnp.diff(tot_at)                          # [D]
        grp = jnp.searchsorted(goff[1:], jnp.arange(ne_s, dtype=jnp.int32),
                               side="right").astype(jnp.int32)
        grp_c = jnp.minimum(grp, D - 1)
        rank = (c - 1) - tot_at[grp_c]                         # rank in group

        if ragged:
            send_off = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(send_sizes)[:-1].astype(jnp.int32)])
            pos = jnp.where(act_e, send_off[grp_c] + rank, ne_s)
            buf_vals = jnp.full((ne_s + 1,), ident, mono.dtype) \
                .at[pos].set(jnp.where(act_e, vals_e, ident))[:ne_s]
            buf_ids = jnp.full((ne_s + 1,), nv, jnp.int32) \
                .at[pos].set(jnp.where(act_e, A["oe_dst_local"], nv))[:ne_s]
            recv_sizes = jax.lax.all_to_all(
                send_sizes.reshape(D, 1), axes, 0, 0).reshape(D)
            recv_off = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(recv_sizes)[:-1].astype(jnp.int32)])
            out_offsets = jax.lax.all_to_all(
                recv_off.reshape(D, 1), axes, 0, 0).reshape(D)
            rvals = jax.lax.ragged_all_to_all(
                buf_vals, jnp.full((cap_in,), ident, mono.dtype),
                send_off, send_sizes, out_offsets, recv_sizes,
                axis_name=axes)
            rids = jax.lax.ragged_all_to_all(
                buf_ids, jnp.full((cap_in,), nv, jnp.int32),
                send_off, send_sizes, out_offsets, recv_sizes,
                axis_name=axes)
            total = jnp.sum(recv_sizes)
            valid = jnp.arange(cap_in, dtype=jnp.int32) < total
        else:
            # portable emulation: per-pair rows of capacity cap_pair
            flat = jnp.where(act_e, grp_c * cap_pair + rank, D * cap_pair)
            buf_vals = jnp.full((D * cap_pair + 1,), ident, mono.dtype) \
                .at[flat].set(jnp.where(act_e, vals_e, ident))[:-1] \
                .reshape(D, cap_pair)
            buf_ids = jnp.full((D * cap_pair + 1,), nv, jnp.int32) \
                .at[flat].set(jnp.where(act_e, A["oe_dst_local"], nv))[:-1] \
                .reshape(D, cap_pair)
            recv_sizes = jax.lax.all_to_all(
                send_sizes.reshape(D, 1), axes, 0, 0).reshape(D)
            rvals = jax.lax.all_to_all(buf_vals, axes, 0, 0).reshape(-1)
            rids = jax.lax.all_to_all(buf_ids, axes, 0, 0).reshape(-1)
            col = jnp.tile(jnp.arange(cap_pair, dtype=jnp.int32), (D, 1))
            valid = (col < recv_sizes[:, None]).reshape(-1)

        ids = jnp.where(valid, rids, nv)
        vals = jnp.where(valid, rvals, ident)
        acc, touched = fold(vals, valid, ids, nv + 1)
        acc, touched = acc[:nv], touched[:nv]

        st3, activated = program.apply_fn(state, acc, touched, it)
        state = _tree_where(touched, st3, state)
        new_active = keep | (activated & touched)
        if program.filter_fn is not None:
            st4, fkeep = _filter(program, state, it, agg)
            state = _tree_where(new_active, st4, state)
            new_active = new_active & fkeep
        return state, new_active

    return step


def build_hybrid_step(program: VertexProgram, meta: dict,
                      axis_names: Sequence[str], fold=None):
    """Per-partition dual-mode iteration — the paper's exact granularity
    (Eq. 1 decided per partition, not per iteration).

    ``dc_mask`` (one bool per local partition) selects, per partition,
    whether its vertices scatter through the dense DC bins or the compacted
    SC exchange; both streams fold into the same accumulator, exactly like
    the single-device engine."""
    mono = program.monoid
    nv, S, D = meta["nv"], meta["S"], meta["D"]
    cap_pair = meta["cap_pair"]
    kpd = meta["kpd"]
    q = nv // kpd
    weighted = meta["weighted"]
    axes = tuple(axis_names)
    fold = fold if fold is not None else _resolve_fold(program)[0]

    def step(state, active, arrays, it, dc_mask):
        A = _squeeze0(arrays)
        dcm = dc_mask[0] if dc_mask.ndim == 2 else dc_mask     # [kpd]
        agg = _aggregate(program, state, meta, axes)
        msgs = program.scatter_fn(state).astype(mono.dtype)
        ident = mono.identity

        if program.init_fn is not None:
            st2, keep = program.init_fn(state, it)
            state = _tree_where(active, st2, state)
            keep = keep & active
        else:
            keep = jnp.zeros((nv,), jnp.bool_)

        # ---- DC stream: only partitions in DC mode ----
        srcl = A["out_src_local"]                              # [D, S]
        src_part = srcl // q
        flag = A["out_valid"] & active[srcl] & dcm[src_part]
        out_vals = jnp.where(flag, msgs[srcl], ident)
        recv_vals = jax.lax.all_to_all(out_vals, axes, 0, 0)
        recv_flag = jax.lax.all_to_all(flag, axes, 0, 0)
        rv = jnp.concatenate([recv_vals.reshape(-1),
                              mono.identity_array((1,))])
        rf = jnp.concatenate([recv_flag.reshape(-1),
                              jnp.zeros((1,), jnp.bool_)])
        ev = rv[A["in_msg_slot"]]
        evalid = rf[A["in_msg_slot"]] & A["in_valid"]
        if program.apply_weight is not None and weighted:
            ev = program.apply_weight(ev, A["in_w"])
        ev = jnp.where(evalid, ev, ident)
        dst = jnp.where(evalid, A["in_dst_local"], nv)
        acc, touched = fold(ev, evalid, dst, nv + 1)

        # ---- SC stream: active vertices of non-DC partitions ----
        vpart = jnp.arange(nv, dtype=jnp.int32) // q
        sc_active = active & ~dcm[vpart]
        ne_s = A["oe_src_local"].shape[0]
        act_e = A["oe_valid"] & sc_active[A["oe_src_local"]]
        vals_e = msgs[A["oe_src_local"]]
        if program.apply_weight is not None and weighted:
            vals_e = program.apply_weight(vals_e, A["oe_w"])
        goff = A["oe_group_off"].astype(jnp.int32)
        c = jnp.cumsum(act_e.astype(jnp.int32))
        co = jnp.concatenate([jnp.zeros((1,), jnp.int32), c])
        tot_at = co[goff]
        send_sizes = jnp.diff(tot_at)
        grp = jnp.searchsorted(goff[1:], jnp.arange(ne_s, dtype=jnp.int32),
                               side="right").astype(jnp.int32)
        grp_c = jnp.minimum(grp, D - 1)
        rank = (c - 1) - tot_at[grp_c]
        flat = jnp.where(act_e, grp_c * cap_pair + rank, D * cap_pair)
        buf_vals = jnp.full((D * cap_pair + 1,), ident, mono.dtype) \
            .at[flat].set(jnp.where(act_e, vals_e, ident))[:-1] \
            .reshape(D, cap_pair)
        buf_ids = jnp.full((D * cap_pair + 1,), nv, jnp.int32) \
            .at[flat].set(jnp.where(act_e, A["oe_dst_local"], nv))[:-1] \
            .reshape(D, cap_pair)
        recv_sizes = jax.lax.all_to_all(
            send_sizes.reshape(D, 1), axes, 0, 0).reshape(D)
        rvals = jax.lax.all_to_all(buf_vals, axes, 0, 0).reshape(-1)
        rids = jax.lax.all_to_all(buf_ids, axes, 0, 0).reshape(-1)
        col = jnp.tile(jnp.arange(cap_pair, dtype=jnp.int32), (D, 1))
        valid = (col < recv_sizes[:, None]).reshape(-1)
        ids = jnp.where(valid, rids, nv)
        vals = jnp.where(valid, rvals, ident)
        acc2, touched2 = fold(vals, valid, ids, nv + 1)

        acc = mono.combine(acc, acc2)[:nv]
        touched = (touched | touched2)[:nv]

        st3, activated = program.apply_fn(state, acc, touched, it)
        state = _tree_where(touched, st3, state)
        new_active = keep | (activated & touched)
        if program.filter_fn is not None:
            st4, fkeep = _filter(program, state, it, agg)
            state = _tree_where(new_active, st4, state)
            new_active = new_active & fkeep
        return state, new_active

    return step


class DistEngine:
    """Multi-device PPM engine over an arbitrary mesh.

    The graph's device dimension is sharded over *all* mesh axes (the PPM
    bin exchange treats the pod mesh as one flat all_to_all group; the pod
    axis simply contributes the slowest-varying device blocks).
    """

    def __init__(self, sharded, program: VertexProgram, mesh,
                 mode: str = "hybrid", bw_ratio: float = 2.0,
                 backend=None, wire_bf16: bool = False,
                 wire_bitmap: bool = True):
        self.sl = sharded
        self.program = program
        self.mesh = mesh
        self.mode = mode
        self.bw_ratio = bw_ratio
        self.axes = tuple(mesh.axis_names)
        self.wire_bf16 = wire_bf16
        self.wire_bitmap = wire_bitmap
        # bf16 wire only engages for f32 monoids; for the integer id
        # monoids (BFS/CC) it is skipped, so requesting it stays exact
        self.wire_compressed = (wire_bf16
                                and program.monoid.dtype == jnp.float32)
        fold, self.backend_name = _resolve_fold(
            program, backend, tile=getattr(sharded, "fold_tile", None),
            q=getattr(sharded, "fold_q", None))
        fused, self.fused_backend_name = _resolve_fused(
            program, backend, tile=getattr(sharded, "fold_tile", None),
            q=getattr(sharded, "fold_q", None))
        self.fold, self.fused = fold, fused
        meta = dict(n=sharded.n, nv=sharded.nv, S=sharded.S, D=sharded.D,
                    cap_in=sharded.cap_in, cap_pair=sharded.cap_pair,
                    kpd=sharded.kpd, weighted=sharded.weighted)
        self.meta = meta
        spec_arr = graph_spec(mesh)
        shard = NamedSharding(mesh, spec_arr)
        # host arrays go straight to their shards (a jnp.asarray first
        # would stage every whole array on device 0)
        self.arrays = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, shard), self.sl.arrays())
        deg = np.zeros(sharded.D * sharded.nv, np.int32)
        deg[:len(sharded.deg)] = sharded.deg
        self.deg = jax.device_put(deg, shard)

        dc_body = build_dc_step(program, meta, self.axes, fold=fold,
                                fused=fused, wire_bf16=wire_bf16,
                                wire_bitmap=wire_bitmap)
        sc_body = build_sc_step(program, meta, self.axes, fold=fold)
        hy_body = build_hybrid_step(program, meta, self.axes, fold=fold)

        def wrap(body):
            def fn(state, active, arrays, it):
                return jax.shard_map(
                    body, mesh=mesh,
                    in_specs=(spec_arr, spec_arr, spec_arr, P()),
                    out_specs=(spec_arr, spec_arr), check_vma=False,
                )(state, active, arrays, it)
            return jax.jit(fn)
        self._dc = wrap(dc_body)
        self._sc = wrap(sc_body)

        def hy_fn(state, active, arrays, it, dc_mask):
            return jax.shard_map(
                hy_body, mesh=mesh,
                in_specs=(spec_arr, spec_arr, spec_arr, P(), spec_arr),
                out_specs=(spec_arr, spec_arr), check_vma=False,
            )(state, active, arrays, it, dc_mask)
        self._hy = jax.jit(hy_fn)

        # batched DC step: ONE shard_map whose body carries a leading
        # query-lane axis — the bin exchange moves [B, D, S] per
        # collective.  jit's shape cache provides the per-width
        # specializations _run_batched_loop asks for (<= log2(B) of them
        # thanks to the pow2 lane compaction)
        dcb_body = build_dc_step(program, meta, self.axes, fold=fold,
                                 fused=fused, wire_bf16=wire_bf16,
                                 wire_bitmap=wire_bitmap, batched=True)
        bspec = P(None, tuple(mesh.axis_names))
        self._bspec = bspec

        def dcb_fn(states, active, arrays, it):
            done = ~active.any(axis=1)                         # [B]
            new_states, new_active = jax.shard_map(
                dcb_body, mesh=mesh,
                in_specs=(bspec, bspec, spec_arr, P()),
                out_specs=(bspec, bspec), check_vma=False,
            )(states, active, arrays, it)
            # freeze converged lanes (cf. Engine._batched_step_fn): an
            # empty frontier is already a no-op for every phase, the
            # explicit freeze makes the contract independent of the
            # program's init/filter behaviour
            keep = ~done
            new_states = _tree_where(keep, new_states, states)
            new_active = new_active & keep[:, None]
            return new_states, new_active
        self._dcb = jax.jit(dcb_fn)

        # per-(global)-partition stats for the Eq. 1 per-partition decision;
        # partitions are index-contiguous q-sized ranges, so the segment
        # reduction is a plain reshape-sum (no segment ops anywhere here)
        k_glob = sharded.D * sharded.kpd
        q = sharded.nv // sharded.kpd
        # overflow-safe accumulation dtype for edge-degree sums: when x64
        # is off, `astype(jnp.int64)` silently means int32 and an active
        # degree sum past 2**31 WRAPS, flipping the Eq. 1 decision.
        # Float never wraps, and its ~1e-7 relative rounding cannot flip
        # a float threshold comparison
        fdt = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        # the degrees ride as an argument: jit would embed a closed-over
        # array in the program as a constant
        deg_f = self.deg.astype(fdt)

        @jax.jit
        def _part_stats(active, deg_f):
            a32 = active.astype(jnp.int32)
            counts = a32.reshape(k_glob, q).sum(axis=1)
            ea = (active.astype(fdt) * deg_f).reshape(k_glob, q).sum(axis=1)
            return counts, ea
        self._pstats = lambda active: _part_stats(active, deg_f)
        from ..core.cost import CostModel
        dc_cost = (sharded.part_msgs * 4 + k_glob * 4
                   + 2 * sharded.part_msgs * 4 + sharded.part_edges * 4)
        kk = len(sharded.part_edges)
        # pad per-partition constants to the padded global partition count
        dcc = np.zeros(k_glob); dcc[:kk] = dc_cost
        r = sharded.part_msgs / np.maximum(sharded.part_edges, 1)
        scc = np.zeros(k_glob); scc[:kk] = 2 * r * 4 + 3 * 4
        self._cost_pp = CostModel(dc_cost=dcc, sc_coeff=scc,
                                  bw_ratio=bw_ratio)

        @jax.jit
        def _stats(active, deg_f):
            # vertex count fits int32 (n < 2**31); the edge-degree sum
            # does not — accumulate it in float (see fdt above)
            return (jnp.sum(active.astype(jnp.int32)),
                    jnp.sum(active.astype(fdt) * deg_f))
        self._stats = lambda active: _stats(active, deg_f)

        # aggregated Eq. 1 threshold: average DC cost per (all) edge vs the
        # per-active-edge SC cost
        L_edges = float(sharded.part_edges.sum())
        self._dc_total = float(
            (sharded.part_msgs.sum() * 4 + sharded.part_edges.sum() * 4
             + 2 * sharded.part_msgs.sum() * 4))
        r = float(sharded.part_msgs.sum()) / max(L_edges, 1.0)
        self._sc_per_edge = 2 * r * 4 + 3 * 4

    def _choose_dc(self, e_active: float) -> bool:
        if self.mode == "dc":
            return True
        if self.mode == "sc":
            return False
        return self._dc_total <= self.bw_ratio * e_active * self._sc_per_edge

    def run(self, state, frontier, max_iters: int = 10_000,
            until_empty: bool = True):
        shard = NamedSharding(self.mesh, graph_spec(self.mesh))
        state = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), shard), state)
        active = jax.device_put(jnp.asarray(frontier, jnp.bool_), shard)
        stats = []
        for it in range(max_iters):
            n_act, e_act = self._stats(active)
            n_act, e_act = int(n_act), float(e_act)
            if until_empty and n_act == 0:
                break
            t0 = time.perf_counter()
            if self.mode == "hybrid_pp":
                counts, ea = self._pstats(active)
                counts = np.asarray(counts)
                ea = np.asarray(ea)
                dc_mask = self._cost_pp.choose_dc(ea, counts > 0)
                state, active = self._hy(
                    state, active, self.arrays, jnp.int32(it),
                    jax.device_put(
                        jnp.asarray(dc_mask),
                        NamedSharding(self.mesh, graph_spec(self.mesh))))
                jax.block_until_ready(active)
                # analytic wire: full DC bin payload for the DC stream +
                # per-active-edge SC payload of the SC partitions
                sc_e = float(ea[(~dc_mask) & (counts > 0)].sum())
                wire = (self.wire_bytes_per_step()
                        + int(self._sc_per_edge * sc_e))
                stats.append(dict(it=it, n_active=n_act, e_active=int(e_act),
                                  mode="hybrid_pp",
                                  dc_parts=int(dc_mask.sum()),
                                  sc_parts=int(((~dc_mask)
                                                & (counts > 0)).sum()),
                                  wire_bytes=wire,
                                  wall_s=time.perf_counter() - t0))
                self._record_iter(stats[-1])
                continue
            use_dc = self._choose_dc(e_act)
            fn = self._dc if use_dc else self._sc
            state, active = fn(state, active, self.arrays, jnp.int32(it))
            jax.block_until_ready(active)
            wire = (self.wire_bytes_per_step() if use_dc
                    else int(self._sc_per_edge * e_act))
            stats.append(dict(it=it, n_active=n_act, e_active=int(e_act),
                              mode="dc" if use_dc else "sc",
                              wire_bytes=wire,
                              wall_s=time.perf_counter() - t0))
            self._record_iter(stats[-1])
        return state, active, stats

    def _record_iter(self, s: dict):
        """Telemetry for one distributed step (no-op when obs is off):
        engine_iter event with the analytic wire bytes and step-wall
        histogram keyed by mode."""
        if not obs.enabled():
            return
        prog = self.program.name
        obs.event("engine_iter", engine="dist", program=prog, **s)
        obs.observe("engine.step_wall_s", s["wall_s"], engine="dist",
                    program=prog or "?", mode=s["mode"])

    # ------------------------------------------------------------------
    def wire_bytes_per_step(self, batch: int = 1) -> int:
        """Analytic per-device all_to_all payload bytes of one DC step
        (values + validity flags) under this engine's wire config, for a
        live lane width of ``batch``."""
        return dc_wire_bytes(
            self.meta, np.dtype(self.program.monoid.dtype).itemsize,
            compressed=self.wire_compressed, wire_bitmap=self.wire_bitmap,
            batch=batch)

    def run_batched(self, states, frontiers, max_iters: int = 10_000,
                    until_empty: bool = True, collect_stats: bool = True):
        """Batched multi-source execution across the mesh: B independent
        queries of the same vertex program advance together through one
        batched DC superstep — the bin exchange moves ``[B, D, S]`` in a
        single all_to_all per payload and the gather folds every lane in
        one flattened-segment fold, so each collective/fold launch is
        amortized across the whole batch.

        ``states`` leaves carry a leading query axis ``[B, ...]``;
        ``frontiers`` is ``[B, D*nv]`` bool over the same global vertex
        space :meth:`run` uses (``D*nv == n_pad``, so the single-device
        ``*_multi`` app entry points work unchanged).  The union frontier
        drives convergence, converged lanes are frozen in-step and
        compacted out between steps at pow2 widths (shared loop:
        :func:`repro.core.engine._run_batched_loop`).  DC mode only —
        batching amortizes launches, while the SC wire advantage shrinks
        as the batched bins fill; the wire blowup is attacked with
        ``wire_bf16`` + the packed frontier bitmap instead.  Results are
        bit-exact with B sequential :meth:`run` calls in ``mode='dc'``
        under the same wire config."""
        shard = NamedSharding(self.mesh, self._bspec)
        states = jax.tree_util.tree_map(
            lambda a: jax.device_put(jnp.asarray(a), shard), states)
        active = jax.device_put(jnp.asarray(frontiers, jnp.bool_), shard)
        assert active.ndim == 2, "frontiers must be [B, D*nv]"

        def step_for_width(W):
            return lambda s, a, it: self._dcb(s, a, self.arrays, it)

        return _run_batched_loop(step_for_width, states, active,
                                 max_iters, until_empty, collect_stats,
                                 engine_name="dist",
                                 program=self.program.name,
                                 wire_bytes_fn=self.wire_bytes_per_step)
