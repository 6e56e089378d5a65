"""Device-sharded partition-centric layout (DESIGN.md §2, §6).

Level-1 partitioning: ``k`` partitions are distributed over ``D`` devices
(``kpd = k/D`` partitions per device, index-contiguous — the same rule the
paper uses for threads).  The 2D bin grid becomes a per-(src-device,
dst-device) exchange:

  * DC mode: the scatter-side message buffer is ``out[D, S]`` (slot tiles
    grouped by destination device, values only); one dense ``all_to_all``
    delivers every bin column to its owner, after which the *statically
    resident* ``in_msg_slot`` / ``in_dst_local`` arrays (the paper's
    pre-written ``dc_bin``) drive a local segmented fold.
  * SC mode: out-edges grouped by destination device with per-group
    compaction and a ``ragged_all_to_all`` — wire bytes proportional to the
    active edges, the paper's work-efficiency on the ICI.

All per-device arrays are padded to the max across devices (SPMD needs equal
shapes); real sizes are kept for the cost model.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from .layout import Layout, _pad_to


@dataclasses.dataclass
class ShardedLayout:
    D: int
    kpd: int                 # partitions per device
    q: int
    nv: int                  # vertices per device = kpd * q
    n: int                   # real vertex count (global)
    S: int                   # message-slot capacity per (src,dst) device pair
    weighted: bool
    fold_tile: int           # blocked-fold message tile (from the Layout)
    fold_q: int              # two-level fold bucket width (from the Layout)

    # ---- DC scatter side (per source device) ----
    out_src_local: np.ndarray   # int32[D, D, S]
    out_valid: np.ndarray       # bool [D, D, S]

    # ---- DC gather side (per destination device) ----
    in_msg_slot: np.ndarray     # int32[D, NEd] -> index into recv[D*S] (sentinel D*S)
    in_dst_local: np.ndarray    # int32[D, NEd] (sentinel nv), sorted per device
    in_valid: np.ndarray        # bool [D, NEd]
    in_w: Optional[np.ndarray]  # f32  [D, NEd]

    # ---- SC side: out-edges grouped by destination device ----
    oe_src_local: np.ndarray    # int32[D, NEs]
    oe_dst_local: np.ndarray    # int32[D, NEs] (local to the *destination*)
    oe_valid: np.ndarray        # bool [D, NEs]
    oe_w: Optional[np.ndarray]  # f32  [D, NEs]
    oe_group_off: np.ndarray    # int64[D, D+1] group boundaries
    cap_in: int                 # SC receive capacity (max in-edges/device)
    cap_pair: int               # SC per-(src,dst)-pair capacity

    # host-side cost-model stats
    part_edges: np.ndarray      # int64[k] (global, from Layout)
    part_msgs: np.ndarray
    deg: np.ndarray             # int64[D*nv] sharded-order out-degrees

    @property
    def ne_d(self) -> int:
        return self.in_msg_slot.shape[1]

    @property
    def ne_s(self) -> int:
        return self.oe_src_local.shape[1]

    def arrays(self) -> dict:
        """The pytree of device-partitioned arrays fed into the step fn."""
        d = dict(out_src_local=self.out_src_local, out_valid=self.out_valid,
                 in_msg_slot=self.in_msg_slot, in_dst_local=self.in_dst_local,
                 in_valid=self.in_valid,
                 oe_src_local=self.oe_src_local, oe_dst_local=self.oe_dst_local,
                 oe_valid=self.oe_valid, oe_group_off=self.oe_group_off)
        if self.weighted:
            d["in_w"] = self.in_w
            d["oe_w"] = self.oe_w
        return d


def shard_layout(L: Layout, D: int) -> ShardedLayout:
    """Regroup a single-device Layout for D devices (k must divide by D)."""
    k, q = L.k, L.q
    assert k % D == 0, f"k={k} not divisible by D={D}"
    kpd = k // D
    nv = kpd * q
    n_pad = L.n_pad
    nm_pad = L.num_msgs

    # ---------- DC scatter side: regroup PNG slots by device pair ----------
    slot_blk = np.repeat(np.arange(k * k, dtype=np.int64),
                         np.diff(L.png_off))
    sp_, dp_ = slot_blk // k, slot_blk % k
    sdev, ddev = sp_ // kpd, dp_ // kpd
    pair = sdev * D + ddev
    order = np.argsort(pair, kind="stable")
    pair_cnt = np.bincount(pair, minlength=D * D)
    S = _pad_to(int(pair_cnt.max(initial=0)), 8)
    rank = np.arange(nm_pad) - np.repeat(
        np.concatenate([[0], np.cumsum(pair_cnt)])[:-1], pair_cnt)
    pos = np.empty(nm_pad, dtype=np.int64)
    pos[order] = rank                                    # position within pair
    # out buffers
    out_src_local = np.zeros((D, D, S), dtype=np.int32)
    out_valid = np.zeros((D, D, S), dtype=bool)
    real = L.png_src < n_pad
    out_src_local[sdev[real], ddev[real], pos[real]] = \
        (L.png_src[real] - sdev[real].astype(np.int64) * nv).astype(np.int32)
    out_valid[sdev[real], ddev[real], pos[real]] = True
    # receive-side index of each slot: row = src device, col = pos
    slot_recv = (sdev * S + pos).astype(np.int64)        # in [0, D*S)

    # ---------- DC gather side: per-destination-device edge slices ----------
    # gather-order blocks are keyed p'*k + p, so each device's incoming edges
    # are one contiguous range of the global arrays.
    dev_edge_lo = L.blk_off[np.arange(D) * kpd * k]
    dev_edge_hi = L.blk_off[(np.arange(D) + 1) * kpd * k]
    ne_d = _pad_to(int((dev_edge_hi - dev_edge_lo).max(initial=0)),
                   L.edge_tile)
    in_msg_slot = np.full((D, ne_d), D * S, dtype=np.int32)
    in_dst_local = np.full((D, ne_d), nv, dtype=np.int32)
    in_valid = np.zeros((D, ne_d), dtype=bool)
    in_w = np.zeros((D, ne_d), dtype=np.float32) if L.weighted else None
    for d in range(D):
        lo, hi = int(dev_edge_lo[d]), int(dev_edge_hi[d])
        c = hi - lo
        dst = L.edge_dst[lo:hi].astype(np.int64)
        dok = dst < n_pad
        dl = np.full(c, nv, dtype=np.int32)
        dl[dok] = (dst[dok] - d * nv).astype(np.int32)
        # each device's edges in destination order (the sentinel nv,
        # and the padding after them, last): the fused gather folds a
        # presorted stream (one sort of packed dst << 32 | position keys)
        key = dl.astype(np.int64) << 32
        key |= np.arange(c, dtype=np.int64)
        key.sort()
        o = lo + (key & 0xFFFFFFFF)
        ms = L.msg_slot[o]
        ok = ms < nm_pad
        slot_mapped = np.full(c, D * S, dtype=np.int32)
        slot_mapped[ok] = slot_recv[ms[ok]].astype(np.int32)
        in_msg_slot[d, :c] = slot_mapped
        in_dst_local[d, :c] = (key >> 32).astype(np.int32)
        in_valid[d, :c] = L.edge_valid[o]
        if L.weighted:
            in_w[d, :c] = L.edge_w[o]

    # ---------- SC side: out-edges grouped by (src device, dst device) ------
    deg_np = L.deg
    src_g = np.repeat(np.arange(L.n, dtype=np.int64),
                      deg_np[:L.n].astype(np.int64))
    dst_g = L.csr_indices.astype(np.int64)
    w_g = L.csr_w
    sdev_e = src_g // nv
    ddev_e = dst_g // nv
    okey = sdev_e * D + ddev_e
    eorder = np.argsort(okey, kind="stable")
    src_g, dst_g, okey = src_g[eorder], dst_g[eorder], okey[eorder]
    sdev_e, ddev_e = sdev_e[eorder], ddev_e[eorder]
    if w_g is not None:
        w_g = w_g[eorder]
    per_dev_cnt = np.bincount(sdev_e, minlength=D)
    ne_s = _pad_to(int(per_dev_cnt.max(initial=0)), 8)
    oe_src_local = np.zeros((D, ne_s), dtype=np.int32)
    oe_dst_local = np.zeros((D, ne_s), dtype=np.int32)
    oe_valid = np.zeros((D, ne_s), dtype=bool)
    oe_w = np.zeros((D, ne_s), dtype=np.float32) if L.weighted else None
    oe_group_off = np.zeros((D, D + 1), dtype=np.int64)
    dev_starts = np.concatenate([[0], np.cumsum(per_dev_cnt)])
    grp_cnt = np.bincount(okey, minlength=D * D).reshape(D, D)
    for d in range(D):
        lo, hi = int(dev_starts[d]), int(dev_starts[d + 1])
        c = hi - lo
        oe_src_local[d, :c] = (src_g[lo:hi] - d * nv).astype(np.int32)
        oe_dst_local[d, :c] = (dst_g[lo:hi]
                               - ddev_e[lo:hi] * nv).astype(np.int32)
        oe_valid[d, :c] = True
        if w_g is not None:
            oe_w[d, :c] = w_g[lo:hi]
        oe_group_off[d, 1:] = np.cumsum(grp_cnt[d])
    in_cnt = np.bincount(np.minimum(dst_g // nv, D - 1), minlength=D)
    cap_in = _pad_to(int(in_cnt.max(initial=1)), 8)
    cap_pair = _pad_to(int(grp_cnt.max(initial=1)), 8)

    deg_pad = np.zeros(D * nv, dtype=np.int64)
    deg_pad[:n_pad] = deg_np
    return ShardedLayout(
        D=D, kpd=kpd, q=q, nv=nv, n=L.n, S=S, weighted=L.weighted,
        fold_tile=L.fold_tile, fold_q=L.fold_q,
        out_src_local=out_src_local, out_valid=out_valid,
        in_msg_slot=in_msg_slot, in_dst_local=in_dst_local,
        in_valid=in_valid, in_w=in_w,
        oe_src_local=oe_src_local, oe_dst_local=oe_dst_local,
        oe_valid=oe_valid, oe_w=oe_w, oe_group_off=oe_group_off,
        cap_in=cap_in, cap_pair=cap_pair,
        part_edges=L.part_edges, part_msgs=L.part_msgs, deg=deg_pad)


def sharded_spec(n: int, m: int, D: int, k_per_dev: int = 4,
                 weighted: bool = False, slot_slack: float = 1.3,
                 edge_slack: float = 1.3):
    """Shape-only ShardedLayout stand-in for the AOT dry-run.

    Buffer sizes follow the same formulas as :func:`shard_layout` but from
    expectations: slots/pair ~ m/D^2 (power-law graphs at device granularity
    are near-uniform under index hashing), edges/device ~ m/D.
    """
    import jax
    k = D * k_per_dev
    q = _pad_to(-(-n // k), 128)
    nv = k_per_dev * q
    S = _pad_to(int(m / (D * D) * slot_slack) + 8, 8)
    ne_d = _pad_to(int(m / D * edge_slack) + 8, 256)
    ne_s = _pad_to(int(m / D * edge_slack) + 8, 8)
    f32 = jax.ShapeDtypeStruct
    arrs = dict(
        out_src_local=f32((D, D, S), np.int32),
        out_valid=f32((D, D, S), np.bool_),
        in_msg_slot=f32((D, ne_d), np.int32),
        in_dst_local=f32((D, ne_d), np.int32),
        in_valid=f32((D, ne_d), np.bool_),
        oe_src_local=f32((D, ne_s), np.int32),
        oe_dst_local=f32((D, ne_s), np.int32),
        oe_valid=f32((D, ne_s), np.bool_),
        oe_group_off=f32((D, D + 1), np.int64),
    )
    if weighted:
        arrs["in_w"] = f32((D, ne_d), np.float32)
        arrs["oe_w"] = f32((D, ne_s), np.float32)
    cap_pair = _pad_to(int(m / (D * D) * edge_slack) + 8, 8)
    meta = dict(n=n, D=D, kpd=k_per_dev, q=q, nv=nv, S=S, cap_in=ne_s,
                cap_pair=cap_pair, weighted=weighted)
    return arrs, meta
