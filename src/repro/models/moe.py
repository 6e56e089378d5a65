"""Mixture-of-Experts with PPM-powered dispatch (DESIGN.md §5).

Token -> expert routing *is* partition-centric message passing: tokens are
source vertices, experts are partitions, the router output is the frontier.
Two dispatch modes mirror the paper's dual communication modes:

  * ``dense_dp`` (default): per-batch-row capacity bins + scatter/gather —
    the DC-like dense mode.  Experts are weight-sharded (FSDP over data, TP
    over model); tokens never cross devices, so dispatch costs zero
    collectives and the expert matmuls are plain einsums.
  * ``ppm_ep`` : explicit expert parallelism via the PPM bin exchange: each
    model-axis shard owns ``E/Dm`` experts; per-(device, expert) capacity
    bins are exchanged with one ``all_to_all`` (scatter), expert FFN runs on
    the owning shard (gather), and a second ``all_to_all`` returns outputs.
    This is the paper's 2D bin grid operating as an LM feature; requires
    ``E % model_axis == 0``.

An Eq. 1-style bytes model (`choose_impl`) picks the mode from the routing
statistics, mirroring the paper's per-partition analytical decision.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dist.sharding import constrain
from .layers import dense_param


def moe_params(key, cfg, n_layers=None):
    d, E, ff = cfg.d_model, cfg.moe_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 7)
    p, a = {}, {}
    p["router"], a["router"] = dense_param(ks[0], d, E, ("embed", None),
                                           n_layers)
    shape = (E, d, ff) if n_layers is None else (n_layers, E, d, ff)
    shape2 = (E, ff, d) if n_layers is None else (n_layers, E, ff, d)
    lx = ("experts", "embed", "ff") if n_layers is None \
        else ("layers", "experts", "embed", "ff")
    lx2 = ("experts", "ff", "embed") if n_layers is None \
        else ("layers", "experts", "ff", "embed")
    sc = 1.0 / np.sqrt(d)
    sc2 = 1.0 / np.sqrt(ff)
    p["w1"] = jax.random.normal(ks[1], shape) * sc
    p["w3"] = jax.random.normal(ks[2], shape) * sc
    p["w2"] = jax.random.normal(ks[3], shape2) * sc2
    a["w1"], a["w3"], a["w2"] = lx, lx, lx2
    if cfg.moe_shared_expert:
        from .layers import mlp_params
        p["shared"], a["shared"] = mlp_params(ks[4], d, ff, n_layers)
    return p, a


def _route(p, cfg, x, dtype):
    """Top-k routing.  Returns (idx [B,S,k], weights [B,S,k])."""
    logits = (x @ p["router"].astype(dtype)).astype(jnp.float32)
    w, idx = jax.lax.top_k(logits, cfg.moe_top_k)
    w = jax.nn.softmax(w, axis=-1)
    return idx, w


def _dispatch_positions(idx, E, capacity):
    """Per-batch-row bin positions (the PPM bin-insertion point walk).

    idx: [B, S, k] expert ids.  Returns pos [B, S, k] position within the
    (row, expert) bin and keep [B, S, k] (capacity drop mask).
    """
    B, S, k = idx.shape

    def per_row(idx_row):                       # [S, k]
        counts = jnp.zeros((E,), jnp.int32)
        poss = []
        for j in range(k):
            oh = jax.nn.one_hot(idx_row[:, j], E, dtype=jnp.int32)  # [S,E]
            ranks = jnp.cumsum(oh, axis=0) - 1                      # [S,E]
            pos_j = jnp.take_along_axis(
                ranks, idx_row[:, j:j + 1], axis=1)[:, 0] \
                + counts[idx_row[:, j]]
            counts = counts + oh.sum(axis=0)
            poss.append(pos_j)
        return jnp.stack(poss, axis=1)          # [S, k]

    pos = jax.vmap(per_row)(idx)
    keep = pos < capacity
    return pos, keep


def moe_fwd_dense(p, cfg, x, *, dtype=jnp.bfloat16):
    """DC-like dense capacity dispatch, data-parallel experts."""
    B, S, d = x.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    cap = int(np.ceil(S * k / E * cfg.moe_capacity))
    idx, wts = _route(p, cfg, x, dtype)
    pos, keep = _dispatch_positions(idx, E, cap)

    # scatter tokens into bins [B, E*cap, d]
    flat_slot = jnp.where(keep, idx * cap + pos, E * cap)       # [B,S,k]
    xe = jnp.zeros((B, E * cap + 1, d), dtype)
    for j in range(k):
        xe = xe.at[jnp.arange(B)[:, None], flat_slot[:, :, j]].add(x)
    xe = xe[:, :-1].reshape(B, E, cap, d)

    # expert FFN (einsum over experts).  Default: experts replicated,
    # ff TP-sharded.  moe_ep: expert-parallel — bins constrained onto the
    # expert shards, which turns the dispatch into the PPM all_to_all
    # (XLA inserts it from the batch->expert sharding transition).
    w1 = p["w1"].astype(dtype)
    w3 = p["w3"].astype(dtype)
    w2 = p["w2"].astype(dtype)
    if cfg.moe_ep:
        xe = constrain(xe, "batch", "model", None, None)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w1)) \
            * jnp.einsum("becd,edf->becf", xe, w3)
        h = constrain(h, "batch", "model", None, None)
        ye = jnp.einsum("becf,efd->becd", h, w2).reshape(B, E * cap, d)
        ye = constrain(ye, "batch", None, None)
    else:
        xe = constrain(xe, "batch", None, None, None)
        h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w1)) \
            * jnp.einsum("becd,edf->becf", xe, w3)
        h = constrain(h, "batch", None, None, "model")
        ye = jnp.einsum("becf,efd->becd", h, w2).reshape(B, E * cap, d)
        ye = constrain(ye, "batch", None, None)
    ye = jnp.concatenate([ye, jnp.zeros((B, 1, d), dtype)], axis=1)

    # combine (gather back with router weights)
    out = jnp.zeros((B, S, d), dtype)
    for j in range(k):
        yj = jnp.take_along_axis(
            ye, flat_slot[:, :, j:j + 1].reshape(B, S, 1), axis=1)
        out = out + yj * (wts[:, :, j] * keep[:, :, j])[..., None].astype(dtype)

    if cfg.moe_shared_expert:
        from .layers import mlp_fwd
        out = out + mlp_fwd(p["shared"], x, dtype)
    return out


def moe_fwd_ppm_ep(p, x=None, mesh_axis="model", *, cfg=None,
                   dtype=jnp.bfloat16):
    """PPM expert-parallel dispatch (inside shard_map over the model axis).

    Must be called under shard_map with ``mesh_axis`` unsplit in x.
    Each shard owns E_loc experts; bins bin[shard][expert] are exchanged
    with one all_to_all per direction — the paper's Scatter/Gather phases.
    """
    Dm = jax.lax.axis_size(mesh_axis)
    B, S, d = x.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    assert E % Dm == 0, "ppm_ep needs experts % model-axis == 0"
    E_loc = E // Dm
    cap = int(np.ceil(S * k / E * cfg.moe_capacity))
    idx, wts = _route(p, cfg, x, dtype)
    pos, keep = _dispatch_positions(idx, E, cap)

    flat_slot = jnp.where(keep, idx * cap + pos, E * cap)
    xe = jnp.zeros((B, E * cap + 1, d), dtype)
    for j in range(k):
        xe = xe.at[jnp.arange(B)[:, None], flat_slot[:, :, j]].add(x)
    xe = xe[:, :-1].reshape(B, E, cap, d)

    # ---- PPM scatter: bins -> owning expert shard ----
    # [B, Dm, E_loc, cap, d] -> all_to_all over Dm
    xe = xe.reshape(B, Dm, E_loc, cap, d).transpose(1, 0, 2, 3, 4)
    xe = jax.lax.all_to_all(xe, mesh_axis, 0, 0)   # rows now = source shards
    # gather phase: this shard's experts process all sources' bins
    xe = xe.transpose(1, 2, 0, 3, 4).reshape(B, E_loc, Dm * cap, d)

    w1 = p["w1"].astype(dtype)    # local slice [E_loc, d, ff] under shard_map
    w3 = p["w3"].astype(dtype)
    w2 = p["w2"].astype(dtype)
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xe, w1)) \
        * jnp.einsum("becd,edf->becf", xe, w3)
    ye = jnp.einsum("becf,efd->becd", h, w2)

    # ---- PPM return scatter ----
    ye = ye.reshape(B, E_loc, Dm, cap, d).transpose(2, 0, 1, 3, 4)
    ye = jax.lax.all_to_all(ye, mesh_axis, 0, 0)
    ye = ye.transpose(1, 0, 2, 3, 4).reshape(B, E * cap, d)
    ye = jnp.concatenate([ye, jnp.zeros((B, 1, d), dtype)], axis=1)

    out = jnp.zeros((B, S, d), dtype)
    for j in range(k):
        yj = jnp.take_along_axis(
            ye, flat_slot[:, :, j:j + 1].reshape(B, S, 1), axis=1)
        out = out + yj * (wts[:, :, j] * keep[:, :, j])[..., None].astype(dtype)
    if cfg.moe_shared_expert:
        from .layers import mlp_fwd
        out = out + mlp_fwd(p["shared"], x, dtype, constrained=False)
    return out


def moe_fwd_ppm_ep_sharded(p, cfg, x, *, dtype=jnp.bfloat16):
    """shard_map wrapper for the explicit PPM dispatch: called from inside
    the (auto-sharded) model; drops into manual collectives over the model
    axis.  Falls back to dense_dp when no mesh is active (tests) or the
    expert count does not divide the model axis (mixtral on 16-way TP)."""
    from ..dist.sharding import _ACT_MESH, _collapse, _data_axes
    mesh = _ACT_MESH[0]
    if mesh is None or "model" not in mesh.axis_names \
            or cfg.moe_experts % mesh.shape["model"] != 0:
        return moe_fwd_dense(p, cfg, x, dtype=dtype)
    from jax.sharding import PartitionSpec as P
    db = _collapse(_data_axes(mesh))

    def spec_of(path_leaf):
        name = path_leaf[0].key if hasattr(path_leaf[0], "key") else ""
        return name

    # per-leaf specs: expert tensors sharded on E over model; rest replicated
    def leaf_spec(path, leaf):
        keys = [getattr(k, "key", "") for k in path]
        if keys[0] in ("w1", "w3", "w2"):
            return P("model", *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    if x.shape[1] % mesh.shape["model"] != 0:
        return moe_fwd_dense(p, cfg, x, dtype=dtype)
    p_specs = jax.tree_util.tree_map_with_path(leaf_spec, p)
    fn = functools.partial(moe_fwd_ppm_ep, cfg=cfg, dtype=dtype)
    # tokens are sequence-split over the model axis: each shard dispatches
    # ONLY its S/Dm token slice (x replicated over model would make every
    # shard bin the same tokens - a 16x compute redundancy, observed)
    return jax.shard_map(
        lambda pp, xx: fn(pp, x=xx),
        mesh=mesh,
        in_specs=(p_specs, P(db, "model", None)),
        out_specs=P(db, "model", None),
        check_vma=False,
    )(p, x)


def moe_fwd(p, cfg, x, *, impl=None, dtype=jnp.bfloat16, **kw):
    impl = impl or cfg.moe_impl
    if impl == "ppm_ep":
        return moe_fwd_ppm_ep_sharded(p, cfg, x, dtype=dtype)
    return moe_fwd_dense(p, cfg, x, dtype=dtype)


def moe_ref(p, cfg, x):
    """Oracle: loop over tokens/experts in fp32, no capacity drops."""
    B, S, d = x.shape
    idx, wts = _route(p, cfg, x, jnp.float32)
    out = np.zeros((B, S, d), np.float32)
    xn = np.asarray(x, np.float32)
    w1 = np.asarray(p["w1"], np.float32)
    w3 = np.asarray(p["w3"], np.float32)
    w2 = np.asarray(p["w2"], np.float32)
    idx = np.asarray(idx)
    wts = np.asarray(wts)

    def silu(v):
        return v / (1.0 + np.exp(-v))

    for b in range(B):
        for s in range(S):
            for j in range(cfg.moe_top_k):
                e = idx[b, s, j]
                h = silu(xn[b, s] @ w1[e]) * (xn[b, s] @ w3[e])
                out[b, s] += wts[b, s, j] * (h @ w2[e])
    if cfg.moe_shared_expert:
        for b in range(B):
            for s in range(S):
                sh = p["shared"]
                h = silu(xn[b, s] @ np.asarray(sh["w1"], np.float32)) \
                    * (xn[b, s] @ np.asarray(sh["w3"], np.float32))
                out[b, s] += h @ np.asarray(sh["w2"], np.float32)
    return out
