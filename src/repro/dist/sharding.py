"""Logical-axis -> mesh-axis sharding rules for every multi-device path.

Model params carry twin "logical axes" pytrees (``init_lm`` returns
``(params, axes)``; leaves are tuples like ``("embed", "heads")``).  This
module owns the single mapping from those names onto mesh axes:

  * ``default_rules(mesh, cfg)``   the rule table (FSDP data axes for
    ``embed``/``batch``, tensor-parallel ``model`` for heads/kv/ff/vocab),
    with per-config overrides via ``cfg.sharding_overrides``;
  * ``spec_for(axes, shape, ...)`` rules -> ``PartitionSpec`` with two
    guards: a dim that does not divide its mesh-axis extent is replicated,
    and each mesh axis is consumed at most once per tensor;
  * ``param_shardings``            the whole-params-tree application;
  * ``constrain`` / ``set_activation_mesh``  activation sharding hints
    inside jitted model code (no-ops until a mesh is activated);
  * ``batch_spec`` / ``graph_spec``  the two non-param layouts: LM batches
    over the data axes, PPM graph arrays over ALL axes flattened.
"""
from __future__ import annotations

import jax
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

# Activation-constraint mesh, a one-element box so model code can read the
# *current* mesh at trace time (``_ACT_MESH[0]``).
_ACT_MESH = [None]


def set_activation_mesh(mesh):
    """Activate (or with ``None`` deactivate) ``constrain`` for model code
    traced after this call."""
    _ACT_MESH[0] = mesh


def _data_axes(mesh):
    """Mesh axes that carry batch-parallel / FSDP work, mesh order."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _collapse(axes):
    """() -> None, (a,) -> a, longer tuples unchanged (PartitionSpec
    equality distinguishes ``"data"`` from ``("data",)``)."""
    if not axes:
        return None
    if isinstance(axes, str):
        return axes
    return axes[0] if len(axes) == 1 else tuple(axes)


def default_rules(mesh, cfg=None):
    """Logical-axis -> mesh-axis table for ``mesh``.

    ``cfg.sharding_overrides`` (``((logical, mesh_axis_or_None), ...)``)
    rewrites individual entries — the hillclimb lever for e.g. attn-DP or
    expert-parallel variants.  Axes absent from the mesh map to None.
    """
    names = tuple(mesh.axis_names)
    data = _collapse(_data_axes(mesh))
    model = "model" if "model" in names else None
    rules = {
        "batch": data,
        "embed": data,        # FSDP: weights sharded over all data axes
        "vocab": model,
        "heads": model,
        "kv": model,
        "ff": model,
        "ssm_inner": model,
        "ssm_heads": model,
        "experts": None,      # dense_dp default: experts replicated
        "layers": None,       # scan dimension, never sharded
    }
    if cfg is not None:
        for logical, axis in getattr(cfg, "sharding_overrides", ()) or ():
            rules[logical] = axis
    return rules


def _place(assignment, dim, mesh, used):
    """One spec entry: ``assignment`` if it is a known, unconsumed mesh
    axis (or tuple) whose extent divides ``dim``, else None (replicate)."""
    if assignment is None:
        return None
    flat = (assignment,) if isinstance(assignment, str) else tuple(assignment)
    if any(a not in mesh.axis_names for a in flat):
        return None
    if any(a in used for a in flat):
        return None
    extent = int(np.prod([mesh.shape[a] for a in flat]))
    if extent <= 0 or dim % extent != 0:
        return None
    used.update(flat)
    return assignment


def spec_for(axes, shape, mesh, rules):
    """PartitionSpec for one tensor from its logical ``axes`` tuple.

    Guards: non-divisible dims are replicated, and each mesh axis is
    consumed at most once (first logical axis mapped to it wins).
    """
    assert len(axes) <= len(shape), \
        f"more logical axes {axes} than dims {shape}"
    used = set()
    entries = []
    for ax, dim in zip(axes, shape):
        assignment = rules.get(ax) if ax is not None else None
        entries.append(_place(assignment, int(dim), mesh, used))
    return P(*entries)


def batch_spec(mesh):
    """[batch, seq] layout: batch over all data axes, seq replicated."""
    return P(_collapse(_data_axes(mesh)), None)


def graph_spec(mesh):
    """PPM graph arrays: the device dimension over ALL mesh axes flattened
    (the bin exchange treats the pod mesh as one flat all_to_all group)."""
    return P(tuple(mesh.axis_names))


def constrain(x, *entries):
    """``with_sharding_constraint`` via logical names, guarded.

    ``entries`` name one spec entry per leading dim of ``x``: ``"batch"``
    (the data axes), a literal mesh axis name/tuple, or None.  Dims whose
    extent does not divide, axes already consumed, and axes missing from
    the active mesh all fall back to replicated — the guard never errors.
    A no-op until ``set_activation_mesh`` installs a mesh (single-device
    tests, shard_map bodies).
    """
    mesh = _ACT_MESH[0]
    if mesh is None:
        return x
    used = set()
    spec = []
    for dim, e in zip(x.shape, entries):
        if e == "batch":
            e = _collapse(_data_axes(mesh))
        spec.append(_place(e, int(dim), mesh, used))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))


def param_shardings(axes_tree, params, mesh, rules=None):
    """NamedSharding tree for a params tree from its logical-axes twin.

    ``params`` leaves only need ``.shape`` (arrays or ShapeDtypeStructs).
    ``rules`` defaults to ``default_rules(mesh)``; pass an amended dict for
    variants (e.g. ZeRO-1 drops the ``embed`` FSDP rule for compute params).
    """
    if rules is None:
        rules = default_rules(mesh)

    def is_axes_leaf(x):
        return isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x)

    def one(axes, p):
        return NamedSharding(mesh, spec_for(axes, p.shape, mesh, rules))

    return jax.tree_util.tree_map(one, axes_tree, params,
                                  is_leaf=is_axes_leaf)
