"""Multi-device machinery: sharding rules and the PPM engine.

GPOP executes graph algorithms as partition-parallel BSP supersteps
(paper §3; DESIGN.md §2), and each superstep maps onto the mesh like so:

  Scatter   every partition streams its active vertices' messages into
            per-destination-partition bins — local, cache-resident writes
            on whichever device owns the partition;
  Sync      the bin exchange, the superstep's only communication: one
            ``all_to_all`` over ALL mesh axes flattened into a single
            device group (``sharding.graph_spec`` lays the graph's
            partition dimension over the full axis tuple, so a 2x16x16
            pod mesh is one 512-way exchange);
  Gather    every partition folds the bins it owns with the app monoid —
            again local to the owning device.

The LM stack reuses the same mesh with named roles instead of the flat
group: ``pod``/``data`` axes carry batch-parallel + FSDP work and
``model`` carries tensor-parallel shards (``sharding.default_rules``).

Modules:
  sharding  logical-axis -> mesh-axis rules, spec construction, activation
            constraints, whole-tree param shardings;
  engine    ``DistEngine`` — the multi-device PPM engine itself.
"""
from .sharding import (batch_spec, constrain, default_rules, graph_spec,
                       param_shardings, set_activation_mesh, spec_for)

__all__ = ["batch_spec", "constrain", "default_rules",
           "graph_spec", "param_shardings", "set_activation_mesh",
           "spec_for"]
