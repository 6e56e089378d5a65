"""The GPOP user API (paper §4.1), adapted to array semantics.

The paper steers applications through four scalar callbacks plus an optional
weight hook.  Here each callback is vectorized over the (padded) vertex space;
the engine applies the activity masks, so user code never sees parallelism,
partitioning, or communication — the same contract as the paper:

  scatter_fn(state)                 ≙ scatterFunc(node)    value sent to out-neighbors
  init_fn(state, it)                ≙ initFunc(node)       selective frontier continuity
  apply_fn(state, acc, touched, it) ≙ gatherFunc(val,node) fold result -> update + activate
  filter_fn(state, it)              ≙ filterFunc(node)     final frontier filtering
  apply_weight(vals, w)             ≙ applyWeight(val,wt)
  aggregate(state)                  ≙ aggregator (Pregel)  summed over the graph -> filter_fn

``state`` is a pytree of per-vertex arrays with leading dim ``n_pad``.
``apply_weight`` must preserve the monoid identity (identity ∘ w = identity) —
true for the paper's usage (min-monoid with val+wt, add-monoid with val*wt).
The gather fold itself is the program's ``monoid`` (see monoid.py for why
associativity is required on TPU).

``aggregate`` is the one global value a program may ask for: the engine sums
``aggregate(state)`` over the real vertices (ids below ``n``, not padding) of
the state each superstep starts with, across every device, and passes the sum
to ``filter_fn`` as a third argument, ``filter_fn(state, it, agg)``.  A
program without it (the default) computes no such sum: its step programs do
not hold one.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from . import monoid as monoid_lib


@dataclasses.dataclass
class VertexProgram:
    name: str
    monoid: monoid_lib.Monoid
    scatter_fn: Callable                      # (state) -> msgs[n_pad]
    apply_fn: Callable                        # (state, acc, touched, it) -> (state, activated)
    init_fn: Optional[Callable] = None        # (state, it) -> (state, keep)
    filter_fn: Optional[Callable] = None      # (state, it) -> (state, keep)
    apply_weight: Optional[Callable] = None   # (vals, w) -> vals
    aggregate: Optional[Callable] = None      # (state) -> vals[n_pad]

    def filter(self, state, it, agg=None):
        """``filter_fn(state, it)``, with ``agg`` as a third argument where
        the program has an aggregate."""
        if self.aggregate is None:
            return self.filter_fn(state, it)
        return self.filter_fn(state, it, agg)
