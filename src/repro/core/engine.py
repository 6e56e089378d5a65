"""The PPM engine: scatter → initFrontier → exchange → gather → filter.

Single-device engine over a partition-centric :class:`repro.graph.layout.Layout`.
Each iteration follows paper Alg. 3/4 exactly:

  1. *Scatter*: active vertices produce messages.  Per-partition mode choice
     (Eq. 1 cost model):
       - **DC stream**: registry kernel ``fused_dc`` gathers each edge's
         source message straight from the vertex table and folds it
         (the adjacency side is static, the paper's pre-written
         ``dc_bin``; no bin buffer is materialized).  Sources that are
         inactive or in SC-mode partitions carry the monoid identity,
         which makes their edges exact no-ops in the fold — the
         array-semantics equivalent of the paper's "scatter the whole
         partition" correctness contract.
       - **SC stream**: the CSR adjacency of the active vertices of SC-mode
         partitions is expanded into a `(value, dst)` message list.  The
         buffers are sized by power-of-four *budgets* (:func:`_sc_budget`)
         so the compute is proportional to the active edge count (rounded
         up) — the static-shape realization of the paper's theoretical
         efficiency.
  2. *initFrontier*: ``init_fn`` on active vertices → selective continuity.
  3. *Gather*: one segmented monoid fold per stream into the (VMEM-resident,
     on TPU) vertex tile, plus a `touched` fold; ``apply_fn`` updates touched
     vertices and proposes activations.
  4. *filterFrontier*: ``filter_fn`` on the union frontier.

The 2-level active list appears as: per-partition active counts drive the mode
decision and exclude empty partitions entirely (gPartList); inside the DC
stream the partition mask marks the sources of non-DC partitions invalid
(binPartList).
"""
from __future__ import annotations

import functools
import time
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..backend import registry as kregistry
from ..graph.layout import Layout
from .cost import CostModel
from .program import VertexProgram


def _tree_where(mask, new, old):
    def sel(a, b):
        m = mask.reshape(mask.shape + (1,) * (a.ndim - mask.ndim))
        return jnp.where(m, a, b)
    return jax.tree_util.tree_map(sel, new, old)


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x - 1).bit_length())


#: smallest SC edge budget; budgets grow by powers of four from here
SC_MIN_BUDGET = 1 << 12


def _sc_budget(edges: int, cap: int) -> int:
    """Static SC stream size for ``edges`` active edges: the next power of
    four from :data:`SC_MIN_BUDGET`, at most ``cap``.  Every distinct
    budget compiles its own SC program — on a TPU the fold's bucket sort
    takes seconds to compile — so the grid is coarse; the SC stream pays
    at most 4x the active edges for it."""
    b = SC_MIN_BUDGET
    while b < edges:
        b *= 4
    return min(b, cap)


# The per-iteration stat records live in the obs schema
# (repro.obs.schema).  The old module-level aliases here are a
# deprecation shim: accessing them still works but warns — import from
# repro.obs.schema (or repro.obs) instead.  Internal code already does.
def __getattr__(name):
    if name in ("IterStats", "BatchIterStats"):
        import warnings
        warnings.warn(
            f"repro.core.engine.{name} is deprecated; import it from "
            "repro.obs.schema", DeprecationWarning, stacklevel=2)
        return getattr(obs, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _compact_lane_index(lane_act: np.ndarray):
    """Surviving lane indices packed to the next power-of-two width.

    Padding repeats the first survivor, whose duplicate rows compute
    identical values, so scattering the packed results back with
    ``.at[idx].set`` is deterministic; the pow2 width keeps the per-width
    jit cache at log2(B) entries."""
    idx_r = np.nonzero(lane_act)[0]
    W = _next_pow2(len(idx_r))
    idx = np.concatenate([idx_r, np.full(W - len(idx_r), idx_r[0])])
    return jnp.asarray(idx, jnp.int32), W


def _run_batched_loop(step_for_width, states, active, max_iters: int,
                      until_empty: bool, collect_stats: bool,
                      engine_name: str = "core", program: str = "",
                      wire_bytes_fn=None):
    """Host-driven batched convergence loop shared by
    :meth:`Engine.run_batched` and
    :meth:`repro.dist.engine.DistEngine.run_batched`.

    ``step_for_width(W)`` returns the jitted batched iteration for lane
    width ``W`` — ``fn(states, active, it) -> (states, active)`` over
    ``[W, ...]`` leaves.  The *union* frontier drives convergence; between
    steps converged lanes are compacted out of the batch entirely (packed
    to pow2 widths via :func:`_compact_lane_index`).

    Telemetry (``repro.obs``): per-step ``batch_iter`` events and a
    step-wall histogram when ``collect_stats`` and obs are both on, and a
    ``lane_compaction`` event whenever converged lanes are repacked.
    Everything recorded is already host-resident (``lane_act`` drives the
    loop), so ``collect_stats=False`` adds zero device syncs regardless
    of the obs switch.  ``wire_bytes_fn(n_lanes)``, when given, prices
    the step's analytic exchange payload into the event."""
    B = active.shape[0]
    tmap = jax.tree_util.tree_map
    stats = []
    for it in range(max_iters):
        lane_act = np.asarray(active.any(axis=1))
        n_lanes = int(lane_act.sum())
        if n_lanes == 0:
            if until_empty:
                break
            continue    # every phase masks on active: a no-op step
        t0 = time.perf_counter()
        n_act = int(jnp.sum(active)) if collect_stats else 0
        if n_lanes == B:
            W = B
            states, active = step_for_width(B)(states, active,
                                               jnp.int32(it))
        else:
            # lane compaction: converged lanes drop out of the batch
            # instead of riding along as frozen flops
            idx, W = _compact_lane_index(lane_act)
            if obs.enabled():
                obs.event("lane_compaction", engine=engine_name,
                          program=program, it=it, lanes_active=n_lanes,
                          width=W, batch=B)
            sub_states = tmap(lambda a: a[idx], states)
            sub_states, sub_active = step_for_width(W)(
                sub_states, active[idx], jnp.int32(it))
            states = tmap(lambda f, p: f.at[idx].set(p),
                          states, sub_states)
            active = active.at[idx].set(sub_active)
        jax.block_until_ready(active)
        wall = time.perf_counter() - t0
        if collect_stats:
            stats.append(obs.BatchIterStats(
                it=it, lanes_active=n_lanes, n_active=n_act, wall_s=wall))
            if obs.enabled():
                wire = (int(wire_bytes_fn(n_lanes))
                        if wire_bytes_fn is not None else None)
                extra = {} if wire is None else {"wire_bytes": wire}
                obs.event("batch_iter", engine=engine_name,
                          program=program, it=it, lanes_active=n_lanes,
                          n_active=n_act, width=W, wall_s=wall, **extra)
                obs.observe("engine.batch_step_wall_s", wall,
                            engine=engine_name, program=program or "?")
    return states, active, stats


class Engine:
    """Single-device PPM engine.

    mode: 'hybrid' (paper's GPOP), 'dc' (GPOP_DC), 'sc' (GPOP_SC).
    backend: kernel backend for the DC step and the SC fold — a name from
    :mod:`repro.backend.registry` ('ref', 'pallas-interpret',
    'pallas-native'), a KernelBackend instance, or None to auto-select
    from the platform / REPRO_KERNEL_BACKEND.
    use_pallas: deprecated alias (True -> backend='pallas-interpret',
    False -> backend='ref').
    """

    def __init__(self, layout: Layout, program: VertexProgram,
                 mode: str = "hybrid", bw_ratio: float = 2.0,
                 backend: Union[str, "kregistry.KernelBackend", None] = None,
                 use_pallas: Optional[bool] = None):
        assert mode in ("hybrid", "dc", "sc")
        if use_pallas is not None:
            warnings.warn(
                "Engine(use_pallas=...) is deprecated; pass "
                "backend='pallas-interpret' / 'ref' instead",
                DeprecationWarning, stacklevel=2)
            if backend is None:
                backend = "pallas-interpret" if use_pallas else "ref"
        self.layout = layout
        self.program = program
        self.mode = mode
        self.cost = CostModel.from_layout(layout, bw_ratio=bw_ratio)
        L = layout
        self.k, self.q, self.n_pad = L.k, L.q, L.n_pad

        # kernel construction goes through the backend registry; each
        # kernel may fall back to 'ref' on its own when the chosen
        # backend has no lowering for this (monoid, dtype, platform)
        kset = kregistry.make_kernels(layout, program.monoid,
                                      backend=backend)
        self.kernels = kset
        self.backend_names = kset.names
        self.use_pallas = kset.any_pallas          # introspection compat
        # SC-stream monoid fold + touched flags through registry kernel
        # 'fold' (the blocked Pallas fold by default — flat below
        # REPRO_FOLD_MAX_SEGMENTS, two-level above, both carrying the
        # layout's tuned fold_tile/fold_q; budgets are static per
        # compiled step, so the stream shape is known at trace time)
        self._fold = kset.fold
        # the DC step (registry kernel 'fused_dc'): one call that
        # gathers each edge's source message and folds it
        self._fused = kset.fused
        self._fused.apply_weight = (
            program.apply_weight
            if (program.apply_weight is not None
                and L.edge_w is not None) else None)

        # device-resident static structure the SC stream reads (the DC
        # step keeps its own in self._fused.arrays).  Every jitted step
        # takes these as arguments (see _args) — jit would embed
        # closed-over arrays in the program as constants, gigabytes at
        # chip scale.
        arrays = {
            "csr_indptr": L.csr_indptr, "csr_indices": L.csr_indices,
            "csr_w": L.csr_w, "deg": L.deg.astype(np.int32)}
        self.arrays = {k: None if v is None else jnp.asarray(v)
                       for k, v in arrays.items()}

        # per-partition reductions used by the host-side mode decision;
        # partitions are contiguous q-vertex ranges, so a reshape-sum
        # (over a leading lane axis too, for the lockstep batched loop)
        @jax.jit
        def _part_stats(active, deg):
            a32 = active.astype(jnp.int32)
            lead = a32.shape[:-1]
            return (a32.reshape(lead + (L.k, L.q)).sum(-1),
                    (a32 * deg).reshape(lead + (L.k, L.q)).sum(-1))
        self._part_stats = lambda active: _part_stats(active,
                                                      self.arrays["deg"])
        self._step_cache = {}            # SC budget / phase -> jitted fn
        self._dc_one_gather = None       # the last DC phase's path
        self._sc_cap = _next_pow2(max(int(L.deg.sum()), 1))

    def _args(self):
        """Every device array a jitted step reads, passed as an argument:
        the engine's own, then the DC step's."""
        return {"engine": self.arrays, "fused": self._fused.arrays}

    # ------------------------------------------------------------------
    def _step_fn(self):
        """Jitted DC-only iteration ``fn(state, active, dc_mask, it,
        args)``, ``args`` being :meth:`_args`, for the batched and
        fixed-iteration loops; cached per instance (an lru_cache on the
        method would pin ``self`` — layout arrays included — for the
        process lifetime).  :meth:`run` runs its phases as separate
        programs instead (:meth:`_phase_fns`)."""
        fn = self._step_cache.get("step")
        if fn is None:
            def step(state, active, dc_mask, it, args):
                agg = self._aggregate(state)
                state, keep, acc, touched, _ = self._dc_phase(
                    state, active, dc_mask, it, args)
                return self._apply_phase(state, keep, acc, touched, it, agg)
            fn = self._step_cache["step"] = jax.jit(step)
        return fn

    def _aggregate(self, state):
        """The program's aggregate (:class:`VertexProgram` ``aggregate``)
        summed over the real vertices, ids below ``n``; None for a
        program without one."""
        if self.program.aggregate is None:
            return None
        vals = self.program.aggregate(state)
        real = jnp.arange(vals.shape[-1]) < self.layout.n
        return jnp.sum(jnp.where(real, vals, 0), axis=-1)

    def _phase_fns(self, be: int, dc: bool = True):
        """``(dc, sc, apply)`` jitted phases of one superstep.  The DC
        stream and the apply phase compile once per engine; only the SC
        stream compiles per budget, so a deep frontier's many budgets do
        not each recompile the DC stream's whole-edge-set fold.  With
        ``dc=False`` (no DC partition this step) the first phase skips
        the DC stream and returns identity accumulators.  A program with
        an aggregate gets one more program, ``aggregate``, which
        :meth:`_superstep` runs first."""
        if "dc" not in self._step_cache:
            self._step_cache["dc"] = jax.jit(self._dc_phase)
            self._step_cache["no_dc"] = jax.jit(
                functools.partial(self._dc_phase, stream=False))
            self._step_cache["apply"] = jax.jit(self._apply_phase)
            if self.program.aggregate is not None:
                self._step_cache["aggregate"] = jax.jit(self._aggregate)
        key = ("sc", be)
        if be and key not in self._step_cache:
            self._step_cache[key] = jax.jit(
                functools.partial(self._sc_phase, be))
        return (self._step_cache["dc" if dc else "no_dc"],
                self._step_cache.get(key), self._step_cache["apply"])

    def _dc_phase(self, state, active, dc_mask, it, args,
                  stream: bool = True):
        """Scatter, initFrontier and the DC stream (paper Alg. 2:
        values-only messages over the pre-written dc_bin adjacency).
        Returns ``(state, keep, acc, touched, one_gather)``, the
        accumulators over ``n_pad + 1`` slots, the last the identity
        sentinel; ``one_gather`` is the fused kernel's report of its path
        (None where the stream is skipped).  ``stream=False`` skips the
        DC stream, whose result is the identity when ``dc_mask`` is all
        false.  The messages are not an output: the SC phase scatters
        its own, and on a TPU XLA keeps the fused kernel's masked table
        in on-chip memory only while the unmasked one is no output."""
        prog, mono, n_pad = self.program, self.program.monoid, self.n_pad
        A = args["engine"]
        dc_v = self._vertex_mask(dc_mask)
        msgs = prog.scatter_fn(state).astype(mono.dtype)      # [n_pad]

        # ---- initFrontier (selective continuity) ----
        if prog.init_fn is not None:
            st2, keep = prog.init_fn(state, it)
            state = _tree_where(active, st2, state)
            keep = keep & active
        else:
            keep = jnp.zeros((n_pad,), jnp.bool_)

        if not stream:
            return (state, keep, mono.identity_array((n_pad + 1,)),
                    jnp.zeros((n_pad + 1,), jnp.bool_), None)
        # the kernel gathers each edge's source value from msgs_p and
        # folds it into the accumulators.  A vertex without out-edges
        # feeds no edge: marking it invalid keeps its message (PageRank's
        # 0.0, the add identity) from forcing the two-gather path
        msgs_p = jnp.concatenate([msgs, mono.identity_array((1,))])
        table_valid = jnp.concatenate(
            [active & dc_v & (A["deg"] > 0), jnp.zeros((1,), jnp.bool_)])
        acc, touched, one_gather = self._fused(msgs_p, table_valid,
                                               arrays=args["fused"])
        return state, keep, acc, touched, one_gather

    def _vertex_mask(self, dc_mask):
        """``dc_mask`` [k] per vertex: partition p holds vertices
        ``p * q`` to ``(p + 1) * q - 1``.  (A lookup by a per-vertex
        partition id lowers to a chain of k selects, after which XLA
        puts the fused kernel's masked table in HBM.)"""
        return jnp.repeat(dc_mask, self.q)

    def _sc_phase(self, be, state, active, dc_mask, acc, touched, args):
        """The SC stream under a static edge budget ``be``: the CSR rows
        of the active vertices of SC partitions, expanded into ``be``
        (value, dst) slots and folded into ``acc`` / ``touched``.
        ``state`` is the superstep's state before initFrontier, which
        the DC phase scattered from too."""
        prog, mono, n_pad = self.program, self.program.monoid, self.n_pad
        A = args["engine"]
        msgs_p = jnp.concatenate([prog.scatter_fn(state).astype(mono.dtype),
                                  mono.identity_array((1,))])
        sc_active = active & ~self._vertex_mask(dc_mask)
        degs = jnp.where(sc_active, A["deg"], 0)               # [n_pad]
        start = jnp.cumsum(degs) - degs       # first slot of each row
        total = start[-1] + degs[-1]
        j = jnp.arange(be, dtype=jnp.int32)
        # slot j belongs to the last nonempty row starting at or before
        # j: mark each row's first slot with its vertex and carry the
        # marks forward (a binary search would gather log2(n_pad) times
        # per slot); slots past the total are padding
        first = jnp.where((degs > 0) & (start < be), start, be)
        src_v = jax.lax.cummax(jnp.zeros((be,), jnp.int32).at[first].max(
            jnp.arange(n_pad, dtype=jnp.int32), mode="drop"))
        valid = j < total
        row_off = A["csr_indptr"][:n_pad].astype(jnp.int32) - start
        e_idx = jnp.where(valid, j + row_off[src_v], 0)
        dst = jnp.where(valid, A["csr_indices"][e_idx],
                        n_pad).astype(jnp.int32)
        vals = msgs_p[src_v]
        if prog.apply_weight is not None and A["csr_w"] is not None:
            vals = prog.apply_weight(vals, A["csr_w"][e_idx])
        vals = jnp.where(valid, vals, mono.identity)
        acc2, touched2 = self._fold(vals, valid, dst, n_pad + 1)
        return mono.combine(acc, acc2), touched | touched2

    def _apply_phase(self, state, keep, acc, touched, it, agg=None):
        """Gather apply and filterFrontier on the union frontier; ``agg``
        is the superstep's :meth:`_aggregate`, which ``filter_fn`` takes
        where the program has one."""
        prog, n_pad = self.program, self.n_pad
        acc, touched = acc[:n_pad], touched[:n_pad]
        st3, activated = prog.apply_fn(state, acc, touched, it)
        state = _tree_where(touched, st3, state)
        new_active = keep | (activated & touched)
        if prog.filter_fn is not None:
            st4, fkeep = prog.filter(state, it, agg)
            state = _tree_where(new_active, st4, state)
            new_active = new_active & fkeep
        return state, new_active

    # ------------------------------------------------------------------
    def run(self, state=None, frontier=None, max_iters: int = 10_000,
            until_empty: bool = True, collect_stats: bool = True, *,
            resume_from=None, touched=None):
        """Host-driven loop: per-iteration mode decision (paper Eq. 1).

        ``resume_from=``/``touched=`` is the incremental-recompute entry
        point for dynamic graphs: pass a *previously converged* state
        (from a run on the pre-delta layout) as ``resume_from`` and the
        delta-touched vertices (``DeltaBuffer.touched()``) as ``touched``,
        and the loop restarts from the old fixpoint with only the touched
        vertices on the initial frontier.

        Exactness contract: for a *min-monoid* program (BFS / SSSP / CC)
        after an **insertion-only** delta this converges to exactly the
        cold fixpoint of the new graph.  The old fixpoint satisfies every
        old edge, insertions can only *lower* the least fixpoint, so the
        old state is a pointwise upper bound whose only violated
        constraints start at touched vertices — relaxation from there
        repairs every consequence and, by the least-fixpoint uniqueness
        argument (see :mod:`repro.serve.cache`), lands bit-exactly on the
        cold answer.  After deletions values may need to *rise*, which
        monotone relaxation cannot do: run cold instead.  Non-min monoids
        (PageRank) resume via residuals — a warm init reaches the unique
        damping-contraction fixpoint in fewer sweeps (see
        :func:`repro.apps.pagerank.pagerank`'s ``pr0``)."""
        if resume_from is not None:
            if state is not None:
                raise ValueError("pass either state= or resume_from=, "
                                 "not both")
            if touched is None:
                raise ValueError("resume_from= needs touched= (the "
                                 "delta-touched initial frontier, or the "
                                 "DeltaBuffer itself)")
            # `touched` may be the DeltaBuffer itself (preferred: the
            # boolean mask cannot carry the insert/delete distinction the
            # exactness contract depends on).  Deletion deltas must NOT
            # quietly recompute from the old fixpoint: monotone
            # relaxation can only lower values, so the resumed run would
            # CONVERGE — to a wrong (stale-upper-bound) answer.
            from ..graph.delta import DeltaBuffer
            if isinstance(touched, DeltaBuffer):
                if touched.num_deletes:
                    raise ValueError(
                        "resume_from= is exact only for insertion-only "
                        f"deltas; this delta removes {touched.num_deletes}"
                        " edge(s) and deleted edges may require values to "
                        "rise, which monotone relaxation cannot do — run "
                        "cold (state=/frontier=) on the new layout "
                        "instead")
                touched = touched.touched()
            if self.program.monoid.name not in ("min", "max", "or",
                                                "min_with_payload"):
                raise ValueError(
                    "resume_from= requires an idempotent monoid (min/max/"
                    f"or): re-folding under {self.program.monoid.name!r} "
                    "double-counts contributions already absorbed into "
                    "the old fixpoint — PageRank-style programs resume "
                    "via the residual path (pagerank(pr0=)) instead")
            state, frontier = resume_from, touched
        if state is None or frontier is None:
            raise ValueError("run() needs state+frontier (or "
                             "resume_from=+touched=)")
        active = jnp.asarray(frontier, jnp.bool_)
        stats = []
        # host spans (repro.obs.annotation): one engine.superstep per
        # pass, the last pass's (the empty frontier's) included; inside
        # it engine.part_stats, engine.split, engine.dispatch,
        # engine.sync and engine.record, in that order
        with obs.annotation("engine.run", program=self.program.name,
                            mode=self.mode):
            for it in range(max_iters):
                with obs.annotation("engine.superstep", it=it) as span:
                    with obs.annotation("engine.part_stats"):
                        counts, ea = self._part_stats(active)
                        counts, ea = np.asarray(counts), np.asarray(ea)
                    n_active = int(counts.sum())
                    if until_empty and n_active == 0:
                        break
                    t0 = time.perf_counter()
                    state, active, dc_mask, split = self._superstep(
                        state, active, it, counts, ea)
                    with obs.annotation("engine.sync"):
                        jax.block_until_ready(active)
                        wall = time.perf_counter() - t0
                    span.set_metadata(**split)
                    if collect_stats:
                        with obs.annotation("engine.record"):
                            stats.append(self._record_iter(
                                it, n_active, ea, counts, dc_mask, split,
                                wall))
        return state, active, stats

    def _superstep(self, state, active, it: int, counts, ea):
        """One superstep from the host-side partition stats ``counts`` /
        ``ea`` (active vertices / active edges per partition): the Eq. 1
        split (span ``engine.split``), then the DC, SC and apply phases
        (span ``engine.dispatch``).  Returns ``(state, active, dc_mask,
        split)``, ``split`` the superstep's partitions and active edges
        by stream, its SC budget (0: no SC stream) and the phase programs
        built for it (``new_programs``: their first call compiles).  The
        fused DC kernel's report of its path, a device scalar (None
        where the DC stream is skipped), is kept as ``_dc_one_gather``
        for the record."""
        with obs.annotation("engine.split"):
            has_active = counts > 0
            if self.mode == "dc":
                dc_mask = has_active
            elif self.mode == "sc":
                dc_mask = np.zeros(self.k, bool)
            else:
                dc_mask = self.cost.choose_dc(ea, has_active)
            sc_sel = (~dc_mask) & has_active
            sc_e = int(ea[sc_sel].sum())
            # SC edge budget: the active edges of SC partitions, rounded
            # up to the budget grid (0: no SC stream this step)
            be = _sc_budget(sc_e, self._sc_cap) if sc_sel.any() else 0
            built = len(self._step_cache)
            dc_fn, sc_fn, apply_fn = self._phase_fns(be, dc_mask.any())
            split = dict(dc_parts=int(dc_mask.sum()),
                         sc_parts=int(sc_sel.sum()),
                         dc_e=int(ea[dc_mask].sum()), sc_e=sc_e,
                         sc_budget=be,
                         new_programs=len(self._step_cache) - built)
        with obs.annotation("engine.dispatch"):
            args, mask = self._args(), jnp.asarray(dc_mask)
            it32 = jnp.int32(it)
            state0 = state
            agg_fn = self._step_cache.get("aggregate")
            agg = None if agg_fn is None else agg_fn(state0)
            state, keep, acc, touched, self._dc_one_gather = dc_fn(
                state, active, mask, it32, args)
            if be:
                acc, touched = sc_fn(state0, active, mask, acc, touched,
                                     args)
            state, active = apply_fn(state, keep, acc, touched, it32, agg)
        return state, active, dc_mask, split

    def _record_iter(self, it, n_active, ea, counts, dc_mask, split, wall):
        """The superstep's :class:`~repro.obs.IterStats`, also recorded
        through :func:`repro.obs.record_engine_iter` with the active
        edges of each stream (``dc_e`` / ``sc_e``) and, where the fused
        DC kernel ran, whether it gathered each edge once
        (``dc_one_gather``; read after the sync, so it waits on
        nothing)."""
        b = self.cost.bytes_for(dc_mask, ea, counts > 0)
        dc_p, sc_p = split["dc_parts"], split["sc_parts"]
        mode_str = "dc" if sc_p == 0 else "sc" if dc_p == 0 else "hybrid"
        st = obs.IterStats(
            it=it, n_active=n_active, e_active=int(ea.sum()),
            dc_parts=dc_p, sc_parts=sc_p,
            dc_bytes=b["dc_bytes"], sc_bytes=b["sc_bytes"],
            wall_s=wall, mode=mode_str, program=self.program.name,
            sc_budget=split["sc_budget"])
        extra = {}
        if self._dc_one_gather is not None and obs.enabled():
            extra["dc_one_gather"] = bool(self._dc_one_gather)
        obs.record_engine_iter("core", st, dc_e=split["dc_e"],
                               sc_e=split["sc_e"], **extra)
        return st

    # ------------------------------------------------------------------
    def _batched_step_fn(self, B: int):
        """Jitted batched iteration: the DC step vmapped over a leading
        query axis, cached per batch size (shapes are static per B)."""
        key = ("batched", B)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        step = self._step_fn()
        k, q = self.k, self.q

        def one(state, active, it, args):
            # per-lane gPartList: partitions with >=1 active vertex run DC,
            # empty partitions are excluded entirely (same decision `run`
            # makes in mode='dc', but computed in-graph so it can vmap)
            counts = active.astype(jnp.int32).reshape(k, q).sum(axis=1)
            return step(state, active, counts > 0, it, args)

        def batched(states, active, it, args):
            done = ~active.any(axis=1)                         # [B]
            new_states, new_active = jax.vmap(
                one, in_axes=(0, 0, None, None))(states, active, it, args)
            # freeze converged lanes: an empty frontier is already a
            # no-op for every phase (all updates are masked on active /
            # touched), but the explicit freeze makes the contract
            # independent of the program's init/filter behaviour
            keep = ~done
            new_states = _tree_where(keep, new_states, states)
            new_active = new_active & keep[:, None]
            return new_states, new_active

        jitted = jax.jit(batched)

        def fn(states, active, it):
            return jitted(states, active, it, self._args())
        self._step_cache[key] = fn
        return fn

    def run_batched(self, states, frontiers, max_iters: int = 10_000,
                    until_empty: bool = True, collect_stats: bool = True):
        """Batched multi-source execution: B independent queries of the
        same vertex program advance together through one vmapped DC
        iteration per superstep.

        ``states`` is a pytree whose leaves carry a leading query axis
        ``[B, ...]``; ``frontiers`` is ``[B, n_pad]`` bool.  Every kernel
        launch (the DC step's gather and fold) is amortized across the
        batch — the serving-tier analogue of the paper's §5
        repeated-query argument: the O(E) layout is resident and shared,
        only the O(V) per-query state is replicated.  The *union* frontier drives
        convergence (the loop runs until every lane drained); per-query
        done masks freeze converged lanes inside a step, and between
        steps converged lanes are compacted out of the batch entirely
        (packed to the next power-of-two width, so at most log2(B)
        distinct step shapes ever compile).  Results are bit-exact with
        B sequential :meth:`run` calls in mode='dc'.

        That vmapped DC-only step is the ``mode='dc'`` engine's.  A
        ``hybrid`` or ``sc`` engine instead advances the lanes in
        lockstep, each through its own Eq. 1 DC/SC split and the same
        compiled phases as :meth:`run` (one host read of every lane's
        partition stats per superstep): bit-exact with B sequential
        :meth:`run` calls in that mode, and a lane whose frontier is
        small pays for its active edges, not for a whole-edge DC pass.
        """
        active = jnp.asarray(frontiers, jnp.bool_)
        assert active.ndim == 2, "frontiers must be [B, n_pad]"
        states = jax.tree_util.tree_map(jnp.asarray, states)
        if self.mode != "dc":
            return self._run_lockstep(states, active, max_iters,
                                      until_empty, collect_stats)
        return _run_batched_loop(self._batched_step_fn, states, active,
                                 max_iters, until_empty, collect_stats,
                                 engine_name="core",
                                 program=self.program.name)

    def _run_lockstep(self, states, active, max_iters, until_empty,
                      collect_stats):
        """:meth:`run_batched` for a hybrid or sc engine: every lane with
        an active vertex takes one :meth:`_superstep` per iteration."""
        tmap = jax.tree_util.tree_map
        B = active.shape[0]
        lanes = [tmap(lambda a, i=i: a[i], states) for i in range(B)]
        acts = [active[i] for i in range(B)]
        stats = []
        # the spans of run(); engine.split and engine.dispatch repeat
        # once for each live lane, and engine.superstep's fields sum the
        # lanes' splits (sc_budget: the largest)
        with obs.annotation("engine.run", program=self.program.name,
                            mode=self.mode):
            for it in range(max_iters):
                with obs.annotation("engine.superstep", it=it) as span:
                    with obs.annotation("engine.part_stats"):
                        counts, ea = self._part_stats(jnp.stack(acts))
                        counts, ea = np.asarray(counts), np.asarray(ea)
                    live = np.nonzero(counts.sum(axis=1) > 0)[0]
                    if not len(live):
                        if until_empty:
                            break
                        continue    # every phase masks on active: no-op
                    t0 = time.perf_counter()
                    splits = []
                    for i in live:
                        lanes[i], acts[i], _, split = self._superstep(
                            lanes[i], acts[i], it, counts[i], ea[i])
                        splits.append(split)
                    with obs.annotation("engine.sync"):
                        jax.block_until_ready(acts)
                        wall = time.perf_counter() - t0
                    span.set_metadata(lanes=len(live), **{
                        k: (max if k == "sc_budget" else sum)(
                            s[k] for s in splits) for k in splits[0]})
                    if collect_stats:
                        with obs.annotation("engine.record"):
                            stats.append(self._record_batch_iter(
                                it, len(live), int(counts.sum()), wall))
        return (tmap(lambda *xs: jnp.stack(xs), *lanes), jnp.stack(acts),
                stats)

    def _record_batch_iter(self, it, lanes, n_act, wall):
        """The lockstep superstep's :class:`~repro.obs.BatchIterStats`,
        also recorded as a ``batch_iter`` event and step-wall
        histogram."""
        if obs.enabled():
            obs.event("batch_iter", engine="core", program=self.program.name,
                      it=it, lanes_active=lanes, n_active=n_act,
                      width=lanes, wall_s=wall)
            obs.observe("engine.batch_step_wall_s", wall, engine="core",
                        program=self.program.name)
        return obs.BatchIterStats(it=it, lanes_active=lanes, n_active=n_act,
                                  wall_s=wall)

    # ------------------------------------------------------------------
    def _fused_loop(self, iters: int):
        """Jitted ``loop(state, active, args)``: ``iters`` DC steps
        (:meth:`_step_fn`, every partition on the DC stream) in one
        ``fori_loop``; cached per ``iters``, so a repeated call compiles
        nothing."""
        key = ("fused", iters)
        fn = self._step_cache.get(key)
        if fn is None:
            step, k = self._step_fn(), self.k

            def loop(state, active, args):
                dc_mask = jnp.ones((k,), jnp.bool_)

                def body(it, carry):
                    st, act = carry
                    return step(st, act, dc_mask, it, args)
                return jax.lax.fori_loop(0, iters, body, (state, active))
            fn = self._step_cache[key] = jax.jit(loop)
        return fn

    def run_fused(self, state, frontier, iters: int):
        """Fully-jitted fixed-iteration loop (DC mode, no host round trips).

        This is the PageRank-style path: all partitions scatter DC every
        iteration (paper §6.2.2: "PageRank always uses DC mode").  With
        telemetry on, the call is one host span ``engine.fused``
        (``iters``, ``dc_e``) around its ``engine.dispatch`` and
        ``engine.sync`` and one ``fused_run`` event; ``dc_e`` is the DC
        stream's active edges per iteration, counted from ``frontier`` as
        :meth:`run` counts a superstep's.
        """
        loop = self._fused_loop(iters)
        active = jnp.asarray(frontier, jnp.bool_)
        if not obs.enabled():
            return loop(state, active, self._args())
        _, ea = self._part_stats(active)
        dc_e = int(np.asarray(ea).sum())
        with obs.annotation("engine.fused", iters=iters, dc_e=dc_e):
            t0 = time.perf_counter()
            with obs.annotation("engine.dispatch"):
                out = loop(state, active, self._args())
            with obs.annotation("engine.sync"):
                jax.block_until_ready(out)
            wall = time.perf_counter() - t0
        obs.event("fused_run", engine="core", program=self.program.name,
                  iters=iters, dc_e=dc_e, wall_s=wall)
        return out
