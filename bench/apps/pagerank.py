"""PageRank traffic: LDBC Graphalytics' PageRank through the system's
normal path.

One ``Engine(L, pagerank_program(n), mode="dc")`` answers
``repro.apps.pagerank.pagerank(L, iters, engine=eng)``: the fixed
iteration count in one device loop (``Engine.run_fused``), every
partition on the DC stream in every iteration.  A request is the whole
graph from the uniform ``1/n`` start, so every call does the same work.

The graph is built as :mod:`bench.apps.bfs` builds it: the edge structure
comes from the configuration's ``structure_seed`` and ``--seed`` draws the
vertex labelling, over all ``2**scale`` ids.  Ids without an edge stay in
the graph as dangling vertices, whose rank the definition spreads over
every vertex.  A system whose ``pagerank_program`` has no global aggregate
(the dangling rank leaks) cannot compute this definition: the app stops
before set-up.

A request's work (the ``count`` of the traffic file) is the graph's
undirected edges, self loops dropped and duplicates merged, times the
iterations.  Its one step record is the engine's ``fused_run`` event:
``wall_s`` is the ``engine.fused`` span's, ``dc_e`` the DC stream's active
edges over all iterations.
"""
from __future__ import annotations

import time

import numpy as np


def _off_table(engine) -> int:
    """Kernels of the engine's path that resolved off the platform's
    table (``registry.TPU_DEFAULTS`` on a TPU) or run interpreted on a
    TPU.  The rule of :mod:`bench.apps.bfs`, kept here so that neither
    app reaches into the other."""
    import jax
    from repro.backend import registry
    platform = jax.default_backend()
    ks = engine.kernels
    ran = ({"fused_dc": engine._fused} if engine._fused is not None
           else {"scatter": ks.scatter, "gather": ks.gather})
    ran["fold"] = ks.fold
    off = 0
    for name, obj in ran.items():
        want = (registry.TPU_DEFAULTS[name][0] if platform == "tpu"
                else registry.default_backend_name(platform, name))
        off += engine.backend_names.get(name) != want
        off += platform == "tpu" and bool(getattr(obj, "interpret", False))
    return int(off)


class App:
    """The system under test, set up for one run of PageRank traffic."""

    def __init__(self, config: dict, traffic: dict, seed: int, generator,
                 timings: dict):
        from repro.apps.pagerank import pagerank_program
        from repro.core.engine import Engine
        from repro.graph import build_layout
        from repro.graph.csr import from_edges, symmetrize

        if getattr(pagerank_program(1), "aggregate", None) is None:
            # a PageRank without the global aggregate leaks the dangling
            # rank: it cannot compute this definition, so stop before
            # set-up rather than answer wrong for a whole run
            raise RuntimeError("repro's pagerank_program has no global "
                               "aggregate for the dangling rank")
        t0 = time.perf_counter()
        n, su, sv = generator.edges(config, int(config["structure_seed"]))
        perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
        self.n = n
        self.u, self.v = perm[su], perm[sv]
        del su, sv
        self.iters = int(traffic["iterations"])
        self.damping = float(traffic["damping"])
        self.limits = dict(rank_rtol=float(traffic["rank_rtol"]),
                           mass_tol=float(traffic["mass_tol"]))
        self.requests = ["graph"] * int(traffic["requests"])
        t1 = time.perf_counter()
        g = symmetrize(from_edges(self.u, self.v, n))
        t2 = time.perf_counter()
        self.layout = build_layout(g, **config["geometry"])
        t3 = time.perf_counter()
        del g
        self.engine = Engine(self.layout,
                             pagerank_program(n, self.damping),
                             mode=traffic["mode"])
        timings.update(generate_s=t1 - t0, ingest_s=t2 - t1,
                       layout_s=t3 - t2,
                       engine_s=time.perf_counter() - t3)
        self.off_table = _off_table(self.engine)
        self.k = self.layout.k
        self._graph = self._log = None

    # ------------------------------------------------------------------
    def call(self, request):
        """One request through the system's entry point; returns the ranks
        with one step record built from the engine's ``fused_run``
        event."""
        from repro import obs
        from repro.apps.pagerank import pagerank
        obs.reset()
        res = pagerank(self.layout, iters=self.iters, damping=self.damping,
                       engine=self.engine)
        e = obs.events("fused_run")[-1]
        # dc_e is absent where the engine does not count it
        dc_e = e.get("dc_e")
        step = dict(it=0, wall_s=e["wall_s"], iters=e["iters"],
                    dc_parts=self.k, sc_parts=0,
                    dc_e=None if dc_e is None else dc_e * e["iters"],
                    sc_e=0)
        return {"pr": res["pr"], "steps": [step]}

    def warm(self, log):
        """One request: every call runs the same programs."""
        self._log = log
        steps = [self.call(r)["steps"][0] for r in self.requests]
        log("warm", requests=len(steps), wall_s=[s["wall_s"] for s in steps])

    def release(self):
        """Drop every device array of the system before the reference."""
        self.engine = self.layout = None

    # ------------------------------------------------------------------
    def check(self, answers):
        """Compare every ``(request, answer)`` with the reference; returns
        :func:`pagerank_reference.compare`'s counts for each answer and
        each answer's work."""
        from bench import pagerank_reference as ref
        graph, want = self._want()
        faults = [ref.compare(ans["pr"], want, **self.limits)
                  for _, ans in answers]
        if self._log is not None:
            self._log("pagerank", max_rel_err=max(
                ref.max_rel_err(ans["pr"], want) for _, ans in answers),
                mass=[float(np.sum(ans["pr"], dtype=np.float64))
                      for _, ans in answers])
        work = graph.edges() * self.iters
        return faults, [work] * len(answers)

    def control(self, answers):
        """Two controls in the system's place, each through the
        comparison the answers go through: the reference's own ranks
        without the dangling term (``leaky_*``), and the reference
        computed in bfloat16, the precision below the system's float32
        (``bf16_*``).  Returns their counts for each answer."""
        import ml_dtypes
        from bench import pagerank_reference as ref
        graph, want = self._want()
        ctl = {"leaky": graph.pagerank(self.iters, self.damping,
                                       dangling=False),
               "bf16": graph.pagerank(self.iters, self.damping,
                                      dtype=ml_dtypes.bfloat16)}
        if self._log is not None:
            self._log("control_readings", **{
                f"{k}_{f}": v for k, pr in ctl.items() for f, v in (
                    ("max_rel_err", ref.max_rel_err(pr, want)),
                    ("mass", float(np.sum(pr, dtype=np.float64))))})
        faults = {f"{k}_{f}": v for k, pr in ctl.items()
                  for f, v in ref.compare(pr, want, **self.limits).items()}
        return [faults for _ in answers]

    def _want(self):
        from bench import pagerank_reference as ref
        if self._graph is None:
            self._graph = ref.Graph(self.n, self.u, self.v)
            self._want_pr = self._graph.pagerank(self.iters, self.damping)
        return self._graph, self._want_pr
