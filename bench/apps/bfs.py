"""BFS traffic: Graph500's kernel 2 through the system's normal path.

One shared ``Engine(L, bfs_program(), mode=...)`` answers
``repro.apps.bfs.bfs(L, root, engine=eng)`` for roots drawn as Graph500
draws its search keys: uniformly among vertices that have an edge other
than a self loop.

The edge structure, the roots and their order come from the
configuration's ``structure_seed``; ``--seed`` draws the vertex labelling
(a permutation of the ids, as Graph500 permutes them).  So every seed
does the same searches over another labelling: the partitions, and with
them the engine's DC/SC split, move with the labelling; the work does
not.  (A window holds a handful of roots whose times differ by half, so
an order drawn from ``--seed`` would move ``teps`` with the root that
the window repeats last.)

A request's work (the ``count`` of the traffic file) is the undirected
edges of the searched component, as Graph500 counts TEPS.
"""
from __future__ import annotations

import time

import numpy as np


def _roots(n, su, sv, count: int, structure_seed: int):
    """``count`` structural ids drawn without replacement, in the order
    drawn, among vertices with an edge that is not a self loop."""
    has = np.zeros(n, bool)
    loop = su == sv
    has[su[~loop]] = True
    has[sv[~loop]] = True
    rng = np.random.default_rng([structure_seed, 1])
    return rng.choice(np.nonzero(has)[0], count, replace=False)


def _off_table(engine) -> int:
    """Kernels of the engine's path that resolved off the platform's
    table (``registry.TPU_DEFAULTS`` on a TPU) or run interpreted on a
    TPU."""
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
    """The system under test, set up for one run of BFS traffic."""

    def __init__(self, config: dict, traffic: dict, seed: int, generator,
                 timings: dict):
        from repro.apps.bfs import bfs_program
        from repro.core.engine import Engine
        from repro.graph import build_layout
        from repro.graph.csr import from_edges, symmetrize

        t0 = time.perf_counter()
        sseed = int(config["structure_seed"])
        n, su, sv = generator.edges(config, sseed)
        roots = _roots(n, su, sv, int(traffic["roots"]), sseed)
        perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
        self.n = n
        self.u, self.v = perm[su], perm[sv]
        del su, sv
        self.requests = [int(r) for r in perm[roots]]
        t1 = time.perf_counter()
        g = symmetrize(from_edges(self.u, self.v, n))
        t2 = time.perf_counter()
        self.layout = build_layout(g, **config["geometry"])
        t3 = time.perf_counter()
        del g
        self.engine = Engine(self.layout, bfs_program(),
                             mode=traffic["mode"])
        timings.update(generate_s=t1 - t0, ingest_s=t2 - t1,
                       layout_s=t3 - t2,
                       engine_s=time.perf_counter() - t3)
        self.off_table = _off_table(self.engine)
        self._graph = None

    # ------------------------------------------------------------------
    def call(self, root: int):
        """One request through the system's entry point; returns the
        answer with the engine's per-superstep records."""
        from repro import obs
        from repro.apps.bfs import bfs
        obs.reset()
        res = bfs(self.layout, root, engine=self.engine)
        # the engine's counters split each superstep's active edges by
        # stream (dc_e, sc_e); absent when telemetry is off
        split = {e["it"]: e for e in obs.events("engine_iter")}
        steps = []
        for s in res["stats"]:
            e = split.get(s.it, {})
            steps.append(dict(it=s.it, wall_s=s.wall_s, dc_parts=s.dc_parts,
                              sc_parts=s.sc_parts, n_active=s.n_active,
                              e_active=s.e_active, dc_e=e.get("dc_e"),
                              sc_e=e.get("sc_e")))
        return {"level": res["level"], "parent": res["parent"],
                "steps": steps}

    def warm(self, log):
        """Run every request once: the window repeats these searches over
        the same labelling, so this reaches every program it runs (the
        DC and DC-free phases, the SC budgets its frontiers pick, the
        apply phase) and no other."""
        steps = [len(self.call(r)["steps"]) for r in self.requests]
        log("warm", requests=len(steps), supersteps=steps)

    def release(self):
        """Drop every device array of the system before the reference."""
        self.engine = self.layout = None

    # ------------------------------------------------------------------
    def check(self, answers):
        """Compare every ``(root, answer)`` with the reference; returns
        :func:`reference.compare`'s counts for each answer and each
        answer's work."""
        faults, work = [], []
        for root, ans in answers:
            wl, _ = self._want(root)
            faults.append(self._compare(root, ans["level"], ans["parent"]))
            work.append(self._graph.component_edges(wl))
        return faults, work

    def control(self, answers):
        """The control: the reference's own answers, cut one level short
        (the last level's vertices left unreached), in the system's
        place; returns :func:`reference.compare`'s counts for each."""
        out = []
        for root, _ in answers:
            wl, wp = self._want(root)
            cut = wl == wl.max()
            out.append(self._compare(root, np.where(cut, -1, wl),
                                     np.where(cut, -1, wp)))
        return out

    def _want(self, root):
        from bench import reference
        if self._graph is None:
            self._graph = reference.Graph(self.n, self.u, self.v)
            self._answers = {}
        if root not in self._answers:
            self._answers[root] = self._graph.bfs(root)
        return self._answers[root]

    def _compare(self, root, level, parent):
        from bench import reference
        return reference.compare(self._graph, root, level, parent,
                                 self._want(root)[0])
