"""LDBC Graphalytics PageRank through every engine path, against a float64
reference, on seeded Kronecker graphs that keep their isolated vertices
(dangling) and self loops; and the program-level aggregate it rests on.

Tolerances are the benchmark cell's: every rank within a relative 1e-4
of the reference (1e-4 / n near 0), the total within 1e-4 of 1."""
import contextlib
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.apps.bfs import bfs_program
from repro.apps.pagerank import pagerank, pagerank_program
from repro.baselines.vc import pagerank_spmv
from repro.core.engine import Engine
from repro.graph import build_layout, rmat
from repro.graph.csr import from_edges, symmetrize

RTOL, MASS_TOL, ITERS, D = 1e-4, 1e-4, 10, 0.85


def kronecker(scale: int, seed: int):
    """Symmetrized Kronecker (R-MAT) graph, all ``2**scale`` ids kept:
    the generator's self loops stay (once) and unreached ids stay
    isolated."""
    g0 = rmat(scale, 8, seed=seed, dedup=False)
    src = np.repeat(np.arange(g0.n), g0.out_degrees())
    return symmetrize(from_edges(src, g0.indices, g0.n))


def reference(g, iters=ITERS, x0=None, damping=D):
    """Graphalytics PageRank in float64 from the CSR arrays."""
    n = g.n
    deg = g.out_degrees().astype(np.float64)
    src = np.repeat(np.arange(n), g.out_degrees())
    x = np.full(n, 1.0 / n) if x0 is None else np.asarray(x0, np.float64)
    for _ in range(iters):
        share = np.divide(x, deg, out=np.zeros(n), where=deg > 0)
        acc = np.bincount(g.indices, weights=share[src], minlength=n)
        x = (1 - damping) / n + damping * (acc + x[deg == 0].sum() / n)
    return x


def assert_graphalytics(got, want):
    got = np.asarray(got, np.float64)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=RTOL / len(want))
    assert abs(got.sum() - 1.0) <= MASS_TOL


@pytest.fixture(scope="module", params=[(10, 1), (11, 4)],
                ids=["scale10", "scale11"])
def graph(request):
    g = kronecker(*request.param)
    deg = g.out_degrees()
    assert (deg == 0).sum() > 0, "no dangling vertex"
    loops = np.repeat(np.arange(g.n), deg) == g.indices
    assert loops.any(), "no self loop"
    return g, build_layout(g, k=8, edge_tile=64, msg_tile=32)


@pytest.mark.parametrize("path", ["fused", "dc", "hybrid"])
def test_pagerank_matches_reference(graph, path):
    g, L = graph
    kw = (dict(fused=True) if path == "fused"
          else dict(fused=False, mode=path))
    assert_graphalytics(pagerank(L, iters=ITERS, **kw)["pr"], reference(g))


def test_spmv_baseline_matches_reference(graph):
    g, _ = graph
    assert_graphalytics(pagerank_spmv(g, iters=ITERS), reference(g))


@pytest.mark.parametrize("mode", ["dc", "hybrid"])
def test_run_batched_lanes_match_reference(graph, mode):
    """Two lanes, a cold start and another start of mass 1: each lane's
    aggregate is its own."""
    g, L = graph
    n, n_pad = L.n, L.n_pad
    warm = np.random.default_rng(0).random(n)
    warm /= warm.sum()
    pr = np.zeros((2, n_pad), np.float32)
    pr[0, :n], pr[1, :n] = 1.0 / n, warm
    deg = np.broadcast_to(L.deg.astype(np.float32), (2, n_pad))
    frontier = np.zeros((2, n_pad), bool)
    frontier[:, :n] = True
    eng = Engine(L, pagerank_program(n), mode=mode)
    states, _, _ = eng.run_batched({"pr": pr, "deg": deg}, frontier,
                                   max_iters=ITERS, until_empty=False)
    got = np.asarray(states["pr"])[:, :n]
    assert_graphalytics(got[0], reference(g))
    assert_graphalytics(got[1], reference(g, x0=warm))


def test_padding_stays_out_of_the_aggregate():
    """Padding ids carry rank and no edge, as dangling vertices do; the
    engine sums the aggregate over ids below n only."""
    g = kronecker(10, 2)
    L = build_layout(g, k=3, edge_tile=64, msg_tile=32)
    assert L.n_pad > L.n
    eng = Engine(L, pagerank_program(L.n), mode="dc")
    state = {"pr": jnp.ones((L.n_pad,), jnp.float32),
             "deg": jnp.asarray(L.deg.astype(np.float32))}
    want = float((L.deg[:L.n] == 0).sum())
    assert float(eng._aggregate(state)) == want


# ----------------------------------------------------------------------
# the aggregate leaves programs without one as they were
# ----------------------------------------------------------------------

def _lower(fn, *args):
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return jitted.lower(*args).as_text()


def test_aggregate_free_programs_lower_as_before():
    """BFS sets no aggregate: its fused step is exactly the DC phase then
    the apply phase, and its DC phase is the same program whether or not
    a program has an aggregate (the aggregate never enters it)."""
    g = kronecker(8, 3)
    L = build_layout(g, k=4, edge_tile=64, msg_tile=32)
    eng = Engine(L, bfs_program(), mode="dc")
    n_pad = L.n_pad
    state = {"parent": jnp.full((n_pad,), -1, jnp.int32),
             "level": jnp.full((n_pad,), -1, jnp.int32),
             "vid": jnp.arange(n_pad, dtype=jnp.uint32)}
    active = jnp.zeros((n_pad,), jnp.bool_).at[0].set(True)
    mask, it, args = jnp.ones((L.k,), jnp.bool_), jnp.int32(0), eng._args()

    def step(state, active, dc_mask, it, args):
        state, keep, acc, touched, _ = eng._dc_phase(state, active,
                                                     dc_mask, it, args)
        return eng._apply_phase(state, keep, acc, touched, it)
    assert (_lower(eng._step_fn(), state, active, mask, it, args)
            == _lower(step, state, active, mask, it, args))

    counted = dataclasses.replace(
        bfs_program(), aggregate=lambda s: s["level"] >= 0,
        filter_fn=lambda s, it, agg: (s, s["level"] < agg))
    with_agg = Engine(L, counted, mode="dc")
    dc = _lower(eng._dc_phase, state, active, mask, it, args)
    assert dc == _lower(with_agg._dc_phase, state, active, mask, it, args)
    # the aggregate is one more reduction, in the step only
    reduces = lambda text: text.count("stablehlo.reduce")  # noqa: E731
    assert (reduces(_lower(with_agg._step_fn(), state, active, mask, it,
                           with_agg._args()))
            == reduces(_lower(step, state, active, mask, it, args)) + 1)


# ----------------------------------------------------------------------
# run_fused: one compile per iteration count, its span and event
# ----------------------------------------------------------------------

class _Compiles:
    """Programs compiled while ``on`` (``jax.monitoring``'s backend
    compile events)."""

    def __init__(self):
        from jax._src import dispatch
        self.on, self.count = False, 0
        name = dispatch.BACKEND_COMPILE_EVENT

        def duration(event, _secs, **_):
            if self.on and event == name:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(duration)


@pytest.fixture(scope="module")
def small():
    g = kronecker(10, 5)
    return g, build_layout(g, k=8, edge_tile=64, msg_tile=32)


def test_run_fused_compiles_once_per_iteration_count(small):
    g, L = small
    eng = Engine(L, pagerank_program(L.n), mode="dc")
    counter = _Compiles()
    pagerank(L, iters=3, engine=eng)
    counter.on = True
    try:
        again = pagerank(L, iters=3, engine=eng)["pr"]
        assert counter.count == 0
        pagerank(L, iters=4, engine=eng)
        assert counter.count > 0            # a new iteration count compiles
    finally:
        counter.on = False
    assert_graphalytics(again, reference(g, iters=3))


def test_run_fused_span_and_event(small, monkeypatch):
    _, L = small
    eng = Engine(L, pagerank_program(L.n), mode="dc")
    pagerank(L, iters=2, engine=eng)            # compile outside the spans
    opened = []
    span = types.SimpleNamespace(set_metadata=lambda **meta: None)

    @contextlib.contextmanager
    def annotation(name, **fields):
        opened.append((name, fields, "open"))
        yield span
        opened.append((name, {}, "close"))

    monkeypatch.setattr(obs, "annotation", annotation)
    obs.reset()
    pagerank(L, iters=2, engine=eng)
    dc_e = int(L.deg.sum())
    assert opened == [("engine.fused", {"iters": 2, "dc_e": dc_e}, "open"),
                      ("engine.dispatch", {}, "open"),
                      ("engine.dispatch", {}, "close"),
                      ("engine.sync", {}, "open"),
                      ("engine.sync", {}, "close"),
                      ("engine.fused", {}, "close")]
    (e,) = obs.events("fused_run")
    assert e["iters"] == 2 and e["dc_e"] == dc_e and e["wall_s"] > 0
    assert obs.validate_event(e) == []
