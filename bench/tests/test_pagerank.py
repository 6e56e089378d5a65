"""The PageRank cell off the chip: a tiny rehearsal, its comparison with
the float64 reference (the system's answer passes; both controls and an
answer rounded to bfloat16 fail), the DC roofline's least bytes on its
step record, and the span metrics against a CPU-made trace that holds
``engine.fused``."""
import gzip
import json
import time
import types
import unittest.mock

import jax
import numpy as np
import pytest

from bench import harness, pagerank_reference, trace

from helpers import GEOMETRY, fixed_generator, tiny_cell

CELL = "graph500-22.pagerank"
APP = harness.BENCH / "apps" / "pagerank.py"


def _metric(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def _app(gen, config, seed=2 ** 31 + 5):
    c = tiny_cell(CELL)
    return harness.load_module(APP).App(dict(c.config, **config), c.traffic,
                                        seed, gen, {})


@pytest.fixture(scope="module")
def small():
    """The cell's generator and traffic at scale 10: 2^10 ids, many of
    them isolated."""
    c = tiny_cell(CELL)
    gen = harness.load_module(harness.BENCH / "generators" / "kronecker.py")
    app = harness.load_module(APP).App(c.config, c.traffic, 2 ** 31 + 5,
                                       gen, {})
    app.warm(lambda *a, **k: None)
    return app


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_cell_runs_correct_and_compiles_nothing_in_its_window(traced):
    c = tiny_cell(CELL)
    lines = []
    result, checks = harness.run(
        c, 2 ** 31 + 11, 0.5, traced, time.perf_counter(),
        lambda tag, **f: lines.append((tag, f)), device=None)
    assert result["correct"] and result["failed"] == 0
    assert set(checks) == {"rank_faults", "mass_faults", "kernels_off_table"}
    assert all(v == 0 for v, _ in checks.values())
    window = dict(lines)["window"]
    assert window["requests"] >= 1 and window["compiles"] == 0
    got = set(result["metrics"])
    if traced:       # no device metric off the chip
        assert got == {"pr_iter_ms", "host_share", "layout_s"}
    else:
        assert got == {"teps", "setup_s"}
    json.dumps(result)


def test_answer_passes_control_and_bf16_fail(small):
    ans = small.call("graph")
    graph, _ = small._want()
    assert (graph.deg == 0).sum() > 0.1 * graph.n       # dangling ids
    faults, work = small.check([("graph", ans)])
    assert faults == [{"rank": 0, "mass": 0}]
    assert work == [graph.edges() * small.iters]
    (control,) = small.control([("graph", ans)])
    assert control["leaky_rank"] > 0 and control["leaky_mass"] == 1
    # the reference kept in bfloat16 fails on its ranks
    assert control["bf16_rank"] > 0.5 * graph.n
    bf16 = ans["pr"].astype(jax.numpy.bfloat16).astype(np.float32)
    (rounded,), _ = small.check([("graph", {"pr": bf16})])
    assert rounded["rank"] > 0.5 * graph.n


def test_reference_counts_edges_as_the_bfs_reference():
    from bench import reference
    rng = np.random.default_rng(3)
    u, v = rng.integers(0, 50, 300), rng.integers(0, 50, 300)
    u[:5] = v[:5]                                       # self loops
    want = reference.Graph(60, u, v).component_edges(np.zeros(60))
    assert pagerank_reference.Graph(60, u, v).edges() == want


def test_least_bytes_count_every_edge_in_every_iteration():
    """A path with a self loop at one end and an isolated id: the DC
    stream carries each directed edge, the loop once, every iteration."""
    n = 40
    u = np.concatenate([np.arange(n - 1), [0]])
    v = np.concatenate([np.arange(1, n), [0]])
    a = _app(fixed_generator(n + 1, u, v), {"geometry": GEOMETRY})
    (step,) = a.call("graph")["steps"]
    per_iter = 2 * (n - 1) + 1
    assert step["dc_parts"] == a.k and step["sc_parts"] == 0
    assert step["dc_e"] == per_iter * a.iters
    # the BFS cell's DC roofline reads the call's record as one superstep
    roof = _metric("fused_dc_roofline")
    assert roof.least_bytes([step]) == 4 * per_iter * a.iters
    _, work = a.check([("graph", a.call("graph"))])
    assert work == [(n - 1) * a.iters]                  # no loop in m


def test_pr_iter_ms_is_silent_on_bfs_steps():
    bfs_step = dict(wall_s=1.0, dc_parts=1, sc_parts=0, dc_e=5, sc_e=0)
    run = types.SimpleNamespace(calls=[{"wall_s": 2.0, "steps": [bfs_step]}],
                                traced=[{"steps": [bfs_step]}], trace=None,
                                peaks=None)
    assert _metric("pr_iter_ms").read(run) is None


def test_span_metrics_read_the_fused_span_of_a_cpu_trace(small, tmp_path):
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            t = time.perf_counter()
            ans = small.call("graph")
            call_s = time.perf_counter() - t
    (path,) = tmp_path.glob("plugins/profile/*/perfetto_trace.json.gz")
    tr = trace.load(path)
    spans = {name: (s, dur) for s, dur, name in tr.host
             if name.startswith("engine.")}
    assert set(spans) == {"engine.fused", "engine.dispatch", "engine.sync"}
    f0, fdur = spans["engine.fused"]
    for child in ("engine.dispatch", "engine.sync"):
        s, dur = spans[child]
        assert f0 <= s and s + dur <= f0 + fdur
    with gzip.open(path, "rt") as f:
        (args,) = [e["args"] for e in json.load(f)["traceEvents"]
                   if e.get("name") == "engine.fused"]
    (step,) = ans["steps"]
    assert int(args["iters"]) == step["iters"] == small.iters
    assert int(args["dc_e"]) * step["iters"] == step["dc_e"]
    # the event's wall lies inside the span, around its two children
    # (5 us for the two clocks' rounding)
    inner = spans["engine.dispatch"][1] + spans["engine.sync"][1]
    assert inner - 5 <= 1e6 * step["wall_s"] <= fdur + 5
    run = types.SimpleNamespace(calls=[{"wall_s": call_s, "steps": [step]}])
    assert _metric("pr_iter_ms").read(run) == pytest.approx(
        1e3 * step["wall_s"] / step["iters"])
    share = _metric("host_share").read(run)
    assert 0 < share < 100
    assert share == pytest.approx(100 * (1 - step["wall_s"] / call_s))


def test_dc_roofline_is_silent_without_dc_e():
    """An engine that does not count ``dc_e`` (the app writes None)
    leaves the DC roofline out rather than failing the run."""
    step = dict(wall_s=1.0, iters=10, dc_parts=2, sc_parts=0, dc_e=None,
                sc_e=0)
    run = types.SimpleNamespace(traced=[{"steps": [step]}], trace=object(),
                                peaks={"hbm_bytes_per_s": 8.19e11})
    with unittest.mock.patch.object(trace, "scope_seconds", return_value=1.0):
        assert _metric("fused_dc_roofline").read(run) is None


def test_app_stops_before_set_up_without_the_aggregate():
    """A PageRank that leaks the dangling rank cannot answer this cell:
    the app raises before it generates the graph."""
    import dataclasses
    import importlib
    # the package exports a function of the module's name
    pr_app = importlib.import_module("repro.apps.pagerank")
    leaky = dataclasses.replace(pr_app.pagerank_program(1), aggregate=None)

    def edges(cfg, seed):
        raise AssertionError("the graph was generated")
    gen = types.SimpleNamespace(edges=edges)
    with unittest.mock.patch.object(pr_app, "pagerank_program",
                                    return_value=leaky):
        with pytest.raises(RuntimeError, match="aggregate"):
            _app(gen, {"geometry": GEOMETRY})


def test_configuration_is_the_bfs_graph_with_its_changes_named():
    """The cell's configuration draws the BFS configuration's graph, all
    2^scale ids of it, and names each key it changed from its source."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == harness.cell(CELL, bench).spec["config"])
    cfg = json.loads((harness.ROOT / entry["file"]).read_text())
    bfs = json.loads((harness.BENCH / "configs" / "graph500-22.json")
                     .read_text())
    for key in ("generator", "scale", "edge_factor", "a", "b", "c",
                "structure_seed", "geometry"):
        assert cfg[key] == bfs[key], key
    assert cfg["vertices"] == 2 ** cfg["scale"]
    assert cfg["vertices"] > cfg["published"]["vertices"]
    assert set(entry["reduced"]) == set(cfg["reduced_why"])
    assert all(key in cfg for key in entry["reduced"])
