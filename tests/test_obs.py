"""Tests for the repro.obs telemetry layer.

Covers the metrics registry (histogram percentile math against
numpy.percentile, label-subset resets), the exporters (JSONL round-trip,
Prometheus text format, the checked-in schema JSON staying in sync with
``EVENT_SCHEMA``), the engine and serve-tier wiring (events validate,
server counters match the obs series, layout swaps segment the hit-rate
series), the engine loop's host spans in a profiler capture, and the
disabled-mode no-op guarantee (no events, no metrics, no spans, no extra
jit retraces).
"""
import importlib.util
import json
import math
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.obs import schema as obs_schema
from repro.obs.export import (JsonlSink, prometheus_text, read_jsonl,
                              write_jsonl)
from repro.obs.metrics import Histogram, Registry

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HYP = True
except ImportError:                                  # pragma: no cover
    _HYP = False

REPO_ROOT = Path(__file__).resolve().parents[1]
G = Histogram.GROWTH


@pytest.fixture(scope="module")
def layout():
    from repro.graph import build_layout, rmat
    g = rmat(8, 8, seed=3)
    return build_layout(g, k=4, edge_tile=64, msg_tile=32)


@pytest.fixture()
def obs_on():
    """Telemetry forced ON with a clean default registry, restored after."""
    with obs.override_enabled(True):
        obs.reset()
        yield obs.registry()
    obs.reset()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO_ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------------
# histogram percentile math
# ----------------------------------------------------------------------

def _check_bracket(samples, p):
    """The log-bucketed estimate must land within one bucket's relative
    width of numpy's linear-interpolated percentile (G per order
    statistic; G**2 total slack absorbs bucket-boundary rounding)."""
    h = Histogram("t", {})
    for v in samples:
        h.observe(v)
    est = h.percentile(p)
    ref = float(np.percentile(np.asarray(samples, float), p))
    assert h.min <= est <= h.max
    assert ref / G**2 - 1e-12 <= est <= ref * G**2 + 1e-12


class TestHistogram:
    def test_empty_is_nan(self):
        assert math.isnan(Histogram("t", {}).percentile(50))

    def test_single_value_exact(self):
        h = Histogram("t", {})
        h.observe(0.125)
        for p in (0, 50, 100):
            assert h.percentile(p) == 0.125

    def test_counts_sum_min_max(self):
        h = Histogram("t", {})
        for v in (1.0, 2.0, 3.0):
            h.observe(v)
        assert (h.n, h.sum, h.min, h.max) == (3, 6.0, 1.0, 3.0)
        s = h.summary()
        assert s["count"] == 3 and s["p50"] == pytest.approx(2.0, rel=G)

    def test_percentiles_bracket_numpy_fixed(self):
        rng = np.random.default_rng(11)
        samples = np.exp(rng.uniform(np.log(1e-6), np.log(1e3), size=500))
        for p in (0, 1, 25, 50, 75, 90, 95, 99, 100):
            _check_bracket(samples, p)

    def test_reset(self):
        h = Histogram("t", {})
        h.observe(1.0)
        h.reset()
        assert h.n == 0 and math.isnan(h.percentile(50))


if _HYP:
    @settings(max_examples=100, deadline=None)
    @given(st.lists(st.floats(min_value=1e-6, max_value=1e6,
                              allow_nan=False, allow_infinity=False),
                    min_size=2, max_size=100),
           st.floats(min_value=0, max_value=100))
    def test_percentile_brackets_numpy_property(samples, p):
        _check_bracket(samples, p)
else:                                                # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_percentile_brackets_numpy_property():
        pass


# ----------------------------------------------------------------------
# registry
# ----------------------------------------------------------------------

class TestRegistry:
    def test_label_series_are_distinct(self):
        r = Registry(enabled=True)
        r.inc("hits", layout="a")
        r.inc("hits", 2, layout="b")
        assert r.counter("hits", layout="a").value == 1
        assert r.counter("hits", layout="b").value == 2
        snap = r.snapshot()
        assert snap["counters"]["hits{layout=a}"] == 1
        assert snap["counters"]["hits{layout=b}"] == 2

    def test_reset_metric_label_subset(self):
        r = Registry(enabled=True)
        r.inc("hits", 3, layout="a", app="bfs")
        r.inc("hits", 5, layout="a", app="sssp")
        r.inc("hits", 7, layout="b", app="bfs")
        r.reset_metric("hits", layout="a")
        assert r.counter("hits", layout="a", app="bfs").value == 0
        assert r.counter("hits", layout="a", app="sssp").value == 0
        assert r.counter("hits", layout="b", app="bfs").value == 7

    def test_disabled_records_nothing(self):
        r = Registry(enabled=False)
        r.inc("hits")
        r.set_gauge("depth", 4)
        r.observe("lat", 0.1)
        r.event("engine_iter", engine="core")
        assert r.metrics() == {}
        assert r.events() == []


# ----------------------------------------------------------------------
# exporters
# ----------------------------------------------------------------------

class TestExport:
    def test_jsonl_round_trip(self, tmp_path):
        r = Registry(enabled=True)
        r.event("cache_clear", layout="0x1")
        r.event("bench_row", kernel="gather", backend="ref", wall_s=0.25)
        p = tmp_path / "events.jsonl"
        assert write_jsonl(p, r) == 2
        back = read_jsonl(p)
        assert back == r.events()

    def test_streaming_sink(self, tmp_path):
        p = tmp_path / "stream.jsonl"
        r = Registry(enabled=True, sink=str(p))
        r.event("cache_clear", layout="0x1")
        r.close()
        assert len(read_jsonl(p)) == 1
        with JsonlSink(p) as sink:
            sink.emit({"event": "cache_clear", "ts": 0.0, "layout": "x"})
        assert len(read_jsonl(p)) == 2

    def test_prometheus_text_format(self):
        r = Registry(enabled=True)
        r.inc("serve.cache_hits", 3, app="bfs", layout="L1")
        r.set_gauge("serve.queue_depth", 4, layout="L1")
        for v in (0.5, 0.5, 2.0):
            r.observe("lat", v)
        text = prometheus_text(r)
        assert text.endswith("\n")
        assert "# TYPE repro_serve_cache_hits counter" in text
        assert 'repro_serve_cache_hits{app="bfs",layout="L1"} 3' in text
        assert "# TYPE repro_serve_queue_depth gauge" in text
        assert 'repro_serve_queue_depth{layout="L1"} 4' in text
        assert "# TYPE repro_lat histogram" in text
        assert 'repro_lat_bucket{le="+Inf"} 3' in text
        assert "repro_lat_sum 3" in text
        assert "repro_lat_count 3" in text
        # two finite buckets (0.5 x2, 2.0 x1) + the +Inf bound
        assert text.count("repro_lat_bucket{") == 3


# ----------------------------------------------------------------------
# schema + checked-in serialization + stdlib validator
# ----------------------------------------------------------------------

class TestSchema:
    def test_validate_event_accepts_valid(self):
        rec = {"event": "engine_iter", "ts": 1.0, "engine": "core",
               "program": "bfs", "it": 0, "mode": "dc", "n_active": 1,
               "e_active": 8, "wall_s": 0.01, "extra": "ok"}
        assert obs_schema.validate_event(rec) == []

    def test_validate_event_flags_violations(self):
        assert obs_schema.validate_event({"ts": 1.0}) \
            == ["missing/invalid 'event' field"]
        assert obs_schema.validate_event({"event": "nope", "ts": 1.0})
        missing = obs_schema.validate_event(
            {"event": "cache_clear", "ts": 1.0})
        assert any("layout" in m for m in missing)
        # bool is an int subclass: must be rejected where int is asked
        rec = {"event": "engine_iter", "ts": 1.0, "engine": "core",
               "program": "bfs", "it": True, "mode": "dc", "n_active": 1,
               "e_active": 8, "wall_s": 0.01}
        assert any("got bool" in m for m in obs_schema.validate_event(rec))

    def test_optional_fields_are_typed_where_present(self, tmp_path):
        checker = _load_tool("check_obs_schema")
        rec = {"event": "engine_iter", "ts": 1.0, "engine": "core",
               "program": "bfs", "it": 0, "mode": "sc", "n_active": 1,
               "e_active": 8, "wall_s": 0.01}
        schema = json.loads(
            (REPO_ROOT / "tools" / "obs_schema.json").read_text())
        for extra, bad in [({}, False), ({"sc_budget": 4096}, False),
                           ({"sc_budget": "4096"}, True),
                           ({"dc_e": 0, "sc_e": 1.5}, True)]:
            r = dict(rec, **extra)
            errs = obs_schema.validate_event(r)
            assert bool(errs) == bad, (extra, errs)
            assert checker.validate_record(r, schema) == errs

    def test_schema_json_in_sync(self):
        on_disk = json.loads(
            (REPO_ROOT / "tools" / "obs_schema.json").read_text())
        assert on_disk == obs_schema.EVENT_SCHEMA

    def test_check_obs_schema_cli(self, tmp_path):
        checker = _load_tool("check_obs_schema")
        good = tmp_path / "good.jsonl"
        good.write_text(json.dumps(
            {"event": "cache_clear", "ts": 1.0, "layout": "x"}) + "\n")
        assert checker.main([str(good)]) == 0
        assert checker.main([str(good), "--require", "cache_clear"]) == 0
        assert checker.main([str(good), "--require", "engine_iter"]) == 1
        bad = tmp_path / "bad.jsonl"
        bad.write_text(json.dumps({"event": "cache_clear", "ts": 1.0})
                       + "\nnot json\n")
        assert checker.main([str(bad)]) == 1


# ----------------------------------------------------------------------
# engine wiring
# ----------------------------------------------------------------------

def _bfs_inputs(layout, source=0):
    import jax.numpy as jnp
    n_pad = layout.n_pad
    parent = jnp.full((n_pad,), -1, jnp.int32).at[source].set(source)
    level = jnp.full((n_pad,), -1, jnp.int32).at[source].set(0)
    vid = jnp.arange(n_pad, dtype=jnp.uint32)
    frontier = np.zeros(n_pad, bool)
    frontier[source] = True
    return {"parent": parent, "level": level, "vid": vid}, frontier


class TestEngineTelemetry:
    def test_run_records_events(self, obs_on, layout):
        from repro.apps import bfs
        res = bfs(layout, source=0)
        iters = obs.events("engine_iter")
        assert len(iters) == len(res["stats"]) > 0
        for e, st_ in zip(iters, res["stats"]):
            assert obs_schema.validate_event(e) == []
            assert e["engine"] == "core" and e["program"] == "bfs"
            assert e["mode"] in ("dc", "sc", "hybrid")
            # the active edges split by stream, and the SC budget class
            assert e["dc_e"] + e["sc_e"] == e["e_active"]
            assert e["sc_budget"] == st_.sc_budget
            assert (e["sc_budget"] >= e["sc_e"]) if e["sc_parts"] \
                else e["sc_budget"] == 0

    def test_batched_run_records_batch_iters(self, obs_on, layout):
        from repro.apps.bfs import bfs_multi
        bfs_multi(layout, [0, 1, 2])
        batched = obs.events("batch_iter")
        assert batched
        for e in batched:
            assert obs_schema.validate_event(e) == []
            # the compiled width starts at the submitted B and only
            # shrinks (pow2 compaction) as lanes converge
            assert e["lanes_active"] <= e["width"] <= 3

    def test_collect_stats_false_is_silent(self, obs_on, layout):
        from repro.apps.bfs import bfs_program
        from repro.core.engine import Engine
        eng = Engine(layout, bfs_program(), mode="dc")
        state, frontier = _bfs_inputs(layout)
        eng.run(state, frontier, collect_stats=False)
        assert obs.events("engine_iter") == []

    def test_disabled_mode_no_events_no_retrace(self, layout):
        from repro.apps.bfs import bfs_program
        from repro.core.engine import Engine
        eng = Engine(layout, bfs_program(), mode="dc")
        state, frontier = _bfs_inputs(layout)
        with obs.override_enabled(True):
            obs.reset()
            eng.run(state, frontier)
            n_events = len(obs.events())
            assert n_events > 0
            keys = set(eng._step_cache)
            sizes = {k: fn._cache_size()
                     for k, fn in eng._step_cache.items()
                     if hasattr(fn, "_cache_size")}
            with obs.override_enabled(False):
                eng.run(state, frontier)
                assert len(obs.events()) == n_events
                assert obs.registry().enabled is False
            # same shapes, telemetry toggled: no new jitted steps and no
            # retrace of the existing ones
            assert set(eng._step_cache) == keys
            for k, n in sizes.items():
                assert eng._step_cache[k]._cache_size() == n
            obs.reset()

    def test_iterstats_compat_shim(self):
        import warnings
        from repro.core import engine as core_engine
        # the old names still resolve, but each access warns
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert core_engine.IterStats is obs_schema.IterStats
            assert core_engine.BatchIterStats is obs_schema.BatchIterStats
        assert len(rec) == 2
        assert all(issubclass(w.category, DeprecationWarning) for w in rec)
        assert "repro.obs.schema" in str(rec[0].message)
        # the public repro.core re-export stays silent
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            import repro.core
            assert repro.core.IterStats is obs_schema.IterStats
        assert not rec
        # pre-obs positional construction still works
        st_ = obs_schema.IterStats(0, 1, 2, 3, 4, 5.0, 6.0, 0.1)
        assert (st_.mode, st_.program) == ("", "")
        assert obs_schema.as_event(st_)["dc_bytes"] == 5.0


# ----------------------------------------------------------------------
# engine loop spans in a profiler capture
# ----------------------------------------------------------------------

LOOP = ["engine.part_stats", "engine.split", "engine.dispatch",
        "engine.sync", "engine.record"]


def _captured_spans(fn):
    """Run ``fn`` under ``jax.profiler.trace``; returns its result and the
    ``engine.*`` complete events of ``perfetto_trace.json.gz``, outer
    before inner."""
    import gzip
    import tempfile

    import jax
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d, create_perfetto_trace=True):
            out = fn()
        (f,) = Path(d).glob("plugins/profile/*/perfetto_trace.json.gz")
        with gzip.open(f, "rt") as fh:
            events = json.load(fh)["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"
             and e["name"].startswith("engine.")]
    return out, sorted(spans, key=lambda e: (e["ts"], -e["dur"]))


def _span_run(layout, path):
    """``(spans, stats)`` of one BFS through ``Engine.run`` or the
    lockstep ``run_batched`` of a hybrid engine (three lanes)."""
    from repro.apps.bfs import bfs_multi, bfs_program
    from repro.core.engine import Engine
    eng = Engine(layout, bfs_program(), mode="hybrid")
    state, frontier = _bfs_inputs(layout)

    def go():
        if path == "run":
            return eng.run(state, frontier)[2]
        return bfs_multi(layout, [0, 1, 2], engine=eng)["stats"]
    go()                                # compile outside the capture
    stats, spans = _captured_spans(go)
    return spans, stats


def _end(e):
    return e["ts"] + e["dur"]


class TestEngineSpans:
    @pytest.mark.parametrize("path", ["run", "lockstep"])
    def test_span_tree(self, obs_on, layout, path):
        spans, stats = _span_run(layout, path)
        eps = 0.01                                  # us of rounding
        assert len({(e["pid"], e["tid"]) for e in spans}) == 1
        (run,) = [e for e in spans if e["name"] == "engine.run"]
        assert run["args"] == {"program": "bfs", "mode": "hybrid"}
        steps = [e for e in spans if e["name"] == "engine.superstep"]
        # one per loop pass: each recorded superstep, then the pass that
        # finds the frontier empty
        assert len(steps) == len(stats) + 1
        inner = [e for e in spans if e["name"] in LOOP]
        for i, step in enumerate(steps):
            assert run["ts"] - eps <= step["ts"] <= _end(step) \
                <= _end(run) + eps
            kids = [e for e in inner if step["ts"] - eps <= e["ts"]
                    and _end(e) <= _end(step) + eps]
            if i == len(stats):
                want = ["engine.part_stats"]
            elif path == "run":
                want = LOOP
            else:
                lanes = stats[i].lanes_active
                want = (LOOP[:1] + LOOP[1:3] * lanes + LOOP[3:])
            assert [e["name"] for e in kids] == want
            for a, b in zip(kids, kids[1:]):
                assert _end(a) <= b["ts"] + eps      # siblings, in order
            assert step["args"]["it"] == str(i)
            if i < len(stats):
                assert "sc_budget" in step["args"]
                assert int(step["args"]["new_programs"]) == 0
                if path == "run":
                    assert int(step["args"]["sc_budget"]) \
                        == stats[i].sc_budget
                    assert int(step["args"]["sc_parts"]) \
                        == stats[i].sc_parts
                else:
                    assert int(step["args"]["lanes"]) \
                        == stats[i].lanes_active
        # every inner span lies in exactly one superstep
        for e in inner:
            assert sum(s["ts"] - eps <= e["ts"] and _end(e) <= _end(s) + eps
                       for s in steps) == 1

    @pytest.mark.parametrize("path", ["run", "lockstep"])
    def test_disabled_mode_opens_no_span(self, layout, path):
        with obs.override_enabled(False):
            spans, stats = _span_run(layout, path)
        assert stats and spans == []

    def test_annotation_is_a_profiler_span_or_a_no_op(self):
        import jax
        with obs.override_enabled(True):
            assert isinstance(obs.annotation("x", a=1),
                              jax.profiler.TraceAnnotation)
        with obs.override_enabled(False):
            with obs.annotation("x", a=1) as span:
                span.set_metadata(b=2)
            assert not isinstance(span, jax.profiler.TraceAnnotation)


# ----------------------------------------------------------------------
# serve-tier wiring
# ----------------------------------------------------------------------

class TestServeTelemetry:
    def _server(self, layout):
        from repro.serve.engine import GraphQuery, GraphQueryServer
        return GraphQueryServer(layout), GraphQuery

    def test_counters_match_server_ints(self, obs_on, layout):
        srv, GraphQuery = self._server(layout)
        reg = obs.registry()
        for i, s in enumerate([0, 1, 2]):
            srv.submit(GraphQuery(qid=i, app="bfs", params={"source": s}))
        srv.run()
        srv.submit(GraphQuery(qid=9, app="bfs", params={"source": 0}))
        srv.run()
        tag = srv._layout_tag
        hits = reg.counter("serve.cache_hits", layout=tag, app="bfs")
        misses = reg.counter("serve.cache_misses", layout=tag, app="bfs")
        assert srv.cache_hits == hits.value == 1
        assert srv.cache_misses == misses.value == 3
        assert reg.gauge("serve.queue_depth", layout=tag).value == 0
        for e in obs.events("serve_batch") + obs.events("serve_query"):
            assert obs_schema.validate_event(e) == []
        assert any(e["cached"] for e in obs.events("serve_query"))

    def test_clear_cache_resets_layout_series(self, obs_on, layout):
        srv, GraphQuery = self._server(layout)
        reg = obs.registry()
        srv.submit(GraphQuery(qid=0, app="bfs", params={"source": 0}))
        srv.run()
        # a foreign layout's series must survive this server's reset
        reg.inc("serve.cache_misses", 7, layout="other", app="bfs")
        tag = srv._layout_tag
        srv.clear_cache()
        assert srv.cache_hits == srv.cache_misses == 0
        assert reg.counter("serve.cache_misses", layout=tag,
                           app="bfs").value == 0
        assert reg.counter("serve.cache_misses", layout="other",
                           app="bfs").value == 7
        assert obs.events("cache_clear")
        # the result cache is gone: the same query is a miss again
        srv.submit(GraphQuery(qid=1, app="bfs", params={"source": 0}))
        srv.run()
        assert (srv.cache_hits, srv.cache_misses) == (0, 1)

    def test_swap_layout_segments_series(self, obs_on, layout):
        from repro.graph import build_layout, rmat
        srv, GraphQuery = self._server(layout)
        reg = obs.registry()
        srv.submit(GraphQuery(qid=0, app="bfs", params={"source": 0}))
        srv.run()
        old_tag = srv._layout_tag
        g2 = rmat(7, 8, seed=5)
        layout2 = build_layout(g2, k=4, edge_tile=64, msg_tile=32)
        srv.swap_layout(layout2)
        assert srv.layout is layout2
        assert srv._layout_tag != old_tag
        swaps = obs.events("layout_swap")
        assert swaps and obs_schema.validate_event(swaps[-1]) == []
        assert swaps[-1]["old"] == old_tag
        assert swaps[-1]["new"] == srv._layout_tag
        # old layout's series were reset; a plain swap evicts nothing,
        # but the old entry is invisible under the NEW tag, so the
        # repeated query is a miss under the new tag only
        assert reg.counter("serve.cache_misses", layout=old_tag,
                           app="bfs").value == 0
        srv.submit(GraphQuery(qid=1, app="bfs", params={"source": 0}))
        srv.run()
        assert reg.counter("serve.cache_misses", layout=srv._layout_tag,
                           app="bfs").value == 1
        assert (srv.cache_hits, srv.cache_misses) == (0, 1)


# ----------------------------------------------------------------------
# report rendering
# ----------------------------------------------------------------------

def test_obs_report_renders_iteration_table(obs_on, layout):
    from repro.apps import bfs
    bfs(layout, source=0)
    report = _load_tool("obs_report")
    out = report.render(obs.events())
    assert "engine=core program=bfs" in out
    header = next(l for l in out.splitlines() if "mode" in l)
    for col in ("it", "mode", "n_active", "e_active", "wire_B", "wall_ms"):
        assert col in header
