"""The engine-loop idle shares (``bench/spans.py``): by hand on a
synthetic trace, and on a small trace recorded on a TPU v5e through the
harness with the engine's spans in place (``rgg_n_2_20.bfs`` cut to
scale 12, one traced root; ``data/``)."""
import types
from pathlib import Path

import pytest

from bench import harness, spans, trace

from test_trace import _host, _op, _write

DATA = Path(__file__).resolve().parent / "data"
METRICS = ["stats_idle_share", "loop_idle_share", "sync_idle_share"]


def _read(name, tr):
    mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
    return mod.read(types.SimpleNamespace(trace=tr))


def _superstep(t, stats, split, dispatch, sync, record):
    """One superstep's spans from ``t``, each given by its length."""
    out = [_host(t, stats + split + dispatch + sync + record,
                 "engine.superstep")]
    for name, dur in [("engine.part_stats", stats), ("engine.split", split),
                      ("engine.dispatch", dispatch), ("engine.sync", sync),
                      ("engine.record", record)]:
        out.append(_host(t, dur, name))
        t += dur
    return out


def test_shares_by_hand(tmp_path):
    # window [0, 1000); busy [0,100) [300,400) [600,650) [900,1000);
    # idle [100,300) [400,600) [650,900) = 650 us
    p = _write(tmp_path, [
        _host(0, 1000, trace.WINDOW), _host(0, 1000, "engine.run"),
        # superstep 1 at [50, 500): the gap [100, 300) straddles
        # part_stats [50,200), split [200,250) and dispatch [250,350)
        *_superstep(50, 150, 50, 100, 130, 20),
        # a Python frame inside the sync counts once, as the sync
        _host(360, 100, "$api.py:3108 try_to_block"),
        # superstep 2 at [510, 990): [500, 510) lies in no loop span
        *_superstep(510, 190, 20, 40, 190, 40),
        # the same span names on another thread are not the window's
        {"ph": "X", "pid": 9, "tid": 2, "ts": 0, "dur": 1000,
         "name": "engine.sync"},
        _op(0, 100, "a:"), _op(300, 100, "b:"), _op(600, 50, "c:"),
        _op(900, 100, "d:"),
    ])
    tr = trace.load(p)
    assert spans.idle_intervals(tr) == [(100, 300), (400, 600), (650, 900)]
    assert spans.span_intervals(tr, {"engine.split", "engine.record"}) == [
        (200, 250), (480, 500), (700, 720), (950, 990)]
    got = {m: _read(m, tr) for m in METRICS}
    # part_stats: [100,200) + [510,600) + [650,700) = 240 us
    # split [200,250) + [700,720), dispatch [250,300) + [720,760),
    # record [480,500) + none (busy [900,1000)) = 180 us
    # sync [400,480) + [760,900) = 220 us
    assert got == pytest.approx({"stats_idle_share": 24.0,
                                 "loop_idle_share": 18.0,
                                 "sync_idle_share": 22.0})
    # the remainder, 10 us of 650, is [500, 510): in no loop span
    assert _read("idle_share", tr) - sum(got.values()) == pytest.approx(1.0)


def test_silent_without_trace_or_spans(tmp_path):
    assert all(_read(m, None) is None for m in METRICS)
    p = _write(tmp_path, [_host(0, 100, trace.WINDOW),
                          _host(0, 100, "run"), _op(0, 10, "a:")])
    tr = trace.load(p)
    assert all(_read(m, tr) is None for m in METRICS)


def test_recorded_chip_trace():
    tr = trace.load(DATA / "rgg12_spans.perfetto_trace.json.gz")
    idle = _read("idle_share", tr)
    got = [_read(m, tr) for m in METRICS]
    assert all(v is not None and 0 < v <= idle for v in got)
    assert sum(got) <= idle + 1e-9
