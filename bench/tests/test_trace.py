"""The trace reduction: by hand on a synthetic trace, and on a small
trace recorded on a TPU v5e through the harness (``rgg_n_2_20.bfs`` cut
to scale 12, one traced root; ``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def _write(tmp_path, events):
    meta = [
        {"ph": "M", "pid": 3, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 3, "tid": 3, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 3, "tid": 4, "name": "thread_name",
         "args": {"name": "Async XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
         "args": {"name": "python3"}},
    ]
    p = tmp_path / "perfetto_trace.json.gz"
    with gzip.open(p, "wt") as f:
        json.dump({"traceEvents": meta + events}, f)
    return p


def _op(ts, dur, tf_op, name="fusion.1", tid=3):
    return {"ph": "X", "pid": 3, "tid": tid, "ts": ts, "dur": dur,
            "name": name, "args": {"tf_op": tf_op}}


def _host(ts, dur, name):
    return {"ph": "X", "pid": 9, "tid": 1, "ts": ts, "dur": dur,
            "name": name}


def test_reduction_by_hand(tmp_path):
    p = _write(tmp_path, [
        _host(100, 1000, trace.WINDOW),
        _host(100, 400, "run"), _host(150, 100, "_part_stats"),
        _host(600, 500, "run"),
        _op(50, 100, "jit(a)/ppm.fold.pallas-native/jit(f)/sort:"),
        _op(300, 100, "jit(_dc_phase)/ppm.fused_dc.pallas-native/"
                      "jit(fused_scatter_fold)/gather:"),
        _op(350, 150, "jit(_dc_phase)/ppm.fused_dc.pallas-native/"
                      "jit(fused_scatter_fold)/pallas_call:"),
        _op(700, 100, "jit(_apply_phase)/select_n:"),
        _op(1050, 100, "jit(x)/ppm.fold.pallas-native/add:"),
        _op(200, 800, "copy", tid=4),          # async copies: not busy
    ])
    tr = trace.load(p)
    assert tr.window == (100.0, 1100.0) and tr.devices == 1
    # busy: [100,150) + [300,500) + [700,800) + [1050,1100) = 400 us
    assert trace.busy_intervals(tr) == [(100, 150), (300, 500),
                                        (700, 800), (1050, 1100)]
    assert trace.busy_s(tr) == pytest.approx(400e-6)
    assert trace.window_s(tr) == pytest.approx(1000e-6)
    assert trace.scope_seconds(tr, "fused_dc") == pytest.approx(250e-6)
    assert trace.scope_seconds(tr, "fold") == pytest.approx(200e-6)
    top = dict(trace.top_ops(tr))
    assert top["ppm.fused_dc.pallas-native/pallas_call"] == pytest.approx(
        150e-6)
    # gaps: [150,300) in _part_stats' parent run (mid 225: _part_stats
    # ended at 250 -> innermost open is _part_stats), [500,700) mid 600
    # in the second run, [800,1050) mid 925 in the second run
    gaps = dict(trace.idle_gaps(tr))
    assert gaps == pytest.approx({"_part_stats": 150e-6,
                                  "run": 450e-6})


def test_window_annotation_is_required(tmp_path):
    p = _write(tmp_path, [_op(0, 10, "")])
    with pytest.raises(ValueError):
        trace.load(p)


def test_recorded_chip_trace():
    tr = trace.load(DATA / "rgg12.perfetto_trace.json.gz")
    assert tr.devices == 1 and tr.ops
    busy, win = trace.busy_s(tr), trace.window_s(tr)
    assert 0 < busy < win
    # the SC fold's ops carry their scope, the whole root is SC-only
    assert trace.scope_seconds(tr, "fold") > 0
    assert trace.scope_seconds(tr, "fused_dc") == 0
    assert sum(s for _, s in trace.idle_gaps(tr, 10 ** 6)) == pytest.approx(
        win - busy, rel=1e-6)
