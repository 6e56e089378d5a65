"""Device idle time under the engine loop's host spans.

The engine (``repro.core.engine``) opens host spans on the profiler's
clock around each step of its loop: ``engine.part_stats`` (the
partition-stats dispatch and its copy to the host), ``engine.split``,
``engine.dispatch``, ``engine.sync`` (``block_until_ready``) and
``engine.record``.  :func:`bench.trace.load` keeps them among the host
events of the thread that drove the traced window.  A share here is the
window's device-idle time (the complement of
:func:`bench.trace.busy_intervals`) that falls inside the named spans,
over the window.  Where the program opens no such span, as one without
them does not, there is nothing to read.
"""
from __future__ import annotations

from bench import trace


def idle_intervals(tr: trace.Trace):
    """The window's gaps between :func:`bench.trace.busy_intervals`, as a
    sorted list of disjoint ``(start_us, end_us)``."""
    s, e = tr.window
    out, t = [], s
    for a, b in trace.busy_intervals(tr):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < e:
        out.append((t, e))
    return out


def span_intervals(tr: trace.Trace, names):
    """Union of the host spans called one of ``names``, as a sorted list
    of disjoint ``(start_us, end_us)``."""
    out = []
    for a, b in sorted((s, s + d) for s, d, n in tr.host if n in names):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap_us(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_share(run, names):
    """Device-idle time of the traced window inside the spans ``names``,
    over the window, in %; None without a trace or without such spans."""
    tr = run.trace
    if tr is None:
        return None
    spans = span_intervals(tr, names)
    if not spans:
        return None
    return 100.0 * overlap_us(idle_intervals(tr), spans) / (
        tr.window[1] - tr.window[0])
