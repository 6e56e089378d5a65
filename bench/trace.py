"""Reduce a profiler capture to what the per-layer metrics read.

``jax.profiler.trace(..., create_perfetto_trace=True)`` writes
``perfetto_trace.json.gz`` beside the ``.xplane.pb``.  In it:

* a process named ``/device:TPU:<i>`` per chip, whose thread ``XLA Ops``
  holds one complete event (``ph: X``, ``ts``/``dur`` in microseconds)
  per device operation;
* each operation's ``args.tf_op`` is its ``jax.named_scope`` path, e.g.
  ``jit(_dc_phase)/ppm.fused_dc.pallas-native/jit(fused_scatter_fold)/gather:``
  — the program tags every registry kernel ``ppm.<kernel>.<backend>``,
  and the XLA gather of the fused DC step lies inside ``ppm.fused_dc``;
* host threads on the same clock, among them the annotation that the
  harness puts around the traced window (``WINDOW``) and, from the
  Python tracer, the host function each interval was in.

:func:`load` reads the file into a :class:`Trace`; the rest works on
that and on nothing else, so a small recorded trace tests it.
"""
from __future__ import annotations

import dataclasses
import gzip
import json
import re
from pathlib import Path

#: name of the host annotation around the traced window
WINDOW = "bench.window"
_JIT = re.compile(r"^jit\(.*\)$")


@dataclasses.dataclass
class Op:
    start_us: float
    dur_us: float
    name: str
    path: str          # tf_op without its jit(...) parts, '' when absent

    @property
    def end_us(self) -> float:
        return self.start_us + self.dur_us

    @property
    def scope(self):
        """The outermost ``ppm.<kernel>.<backend>`` scope, or None."""
        for part in self.path.split("/"):
            if part.startswith("ppm."):
                return part
        return None


@dataclasses.dataclass
class Trace:
    ops: list            # [Op] on the device(s), sorted by start
    host: list           # [(start_us, dur_us, name)] on the thread that
                         # drove the window (its events nest)
    window: tuple        # (start_us, end_us) of the WINDOW annotation
    devices: int         # device processes seen


def _clean_path(tf_op: str) -> str:
    parts = [p for p in tf_op.rstrip(":").split("/") if p and
             not _JIT.match(p)]
    return "/".join(parts)


def load(path) -> Trace:
    """Parse a ``perfetto_trace.json.gz`` (or plain ``.json``)."""
    path = Path(path)
    opener = gzip.open if path.suffix == ".gz" else open
    with opener(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    dev_pids = {p for p, nm in procs.items() if nm.startswith("/device:TPU")}
    op_threads = {k for k, nm in threads.items()
                  if k[0] in dev_pids and nm == "XLA Ops"}
    complete = [e for e in events if e.get("ph") == "X"]
    marks = [e for e in complete if e["name"] == WINDOW]
    if not marks:
        raise ValueError(f"{path}: no {WINDOW!r} annotation in the trace")
    w = marks[0]
    window = (float(w["ts"]), float(w["ts"]) + float(w["dur"]))
    host_thread = (w.get("pid"), w.get("tid"))
    ops, host = [], []
    for e in complete:
        key = (e.get("pid"), e.get("tid"))
        if key in op_threads:
            ops.append(Op(float(e["ts"]), float(e["dur"]), e["name"],
                          _clean_path(e.get("args", {}).get("tf_op", ""))))
        elif key == host_thread and e is not w:
            host.append((float(e["ts"]), float(e["dur"]), e["name"]))
    ops.sort(key=lambda o: o.start_us)
    return Trace(ops=ops, host=host, window=window, devices=len(dev_pids))


def in_window(tr: Trace):
    """Device ops that overlap the traced window."""
    s, e = tr.window
    return [o for o in tr.ops if o.end_us > s and o.start_us < e]


def busy_intervals(tr: Trace):
    """Union of the device ops' intervals, clipped to the window, as a
    sorted list of disjoint ``(start_us, end_us)``."""
    s, e = tr.window
    out = []
    for o in in_window(tr):
        a, b = max(o.start_us, s), min(o.end_us, e)
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_s(tr: Trace) -> float:
    """Seconds in the window during which some op ran, averaged over
    the devices traced."""
    total = sum(b - a for a, b in busy_intervals(tr))
    return total / 1e6 / max(tr.devices, 1)


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e6


def scope_seconds(tr: Trace, kernel: str) -> float:
    """Device seconds of the window's ops under ``ppm.<kernel>.*``."""
    pre = f"ppm.{kernel}."
    return sum(o.dur_us for o in in_window(tr)
               if (o.scope or "").startswith(pre)) / 1e6


def _op_key(o: Op) -> str:
    if o.path:
        return o.path
    return re.sub(r"[.\d]+$", "", o.name.split(" ")[0].lstrip("%"))


def top_ops(tr: Trace, count: int = 10):
    """``[[op, seconds]]``: the device ops that took the most time in the
    window, grouped by scope path (or op name where there is none)."""
    tot = {}
    for o in in_window(tr):
        k = _op_key(o)
        tot[k] = tot.get(k, 0.0) + o.dur_us / 1e6
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:count]


def idle_gaps(tr: Trace, count: int = 10):
    """``[[host activity, seconds]]``: the window's idle device time,
    each gap named by the innermost host event at its middle (the
    Python function or annotation the host was in), summed by name."""
    s, e = tr.window
    busy = busy_intervals(tr)
    gaps, t = [], s
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < e:
        gaps.append((t, e))
    # one thread's events nest: a sweep keeps those open at each gap's
    # middle, the innermost last
    host = sorted(tr.host, key=lambda h: (h[0], -h[1]))
    tot, stack, i = {}, [], 0
    for a, b in gaps:
        mid = (a + b) / 2
        while i < len(host) and host[i][0] <= mid:
            stack.append(host[i])
            i += 1
        stack = [h for h in stack if h[0] + h[1] >= mid]
        name = stack[-1][2] if stack else "(no host event)"
        tot[name] = tot.get(name, 0.0) + (b - a) / 1e6
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])
            ][:count]
