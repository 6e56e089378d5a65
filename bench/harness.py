"""The benchmark's harness: one run of one cell, driven by data.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Everything that belongs to one of them, or to one metric, is a file found
by its name, so a later change adds files and edits none:

* ``bench/configs/<config>.json``: the configuration's sizes, its
  ``generator`` and the guarantees it states;
* ``bench/generators/<generator>.py``: ``edges(config, seed)`` and
  ``expected_edges(config)``;
* ``bench/traffic/<traffic>.json``: the mix's parameters, among them
  ``app``;
* ``bench/apps/<app>.py``: ``App(config, traffic, seed, generator,
  timings)`` with ``requests``, ``warm``, ``call``, ``release`` and
  ``check``;
* ``bench/metrics/<metric>.py``: ``read(run)``, the metric's value or
  None where it finds nothing to read.

A run: set-up (the app's constructor and ``warm``), a closed-loop window
of ``seconds`` that runs the requests in their seeded order and ends with
the request in progress, the peak of device memory, with ``trace`` one
more traced window of ``trace_requests`` requests, then the system's
device state is dropped and every answer is compared with the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path):
    """Import a file of the benchmark by its path."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                               .parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict           # the workload entry of BENCHMARK.json
    config: dict         # bench/configs/<config>.json
    traffic: dict        # bench/traffic/<traffic>.json
    end_to_end: list     # metric entries this cell reports
    per_layer: list


def _for_cell(metrics, cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def cell(name: str, bench: dict = None) -> Cell:
    """Resolve a workload of ``BENCHMARK.json`` to its files."""
    bench = bench if bench is not None else read_json(ROOT /
                                                       "BENCHMARK.json")
    spec = next((w for w in bench["workloads"] if w["name"] == name), None)
    if spec is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == spec["config"])
    return Cell(name=name, spec=spec,
                config=read_json(ROOT / conf["file"]),
                traffic=read_json(BENCH / "traffic" /
                                  f"{spec['traffic']}.json"),
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name))


class CompileCount:
    """Programs compiled or loaded from the persistent cache while
    ``on``: JAX reports each through ``jax.monitoring``."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.on, self.count = False, 0
        backend = dispatch.BACKEND_COMPILE_EVENT

        def event(name, **_):
            if self.on and name == "/jax/compilation_cache/cache_hits":
                self.count += 1

        def duration(name, _secs, **_):
            if self.on and name == backend:
                self.count += 1
        jax.monitoring.register_event_listener(event)
        jax.monitoring.register_event_duration_secs_listener(duration)


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers read this."""
    setup_s: float
    window_s: float
    calls: list          # [{"request", "wall_s", "steps", ...}] of the window
    traced: list         # calls of the traced window
    timings: dict        # host seconds of set-up's phases
    peaks: dict          # bench.peaks row, None off the chip
    memory_peak_bytes: int
    trace: object = None     # bench.trace.Trace of the traced window
    work: list = None        # each window call's work, from the reference


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        log, control: bool = False, device: dict = None, trace_dir=None):
    """One run of ``cell``; returns ``(result, checks)``: the result line
    without its checks, and ``{name: (value, limit)}`` of the numbers
    compared.  With ``control`` the app's control is compared too, and
    its numbers logged (the result is the system's).  ``t0`` is the
    process's start on the host clock; ``device`` describes the chip
    (None in a rehearsal off the chip, which reports no device metric).
    The profile is written to ``trace_dir`` and kept there, or to a
    temporary directory that is removed."""
    import jax
    from bench import peaks as peaks_mod
    from bench import trace as trace_mod

    counter = CompileCount()
    generator = load_module(BENCH / "generators" /
                            f"{cell.config['generator']}.py")
    app_mod = load_module(BENCH / "apps" / f"{cell.traffic['app']}.py")
    peaks = peaks_mod.peaks_for(device["kind"]) if device else None

    timings = {}
    app = app_mod.App(cell.config, cell.traffic, seed, generator, timings)
    t = time.perf_counter()
    app.warm(log)
    timings["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t0
    log("setup", setup_s=setup_s, **timings)

    requests = app.requests
    calls = []
    counter.on = True
    start = time.perf_counter()
    i = 0
    while True:
        req = requests[i % len(requests)]
        a = time.perf_counter()
        ans = app.call(req)
        b = time.perf_counter()
        calls.append(dict(request=req, wall_s=b - a, answer=ans,
                          steps=ans["steps"]))
        i += 1
        if b - start >= seconds:
            break
    window_s = time.perf_counter() - start
    counter.on = False
    log("window", seconds=window_s, requests=len(calls),
        compiles=counter.count,
        per_request=[[c["request"], c["wall_s"], len(c["steps"])]
                     for c in calls])
    # each distinct request's supersteps once: [dc_parts, sc_parts, dc_e,
    # sc_e, ms]
    seen = {}
    for c in calls:
        seen.setdefault(c["request"], [
            [s["dc_parts"], s["sc_parts"], s.get("dc_e"), s.get("sc_e"),
             round(1e3 * s["wall_s"], 3)] for s in c["steps"]])
    log("supersteps", **{str(k): v for k, v in seen.items()})
    mem = None
    if device:
        mem = int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])

    traced, tr = [], None
    if trace:
        traced, tr = _traced_window(app, requests, i, cell, log, device,
                                    trace_dir)

    app.release()
    gc.collect()
    t = time.perf_counter()
    answers = [(c["request"], c["answer"]) for c in calls + traced]
    faults, work = app.check(answers)
    log("reference", seconds=time.perf_counter() - t, answers=len(answers),
        distinct=len({r for r, _ in answers}))
    if control:
        cf = app.control(answers)
        log("control", **{k: sum(f[k] for f in cf) for k in cf[0]},
            failed=sum(1 for f in cf if any(f.values())))

    r = Run(setup_s=setup_s, window_s=window_s,
            calls=calls, traced=traced, timings=timings, peaks=peaks,
            memory_peak_bytes=mem, trace=tr, work=work[:len(calls)])
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        val = load_module(BENCH / "metrics" / f"{m['name']}.py").read(r)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}

    # every number compared is a count of broken guarantees: limit 0
    checks = {f"{k}_faults": (sum(f[k] for f in faults), 0)
              for k in faults[0]}
    checks["kernels_off_table"] = (app.off_table, 0)
    attempted = len(answers)
    correct = all(v <= lim for v, lim in checks.values())
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": sum(1 for f in faults if any(f.values())),
              "metrics": metrics}
    dev = dict(device or {"platform": jax.devices()[0].platform,
                          "kind": jax.devices()[0].device_kind,
                          "count": len(jax.devices())})
    dev["memory_peak_bytes"] = mem
    if tr is not None:
        dev["busy_s"] = trace_mod.busy_s(tr)
        dev["window_s"] = trace_mod.window_s(tr)
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    result["device"] = dev
    return result, checks


def _traced_window(app, requests, i, cell, log, device, trace_dir):
    """``trace_requests`` more requests under the profiler, inside one
    :data:`bench.trace.WINDOW` annotation; returns the calls and the
    reduced trace (None off the chip)."""
    import jax
    from bench import trace as trace_mod
    d = Path(trace_dir or tempfile.mkdtemp(prefix="bench-trace-"))
    traced = []
    try:
        with jax.profiler.trace(str(d), create_perfetto_trace=True):
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW):
                for j in range(int(cell.traffic["trace_requests"])):
                    req = requests[(i + j) % len(requests)]
                    a = time.perf_counter()
                    ans = app.call(req)
                    traced.append(dict(request=req, answer=ans,
                                       wall_s=time.perf_counter() - a,
                                       steps=ans["steps"]))
        files = sorted(d.glob("plugins/profile/*/perfetto_trace.json.gz"))
        tr = trace_mod.load(files[-1]) if files and device else None
        log("trace", requests=len(traced),
            ops=None if tr is None else len(tr.ops),
            bytes=files[-1].stat().st_size if files else None)
    finally:
        if trace_dir is None:
            shutil.rmtree(d, ignore_errors=True)
    return traced, tr
