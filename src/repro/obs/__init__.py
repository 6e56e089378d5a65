"""repro.obs — unified telemetry for engines, kernels, and the serving
tier.

GPOP's efficiency claims are *measured* claims: the Eq. 1 hybrid mode
decision and the paper's traffic tables exist because the runtime knows
per-partition active counts, degrees, and communication volumes every
iteration.  This package is where those signals live instead of dying at
the call site: a dependency-free metrics registry (counters, gauges,
log-bucketed histograms with p50/p95/p99), a schema'd JSONL event
stream, host spans of the engine loop and kernel named scopes for a
profiler capture, and Prometheus/JSONL exporters.

Environment knobs
-----------------

``REPRO_OBS``
    Master switch.  Unset or truthy -> telemetry ON (the default: the
    recording paths are host-side appends on data the engines already
    hold, never extra device syncs).  ``REPRO_OBS=0`` (also ``false`` /
    ``off`` / ``no``) disables every recording entry point behind a
    single attribute test — no metric objects are created, no events are
    buffered, no spans are opened, traced computations are unchanged (no
    retraces).  Measured on a TPU v5e host with jax 0.9.0, a host span
    costs 0.70 us with no profiler running (1.35 us with six fields),
    1.9 us under a capture (3.3 us with fields): ~5 us of a superstep's
    host work (~13 us traced), against 16.7 ms a superstep of BFS over
    a 2^20 vertex random geometric graph.
    ``set_enabled()`` / ``override_enabled()`` flip it at runtime.

``REPRO_OBS_SINK``
    Optional path.  When set, every event the default registry records
    is also streamed to this file as one JSON line (append mode,
    flushed per event) — the artifact ``tools/check_obs_schema.py``
    validates and ``tools/obs_report.py`` renders.

What gets recorded
------------------

* **Engines** — ``Engine.run`` / ``run_batched`` / ``run_fused`` and
  ``DistEngine.run`` / ``run_batched`` emit per-iteration events
  (mode decision, dc/sc partition counts, active vertex/edge counts,
  the active edges of each stream and the SC budget class, modeled or
  analytic wire bytes, step wall time), step-wall histograms keyed by
  mode, and lane-compaction events on the batched paths.
* **Engine loop spans** — ``Engine.run``, the lockstep
  ``run_batched`` of a hybrid or sc engine and ``Engine.run_fused`` open
  host spans (:func:`annotation`, a ``jax.profiler.TraceAnnotation``),
  so a capture shows what the host did while the device idled.
* **Kernels** — every registry-constructed ``fused_dc`` / ``fold``
  call runs under a ``jax.named_scope`` tagged with the kernel and
  backend name, so a ``jax.profiler.trace()`` capture (see
  :func:`trace`) attributes device time to PPM phases.
* **Serving tier** — ``GraphQueryServer`` and the LM ``Server`` record
  queue depth, fused-batch/drain sizes, LRU hit/miss counters (labeled
  by layout identity, so hit rates never aggregate across incompatible
  layouts), and end-to-end query latency histograms.

Reading a trace
---------------

Capture with ``jax.profiler.trace(dir, create_perfetto_trace=True)``
(or :func:`trace`); ``perfetto_trace.json.gz`` holds the device's ops
and, on the thread that called the engine, these spans, the fields of
each in its ``args``:

``engine.run`` (``program``, ``mode``)
    one ``run`` call, or one lockstep ``run_batched`` call.
``engine.superstep`` (``it``; after the split ``dc_parts``,
``sc_parts``, ``dc_e``, ``sc_e``, ``sc_budget``, ``new_programs``)
    one pass of the loop, the last pass (which finds the frontier empty)
    included.  ``sc_budget`` is the SC stream's static size (0: none),
    ``new_programs`` the phase programs built for this superstep, whose
    first call compiles.  A lockstep pass adds ``lanes`` and sums the
    lanes' fields (``sc_budget``: the largest).
``engine.part_stats``
    the per-partition active counts: their dispatch and the copy to the
    host, which waits for the previous superstep's last program.
``engine.split``
    the Eq. 1 DC/SC split, the SC budget and the phase programs' lookup.
``engine.dispatch``
    the calls of the DC (or ``no_dc``), SC and apply phases; a first
    call compiles inside it.
``engine.sync``
    ``block_until_ready`` on the new frontier: the host waits for the
    device.
``engine.record``
    the superstep's ``IterStats`` and its events.

Inside ``engine.superstep`` these five are siblings in that order and do
not overlap; a lockstep pass repeats ``engine.split`` and
``engine.dispatch`` once for each live lane.  ``IterStats.wall_s`` runs
from the end of ``engine.part_stats`` to the end of the sync.

``engine.fused`` (``iters``, ``dc_e``)
    one ``run_fused`` call's device loop: its ``engine.dispatch`` and
    ``engine.sync``; ``dc_e`` is the DC stream's active edges per
    iteration.  The ``fused_run`` event's ``wall_s`` is its wall time.

Quick use::

    from repro import obs
    obs.reset()
    bfs(layout, source=0)
    for e in obs.events("engine_iter"):
        ...                                   # one record per superstep
    print(obs.export.prometheus_text())
    obs.export.write_jsonl("events.jsonl")
"""
from __future__ import annotations

from . import export, schema, tracing
from .metrics import (Counter, Gauge, Histogram, Registry, counter,
                      enabled, event, events, gauge, histogram, inc, observe,
                      override_enabled, registry, reset, set_enabled,
                      set_gauge, snapshot)
from .schema import BatchIterStats, EVENT_SCHEMA, IterStats, validate_event
from .tracing import annotation, kernel_scope, trace

__all__ = [
    "export", "schema", "tracing",
    "Counter", "Gauge", "Histogram", "Registry",
    "counter", "enabled", "event", "events", "gauge", "histogram", "inc",
    "observe", "override_enabled", "registry", "reset", "set_enabled",
    "set_gauge", "snapshot",
    "BatchIterStats", "EVENT_SCHEMA", "IterStats", "validate_event",
    "annotation", "kernel_scope", "trace",
    "record_engine_iter",
]


def record_engine_iter(engine: str, st: IterStats, wire_bytes=None,
                       **extra):
    """Record one engine iteration: JSONL event + step-wall histogram.
    A no-op when telemetry is disabled; every value is host-resident
    already (no device syncs)."""
    if not enabled():
        return
    d = schema.as_event(st)
    if wire_bytes is not None:
        d["wire_bytes"] = int(wire_bytes)
    d.update(extra)
    event("engine_iter", engine=engine, **d)
    observe("engine.step_wall_s", st.wall_s, engine=engine,
            program=st.program or "?", mode=st.mode or "?")
