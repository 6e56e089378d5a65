"""Render the per-iteration telemetry table from an obs JSONL export.

  PYTHONPATH=src python tools/obs_report.py EVENTS.jsonl
  PYTHONPATH=src python tools/obs_report.py --demo [--sink out.jsonl]

Given a JSONL event file (``repro.obs.export.write_jsonl`` or the
``REPRO_OBS_SINK`` stream), prints, per engine run:

  * the ``engine_iter`` table — iteration, mode decision (dc / sc /
    hybrid and the per-partition split), active vertex/edge counts, the
    wire bytes (analytic all_to_all payload for dist steps, the Eq. 1
    modeled dc+sc traffic for single-device steps), and step wall time;
  * the ``batch_iter`` table — live lanes, compiled width, union-frontier
    active count and step wall per batched superstep;
  * a one-line summary per serve / fused / bench event family.

``--demo`` runs a small self-contained workload first (BFS + unfused
PageRank on an rmat graph, then a batch of GraphQueryServer queries),
with telemetry forced ON, and reports the collected events — the CI
serve lane uses it as the obs smoke workload.  ``--sink`` additionally
streams every event to the given JSONL path (the artifact
``tools/check_obs_schema.py`` then validates).
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]
if str(REPO_ROOT / "src") not in sys.path:
    sys.path.insert(0, str(REPO_ROOT / "src"))


def _fmt_row(cols, widths):
    return "  ".join(str(c).rjust(w) for c, w in zip(cols, widths))


def _wire_of(e) -> int:
    if "wire_bytes" in e:
        return int(e["wire_bytes"])
    return int(e.get("dc_bytes", 0) + e.get("sc_bytes", 0))


def render(events) -> str:
    lines = []
    iters = [e for e in events if e.get("event") == "engine_iter"]
    # one table per (engine, program) run, in first-seen order
    groups: dict = {}
    for e in iters:
        groups.setdefault((e.get("engine", "?"), e.get("program", "?")),
                          []).append(e)
    for (engine, program), evs in groups.items():
        lines.append(f"== engine={engine} program={program} "
                     f"({len(evs)} iterations) ==")
        header = ("it", "mode", "dc/sc", "n_active", "e_active",
                  "wire_B", "wall_ms")
        rows = []
        for e in sorted(evs, key=lambda e: e.get("it", 0)):
            parts = (f"{e['dc_parts']}/{e['sc_parts']}"
                     if "dc_parts" in e and "sc_parts" in e else "-")
            rows.append((e.get("it", "?"), e.get("mode", "?"), parts,
                         e.get("n_active", "?"), e.get("e_active", "?"),
                         _wire_of(e), f"{e.get('wall_s', 0) * 1e3:.2f}"))
        widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
                  for i, h in enumerate(header)]
        lines.append(_fmt_row(header, widths))
        for r in rows:
            lines.append(_fmt_row(r, widths))
        tot = sum(e.get("wall_s", 0) for e in evs)
        lines.append(f"   total {tot * 1e3:.2f} ms, "
                     f"{sum(_wire_of(e) for e in evs)} wire bytes")
        lines.append("")

    batched = [e for e in events if e.get("event") == "batch_iter"]
    bgroups: dict = {}
    for e in batched:
        bgroups.setdefault((e.get("engine", "?"), e.get("program", "?")),
                           []).append(e)
    for (engine, program), evs in bgroups.items():
        lines.append(f"== batched engine={engine} program={program} "
                     f"({len(evs)} supersteps) ==")
        header = ("it", "lanes", "width", "n_active", "wall_ms")
        rows = [(e.get("it", "?"), e.get("lanes_active", "?"),
                 e.get("width", "?"), e.get("n_active", "?"),
                 f"{e.get('wall_s', 0) * 1e3:.2f}")
                for e in sorted(evs, key=lambda e: e.get("it", 0))]
        widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
                  for i, h in enumerate(header)]
        lines.append(_fmt_row(header, widths))
        for r in rows:
            lines.append(_fmt_row(r, widths))
        lines.append("")

    for kind, fmt in (
            ("fused_run", lambda e: f"engine={e.get('engine')} "
             f"program={e.get('program')} iters={e.get('iters')} "
             f"wall={e.get('wall_s', 0) * 1e3:.2f}ms"),
            ("lane_compaction", lambda e: f"program={e.get('program')} "
             f"it={e.get('it')} lanes={e.get('lanes_active')} -> "
             f"width={e.get('width')} (of {e.get('batch')})"),
            ("serve_batch", lambda e: f"app={e.get('app')} "
             f"batch={e.get('batch')} distinct={e.get('distinct_sources')} "
             f"width={e.get('width')} wall={e.get('wall_s', 0)*1e3:.2f}ms"),
            ("serve_query", lambda e: f"app={e.get('app')} "
             f"cached={e.get('cached')} "
             f"wall={e.get('wall_s', 0) * 1e3:.2f}ms"),
            ("bench_row", lambda e: f"kernel={e.get('kernel')} "
             f"backend={e.get('backend')} "
             f"wall={e.get('wall_s', 0) * 1e3:.3f}ms")):
        evs = [e for e in events if e.get("event") == kind]
        if evs:
            lines.append(f"== {kind} ({len(evs)}) ==")
            lines.extend("   " + fmt(e) for e in evs)
            lines.append("")
    return "\n".join(lines)


def demo():
    """BFS + unfused PageRank + a served query batch, telemetry forced on
    (PageRank's default fused loop records a single fused_run event; the
    per-iteration table wants the host-driven loop, hence fused=False)."""
    import numpy as np

    from repro import obs
    from repro.apps import bfs, pagerank
    from repro.graph import build_layout, rmat
    from repro.serve.engine import GraphQuery, GraphQueryServer

    obs.set_enabled(True)
    obs.reset()
    g = rmat(9, 8, seed=1)
    layout = build_layout(g, k=8, edge_tile=64, msg_tile=32)
    bfs(layout, source=int(np.argmax(g.out_degrees())))
    pagerank(layout, iters=5, fused=False)
    srv = GraphQueryServer(layout)
    for i, s in enumerate([0, 1, 2, 3, 0]):
        srv.submit(GraphQuery(qid=i, app="bfs", params={"source": int(s)}))
    srv.run()
    # a repeat of an answered query: exercises the LRU hit path
    srv.submit(GraphQuery(qid=99, app="bfs", params={"source": 0}))
    srv.run()
    print(f"demo: {len(obs.events())} events "
          f"(cache hits={srv.cache_hits} misses={srv.cache_misses})",
          file=sys.stderr)
    return obs.events()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="*", help="JSONL event files")
    ap.add_argument("--demo", action="store_true",
                    help="run the built-in workload and report it")
    ap.add_argument("--sink", default=None,
                    help="with --demo: also stream events to this JSONL")
    args = ap.parse_args(argv)
    if not args.demo and not args.files:
        ap.error("need JSONL files or --demo")

    events = []
    if args.demo:
        import os
        if args.sink:
            # the streaming sink must exist before the workload runs
            os.environ["REPRO_OBS_SINK"] = args.sink
            Path(args.sink).unlink(missing_ok=True)
            from repro import obs
            obs.registry().set_sink(args.sink)
        events.extend(demo())
    for fname in args.files:
        from repro.obs.export import read_jsonl
        events.extend(read_jsonl(fname))
    print(render(events))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
