"""Distributed serving benchmark: batched vs sequential multi-source
queries across the device mesh, and the wire-compression payoff.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m benchmarks.bench_dist [--smoke]
      [--scales 8] [--batches 1,4,8,16] [--out BENCH_dist.json]

For each (scale, app, batch size) over a ``D = jax.device_count()`` mesh
the harness times

  * ``seq``          — B sequential :meth:`DistEngine.run` calls (one
                       host-driven loop per query: B× every all_to_all
                       dispatch),
  * ``batched``      — the same B queries as ONE fused
                       :meth:`DistEngine.run_batched` invocation (the bin
                       exchange carries ``[B, D, S]`` per collective;
                       packed frontier-bitmap flags), and
  * ``batched_wire`` — the fused batch with ``wire_bf16=True`` on top
                       (f32 monoids only: the value payload halves).

Every row records the *analytic* per-step per-device all_to_all payload
(``wire_bytes``, from :func:`repro.dist.engine.dc_wire_bytes`) next to the
uncompressed bool-lane baseline (``wire_bytes_raw``), so the wire
reduction is read off the JSON directly.  Rows share the
``BENCH_kernels.json`` schema (batch in the kernel name, e.g.
``dist_bfs_batched_b8``) and are gated by
``tools/check_bench_regression.py`` in CI unchanged.  ``--smoke`` (the CI
dist-serve lane) runs one scale at best-of-2.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from .common import time_best as _time_best
from .common import write_telemetry

REPO_ROOT = Path(__file__).resolve().parents[1]
APPS = ("bfs", "sssp")


def _engines(app: str, sharded, mesh, backend):
    """(plain, wired) shared engines for one app — plain ships f32 values
    + bitmap flags, wired adds the bf16 value wire (f32 monoids only)."""
    from repro.apps.bfs import bfs_program
    from repro.apps.sssp import sssp_program
    from repro.dist.engine import DistEngine
    program = bfs_program() if app == "bfs" else sssp_program()
    plain = DistEngine(sharded, program, mesh, mode="dc", backend=backend)
    wired = DistEngine(sharded, (bfs_program() if app == "bfs"
                                 else sssp_program()), mesh, mode="dc",
                       backend=backend, wire_bf16=True)
    return plain, wired


def bench_app(app: str, layout, engines, sources, reps: int):
    """{variant: wall_s} for B queries, compile excluded (one warmup run
    of each path before timing)."""
    from repro.apps.bfs import bfs, bfs_multi
    from repro.apps.sssp import sssp, sssp_multi
    single_fn, multi_fn = ((bfs, bfs_multi) if app == "bfs"
                           else (sssp, sssp_multi))
    plain, wired = engines

    def seq():
        for s in sources:
            single_fn(layout, source=s, engine=plain)

    def batched():
        multi_fn(layout, sources, engine=plain)

    def batched_wire():
        multi_fn(layout, sources, engine=wired)

    seq(); batched(); batched_wire()       # warmup: compile all paths
    return {"seq": _time_best(seq, reps),
            "batched": _time_best(batched, reps),
            "batched_wire": _time_best(batched_wire, reps)}


def run(scales, batches, reps: int, k: int, out_path: Path, backend=None):
    from jax.sharding import AxisType
    from repro.dist.engine import dc_wire_bytes
    from repro.graph import build_layout, rmat
    from repro.graph.shard import shard_layout

    D = jax.device_count()
    mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,))
    k = max(k, D)
    results = []
    for scale in scales:
        g = rmat(scale, 8, seed=1, weighted=True)
        layout = build_layout(g, k=k, edge_tile=32, msg_tile=16)
        sharded = shard_layout(layout, D)
        meta = dict(S=sharded.S, D=D)
        rng = np.random.default_rng(7)
        order = np.argsort(g.out_degrees())[::-1]
        pool = order[:max(64, max(batches))]
        for app in APPS:
            engines = _engines(app, sharded, mesh, backend)
            itemsize = 4                   # both monoids carry 4B values
            compress = engines[1].wire_compressed
            for B in batches:
                sources = [int(s) for s in
                           rng.choice(pool, size=B, replace=False)]
                walls = bench_app(app, layout, engines, sources, reps)
                raw = dc_wire_bytes(meta, itemsize, compressed=False,
                                    wire_bitmap=False, batch=B)
                wb = {"seq": dc_wire_bytes(meta, itemsize, batch=1),
                      "batched": dc_wire_bytes(meta, itemsize, batch=B),
                      "batched_wire": dc_wire_bytes(
                          meta, itemsize, compressed=compress, batch=B)}
                for variant, wall in walls.items():
                    results.append({
                        "kernel": f"dist_{app}_{variant}_b{B}",
                        "monoid": "min", "backend": "dist",
                        "scale": scale, "n": int(g.n), "m": int(g.m),
                        "devices": D, "batch": B, "wall_s": wall,
                        "qps": B / max(wall, 1e-9),
                        "wire_bytes": wb[variant],
                        "wire_bytes_raw": raw,
                    })
                print(f"scale={scale} app={app} D={D} B={B}: "
                      f"seq={walls['seq']*1e3:.1f}ms "
                      f"batched={walls['batched']*1e3:.1f}ms "
                      f"wire={walls['batched_wire']*1e3:.1f}ms "
                      f"speedup={walls['seq']/max(walls['batched'],1e-9):.2f}x "
                      f"bytes {raw}->{wb['batched_wire']}",
                      file=sys.stderr)
    write_telemetry(out_path, results)
    doc = {
        "meta": {
            "platform": jax.default_backend(),
            "jax": jax.__version__,
            "devices": D,
            "reps": reps,
            "created": time.strftime("%Y-%m-%dT%H:%M:%S"),
        },
        "results": results,
    }
    out_path.write_text(json.dumps(doc, indent=2))
    print(f"wrote {out_path} ({len(results)} rows)", file=sys.stderr)
    return doc


def main() -> None:
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one small scale, best-of-2 (CI dist-serve lane)")
    ap.add_argument("--scales", default=None,
                    help="comma-separated rmat scales (default 8)")
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch sizes (default 1,4,8,16)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_dist.json"))
    args = ap.parse_args()
    if args.smoke:
        scales, reps = [8], 2
    else:
        scales = [int(s) for s in (args.scales or "8").split(",")]
        reps = args.reps
    batches = [int(b) for b in (args.batches or "1,4,8,16").split(",")]
    run(scales, batches, reps, args.k, Path(args.out))


if __name__ == "__main__":
    main()
