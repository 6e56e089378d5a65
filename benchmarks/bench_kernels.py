"""Kernel microbenchmark harness: per-backend timings for the PPM kernels.

  PYTHONPATH=src python -m benchmarks.bench_kernels [--smoke]
      [--scales 8,10,12] [--backends ref,pallas-interpret]
      [--out BENCH_kernels.json]

Times one compiled call of each of ``fold`` (fold_block — the blocked
segmented fold behind the SC stream and the distributed gather),
``fold2`` (fold_two_level — the same fold on an over-cap segment count,
where the two-level bucketed kernel runs) and ``fused`` (fused_step — the
DC step, a table gather and the two-level fold) for every backend the
registry can lower on this platform, across rmat graph scales, and
writes the results to ``BENCH_kernels.json`` at the repo root — the
perf-trajectory artifact every hot-path PR regenerates.  ``--smoke``
(used by CI) runs two small scales at best-of-2 so the emission path
can never silently rot; CI compares the smoke rows against the
committed baseline with ``tools/check_bench_regression.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

from repro.backend import registry, tuning
from repro.graph import build_layout, rmat

from .common import write_telemetry

REPO_ROOT = Path(__file__).resolve().parents[1]
KERNELS = ("fold", "fold2", "fused")


def bench_backend(layout, backend_name: str, platform: str, reps: int):
    """Per-kernel best-of-reps wall times; skips combos the backend cannot
    lower (recording which backend actually ran is the registry's job)."""
    rows = []
    for kernel in KERNELS:
        monoid = "add"
        # fold2 is the registry 'fold' kernel timed in the over-cap
        # (two-level) regime, not a separate registry entry; 'fused'
        # is registry kernel 'fused_dc'
        reg_kernel = "fused_dc" if kernel == "fused" else "fold"
        resolved = registry.resolve(reg_kernel, monoid,
                                    platform=platform, choice=backend_name)
        if resolved.name != backend_name:
            continue                 # would silently time the fallback
        t = tuning.time_layout(layout, backend_name, platform,
                               kernels=(kernel,), reps=reps,
                               monoid=monoid)
        rows.append({"kernel": kernel, "monoid": monoid,
                     "backend": backend_name, "wall_s": t[kernel]})
    return rows


def run(scales, backends, reps: int, k: int, out_path: Path) -> dict:
    platform = jax.default_backend()
    results = []
    for scale in scales:
        g = rmat(scale, 8, seed=1)
        layout = build_layout(g, k=min(k, max(1, g.n)))
        for backend_name in backends:
            rows = bench_backend(layout, backend_name, platform, reps)
            for r in rows:
                r.update(scale=scale, n=int(g.n), m=int(g.m),
                         k=int(layout.k), q=int(layout.q),
                         edge_tile=int(layout.edge_tile),
                         msg_tile=int(layout.msg_tile),
                         fold_tile=int(layout.fold_tile),
                         fold_q=int(layout.fold_q))
                results.append(r)
            print(f"scale={scale} backend={backend_name}: "
                  + (", ".join(f"{r['kernel']}={r['wall_s']*1e3:.3f}ms"
                               for r in rows) or "no supported kernels"),
                  file=sys.stderr)
    write_telemetry(out_path, results)
    doc = {
        "meta": {
            "platform": platform,
            "jax": jax.__version__,
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
                    help="tiny scale, 1 rep (CI artifact-emission check)")
    ap.add_argument("--scales", default=None,
                    help="comma-separated rmat scales (default 8,10,12)")
    ap.add_argument("--backends", default=None,
                    help="comma-separated backend names (default: all "
                         "resolvable on this platform)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--k", type=int, default=32)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_kernels.json"))
    args = ap.parse_args()

    if args.smoke:
        # two scales x best-of-2: enough signal for the CI regression
        # guard's machine calibration without a full bench run
        scales = [6, 8]
        reps = 2
    else:
        scales = [int(s) for s in (args.scales or "8,10,12").split(",")]
        reps = args.reps
    if args.backends:
        backends = args.backends.split(",")
    else:
        platform = jax.default_backend()
        backends = [n for n in registry.available_backends()
                    if registry.BACKENDS[n].supports(platform, "fused_dc",
                                                     "add", "float32")]
    run(scales, backends, reps, args.k, Path(args.out))


if __name__ == "__main__":
    main()
