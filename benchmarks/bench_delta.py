"""Dynamic-graph delta benchmark: dirty-partition relayout vs full
rebuild, and incremental recompute vs cold convergence.

  PYTHONPATH=src python -m benchmarks.bench_delta [--smoke]
      [--scales 10,12] [--fracs 0.05,0.1,0.25] [--out BENCH_delta.json]

For each scale an rmat graph is laid out with ``k`` partitions, then a
batch of edge insertions confined to ``ceil(frac * k)`` partitions (both
endpoints — so the dirty fraction is controlled) is applied two ways:

  * ``delta_relayout_p<pct>`` — :func:`repro.graph.delta.apply_delta`:
    only the dirty partitions' CSR rows, scatter slots and gather bins
    are recomputed; everything else is sliced out of the old layout.
  * ``delta_rebuild_p<pct>``  — the reference path: edit the edge list
    (``DeltaBuffer.edit_graph``) and :func:`build_layout` from scratch.

The two produce bit-identical layouts (tests/test_delta.py), so the gap
is pure relayout work.  The claim the committed baseline pins down: at a
<= 10% dirty fraction the scoped relayout beats the full rebuild.

A second pair times closing the loop on the result side, on the
symmetrized graph:

  * ``delta_cc_cold``   — connected components from scratch on the
    post-delta layout;
  * ``delta_cc_resume`` — the same fixpoint restarted from the pre-delta
    labels with ``DeltaBuffer.touched()`` as the frontier (exact for the
    min monoid under insertion-only deltas).

Rows land in ``BENCH_delta.json`` with the ``BENCH_kernels.json`` schema
(``monoid``/``backend``/``scale`` keys), so
``tools/check_bench_regression.py`` gates them in CI unchanged.
``--smoke`` (the CI serve lane) runs one scale at best-of-2.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

from repro.backend import registry
from repro.core.engine import _next_pow2
from repro.graph import (DeltaBuffer, apply_delta, build_layout, rmat,
                         symmetrize)

from .common import time_best as _time_best
from .common import write_telemetry

REPO_ROOT = Path(__file__).resolve().parents[1]


def confined_delta(layout, frac: float, n_ops: int, rng,
                   symmetric: bool = False) -> DeltaBuffer:
    """``n_ops`` edge insertions with BOTH endpoints inside the first
    ``ceil(frac * k)`` partitions, so exactly that fraction of the
    layout is dirty."""
    k, q, n = layout.k, layout.q, layout.n
    dirty_k = max(1, int(np.ceil(frac * k)))
    hi = min(n, dirty_k * q)
    d = DeltaBuffer.for_layout(layout)
    for _ in range(n_ops):
        u = int(rng.integers(0, hi))
        v = int(rng.integers(0, hi))
        w = float(rng.random() + 0.1) if layout.weighted else None
        d.insert(u, v, w)
        if symmetric:
            d.insert(v, u, w)
    return d


def bench_relayout(g, layout, frac: float, reps: int, rng):
    """(relayout_wall, rebuild_wall, dirty_parts) for one confined
    insertion batch."""
    d = confined_delta(layout, frac, n_ops=64, rng=rng)
    kw = dict(k=layout.k, edge_tile=layout.edge_tile,
              msg_tile=layout.msg_tile, fold_tile=layout.fold_tile,
              fold_q=layout.fold_q)

    def relayout():
        apply_delta(layout, d)

    def rebuild():
        build_layout(d.edit_graph(g), **kw)

    relayout(); rebuild()                       # warm any lazy imports
    return (_time_best(relayout, reps), _time_best(rebuild, reps),
            len(d.dirty_partitions()))


def bench_cc_resume(layout, frac: float, reps: int, rng):
    """(resume_wall, cold_wall) for connected components after a
    symmetric confined insertion batch."""
    from repro.apps import connected_components

    d = confined_delta(layout, frac, n_ops=32, rng=rng, symmetric=True)
    new_layout = apply_delta(layout, d)
    old_labels = connected_components(layout)["label"]
    touched = d.touched()

    def cold():
        connected_components(new_layout)

    def resume():
        connected_components(new_layout, resume_labels=old_labels,
                             touched=touched)

    cold(); resume()                            # warmup: compile both
    return _time_best(resume, reps), _time_best(cold, reps)


def _delta_layout(g, k: int):
    """Tile geometry proportional to the per-block edge count (same
    reasoning as bench_serve's _serving_layout): the static 256-slot
    default pads every non-empty (p, p') block of a small graph to a
    mostly-empty tile, and the padded-bin memcpy — identical work for
    relayout and rebuild — swamps the dirty-vs-full signal this
    benchmark is after."""
    k = min(k, max(1, g.n))
    edge_tile = min(256, max(16, _next_pow2(4 * g.m // (k * k))))
    return build_layout(g, k=k, edge_tile=edge_tile,
                        msg_tile=max(8, edge_tile // 2))


def run(scales, fracs, reps: int, k: int, out_path: Path):
    platform = jax.default_backend()
    results = []
    for scale in scales:
        g = rmat(scale, 8, seed=1, weighted=True)
        layout = _delta_layout(g, k)
        rng = np.random.default_rng(3)
        for frac in fracs:
            re_s, rb_s, dirty = bench_relayout(g, layout, frac, reps, rng)
            pct = int(round(frac * 100))
            for variant, wall in (("relayout", re_s), ("rebuild", rb_s)):
                results.append({
                    "kernel": f"delta_{variant}_p{pct}",
                    "monoid": "min", "backend": "host",
                    "scale": scale, "n": int(g.n), "m": int(g.m),
                    "dirty_parts": dirty, "k": int(layout.k),
                    "wall_s": wall,
                })
            print(f"scale={scale} dirty={pct}% ({dirty}/{layout.k} parts): "
                  f"relayout={re_s*1e3:.1f}ms rebuild={rb_s*1e3:.1f}ms "
                  f"speedup={rb_s/max(re_s,1e-9):.2f}x", file=sys.stderr)
        # incremental recompute on the symmetrized graph (CC needs the
        # undirected view); smallest dirty fraction = the serving case
        gs = symmetrize(g)
        lays = _delta_layout(gs, k)
        backend = registry.default_backend_name(kernel="fused_dc")
        res_s, cold_s = bench_cc_resume(lays, min(fracs), reps, rng)
        for variant, wall in (("resume", res_s), ("cold", cold_s)):
            results.append({
                "kernel": f"delta_cc_{variant}",
                "monoid": "min", "backend": backend,
                "scale": scale, "n": int(gs.n), "m": int(gs.m),
                "wall_s": wall,
            })
        print(f"scale={scale} cc: resume={res_s*1e3:.1f}ms "
              f"cold={cold_s*1e3:.1f}ms "
              f"speedup={cold_s/max(res_s,1e-9):.2f}x", file=sys.stderr)
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
                    help="one scale, best-of-2 (CI serve lane)")
    ap.add_argument("--scales", default=None,
                    help="comma-separated rmat scales (default 10,12)")
    ap.add_argument("--fracs", default=None,
                    help="comma-separated dirty fractions "
                         "(default 0.05,0.1,0.25)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--out", default=str(REPO_ROOT / "BENCH_delta.json"))
    args = ap.parse_args()

    if args.smoke:
        scales, reps = [12], 2
    else:
        # default includes the smoke scale so the committed baseline
        # always has rows for the CI guard to match against
        scales = [int(s) for s in (args.scales or "10,12").split(",")]
        reps = args.reps
    fracs = [float(f) for f in (args.fracs or "0.05,0.1,0.25").split(",")]
    run(scales, fracs, reps, args.k, Path(args.out))


if __name__ == "__main__":
    main()
