"""Benchmark harness: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig4,...]

Prints CSV sections; the dry-run roofline tables live in results/dryrun and
EXPERIMENTS.md (they need the 512-device AOT environment, not this harness).
"""
import argparse
import sys
import time


def main() -> None:
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--scale", type=int, default=None,
                    help="rmat scale for graph benchmarks")
    ap.add_argument("--skip-scaling", action="store_true",
                    help="skip the strong-scaling benchmark (on a CPU "
                         "host it runs each device count in a child "
                         "process; on an accelerator, in this one)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from . import (fig4_exec_time, fig56_strong_scaling, fig78_weak_scaling,
                   fig9_modes, tab456_traffic)
    sections = [
        ("fig4_exec_time", lambda: fig4_exec_time.run(args.scale)),
        ("tab456_traffic", lambda: tab456_traffic.run(args.scale)),
        ("fig9_modes", lambda: fig9_modes.run(args.scale)),
        ("fig78_weak_scaling", lambda: fig78_weak_scaling.run()),
    ]
    if not args.skip_scaling:
        sections.append(("fig56_strong_scaling",
                         lambda: fig56_strong_scaling.run()))
    for name, fn in sections:
        if only and name not in only:
            continue
        print(f"\n=== {name} ===")
        t0 = time.time()
        fn()
        print(f"# section wall: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == '__main__':
    main()
