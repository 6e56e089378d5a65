#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip it is started on.

    python3 bench/run.py --workload graph500-22.bfs --seed 7 --seconds 51 --trace 0

The cell is a workload of ``BENCHMARK.json`` (see ``bench/harness.py``
for the files it resolves to).  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics, read partly from
a profiler trace of a short extra window.  ``--control 1`` puts the
reference's answers, cut one level short, through the same comparison
after the system's, and logs its numbers on a ``[control]`` line: they
must break the limits (the benchmark's own runs never pass it).

There is no CPU fallback: without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit); the last lines
of standard error repeat the numbers compared.  Progress lines go to
standard error, each with ``t``, host seconds since start.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def log(tag: str, **fields):
    fields["t"] = time.perf_counter() - T0
    print(f"[{tag}] " + json.dumps(fields, default=str), file=sys.stderr,
          flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    for p in (ROOT, ROOT / "src"):
        sys.path.insert(0, str(p))
    from bench import harness
    cell = harness.cell(args.workload)
    chips = int(cell.spec["chips"])

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"bench: JAX finds no TPU (platform {platform!r}); the "
              "benchmark has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"bench: {args.workload} needs {chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.compile_cache import setup_compile_cache
    cache = setup_compile_cache()
    # cache every program, however quick to compile, so that a run
    # after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log("device", compile_cache=cache, jax=jax.__version__, **device)

    result, checks = harness.run(cell, args.seed, args.seconds,
                                 bool(args.trace), T0, log,
                                 control=bool(args.control), device=device)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
