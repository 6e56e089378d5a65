"""Small graphs and tiny cells for the benchmark's CPU tests."""
import json
import time
import types

import numpy as np

from bench import harness

GEOMETRY = {"k": 2, "edge_tile": 128, "msg_tile": 128, "fold_tile": 128,
            "fold_q": 128}


def fixed_generator(n, u, v):
    """A generator module that returns one given edge list."""
    return types.SimpleNamespace(
        edges=lambda cfg, seed: (n, np.asarray(u, np.int32),
                                 np.asarray(v, np.int32)))


def app(n, u, v, mode="hybrid", seed=0):
    mod = harness.load_module(harness.BENCH / "apps" / "bfs.py")
    return mod.App({"structure_seed": 0, "geometry": GEOMETRY},
                   {"roots": 1, "mode": mode}, seed,
                   fixed_generator(n, u, v), {})


def tiny_cell(name="graph500-22.bfs", scale=10):
    """The committed cell ``name`` with its graph cut to ``scale``."""
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    c = harness.cell(name, bench)
    c.config["scale"] = scale
    return c


def run(c, seed=2 ** 31 + 11, seconds=0.5, trace=False):
    """A whole run off the chip: no device metric is reported."""
    return harness.run(c, seed, seconds, trace, time.perf_counter(),
                       lambda *a, **k: None, device=None)
