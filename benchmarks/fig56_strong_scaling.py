"""Paper Figs. 5/6: strong scaling of BFS and PageRank.

The paper scales OS threads on fixed input; the TPU-mapping analogue is the
device count.  The reproduction reports the *per-device work and wire
bytes* of the distributed engine as the device count scales (the quantities
that determine scaling on real hardware), measured from real multi-device
executions.

On a CPU host each device count runs in a child process with that many
virtual CPU devices (the count is fixed before JAX starts).  On an
accelerator the process that asks already holds the chips, and a child
could not reach them, so the benchmark measures in-process over the
devices present.  A failed measurement raises: no row is ever written for
a run that did not finish.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import emit

_CHILD = ("import json; from benchmarks.fig56_strong_scaling import measure; "
          "print(json.dumps(measure({D}, {scale})))")


def measure(D: int, scale: int) -> dict:
    """BFS and PageRank on a ``D``-device mesh over ``rmat(scale)``."""
    import time

    import jax
    import numpy as np
    from jax.sharding import AxisType

    from repro.apps.bfs import bfs_program
    from repro.apps.pagerank import pagerank_program
    from repro.dist.engine import DistEngine
    from repro.graph import build_layout, rmat
    from repro.graph.shard import shard_layout

    mesh = jax.make_mesh((D,), ("dev",), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:D])
    g = rmat(scale, 16, seed=1)
    L = build_layout(g, k=max(16, 4 * D), edge_tile=64, msg_tile=32)
    SL = shard_layout(L, D)
    N = D * SL.nv
    src = int(np.argmax(g.out_degrees()))

    parent = np.full(N, -1, np.int32); parent[src] = src
    level = np.full(N, -1, np.int32); level[src] = 0
    f = np.zeros(N, bool); f[src] = True
    eng = DistEngine(SL, bfs_program(), mesh, mode="hybrid")
    st = {"parent": parent, "level": level,
          "vid": np.arange(N, dtype=np.uint32)}
    eng.run(st, f)                                  # warm (compiles)
    t0 = time.time()
    eng.run(st, f)
    bfs_t = time.time() - t0

    pr0 = np.zeros(N, np.float32); pr0[:g.n] = 1.0 / g.n
    deg = np.zeros(N, np.float32); deg[:L.n_pad] = SL.deg[:L.n_pad]
    f = np.zeros(N, bool); f[:g.n] = True
    eng = DistEngine(SL, pagerank_program(g.n), mesh, mode="dc")
    st0 = {"pr": pr0, "deg": deg}
    eng.run(st0, f, max_iters=3, until_empty=False)
    t0 = time.time()
    eng.run(st0, f, max_iters=3, until_empty=False)
    pr_t = (time.time() - t0) / 3
    return dict(D=D, platform=jax.devices()[0].platform, bfs_s=bfs_t,
                pr_iter_s=pr_t, edges_per_dev=int(SL.ne_d),
                dc_slots_per_dev=int(D * SL.S))


def _measure_in_child(D: int, scale: int) -> dict:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={D}",
               PYTHONPATH=os.pathsep.join([os.path.join(repo, "src"), repo]))
    r = subprocess.run([sys.executable, "-c",
                        _CHILD.format(D=D, scale=scale)],
                       capture_output=True, text=True, env=env, timeout=1200,
                       cwd=repo)
    if r.returncode != 0:
        raise RuntimeError(f"fig56 measurement on {D} CPU devices failed "
                           f"(exit {r.returncode}):\n{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(scale: int = 12, devices=(1, 2, 4, 8)):
    import jax
    if jax.default_backend() == "cpu":
        results = [_measure_in_child(D, scale) for D in devices]
    else:
        # this process holds the accelerator: measure over what is here
        results = [measure(len(jax.devices()), scale)]
    rows = [(d["D"], d["platform"], f"{d['bfs_s']*1e3:.0f}",
             f"{d['pr_iter_s']*1e3:.0f}", d["edges_per_dev"],
             d["dc_slots_per_dev"]) for d in results]
    emit(rows, ["devices", "platform", "bfs_ms", "pr_iter_ms",
                "edges_per_dev", "dc_slots_per_dev"])
    return rows


if __name__ == "__main__":
    from repro.compile_cache import setup_compile_cache
    setup_compile_cache()
    run()
