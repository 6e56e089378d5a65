"""``pr_iter_ms``: the mean over the window's PageRank calls of the
``engine.fused`` span's wall time (the ``fused_run`` event's ``wall_s``:
the device loop's dispatch and its ``block_until_ready``) over its
iterations, in ms."""


def read(run):
    w = [s["wall_s"] / s["iters"] for c in run.calls for s in c["steps"]
         if s.get("iters")]
    return 1e3 * sum(w) / len(w) if w else None
