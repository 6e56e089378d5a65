"""``dc_step_ms``: mean ``IterStats.wall_s`` of the window's supersteps
that ran the DC stream for at least one partition, in ms."""


def read(run):
    w = [s["wall_s"] for c in run.calls for s in c["steps"]
         if s["dc_parts"] > 0]
    return 1e3 * sum(w) / len(w) if w else None
