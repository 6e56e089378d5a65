"""``sc_step_ms``: mean ``IterStats.wall_s`` of the window's SC-only
supersteps (no partition on the DC stream), in ms."""


def read(run):
    w = [s["wall_s"] for c in run.calls for s in c["steps"]
         if s["dc_parts"] == 0 and s["sc_parts"] > 0]
    return 1e3 * sum(w) / len(w) if w else None
