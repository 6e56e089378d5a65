"""``host_share``: the share of the window's request time that no
superstep covers: ``1 - sum(IterStats.wall_s) / sum(call wall)``, in %.

A superstep's ``wall_s`` is the engine's own host clock from its
dispatch to its ``block_until_ready``; what is left is the engine loop's
host work between supersteps (the partition-stats copy and the Eq. 1
split) and the app's set-up and copies of each answer."""


def read(run):
    call = sum(c["wall_s"] for c in run.calls)
    step = sum(s["wall_s"] for c in run.calls for s in c["steps"])
    if call <= 0:
        return None
    return 100.0 * (1.0 - step / call)
