"""``stats_idle_share``: the traced window's device-idle time inside the
engine's ``engine.part_stats`` spans (the partition-stats dispatch and
its copy to the host, one per superstep), over the window, in %."""
from bench import spans


def read(run):
    return spans.idle_share(run, {"engine.part_stats"})
