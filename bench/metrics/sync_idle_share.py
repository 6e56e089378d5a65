"""``sync_idle_share``: the traced window's device-idle time inside the
engine's ``engine.sync`` spans (``block_until_ready`` on each
superstep's frontier), over the window, in %."""
from bench import spans


def read(run):
    return spans.idle_share(run, {"engine.sync"})
