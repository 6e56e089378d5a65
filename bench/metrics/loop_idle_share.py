"""``loop_idle_share``: the traced window's device-idle time inside the
engine's ``engine.split``, ``engine.dispatch`` and ``engine.record``
spans (the loop's host work: the Eq. 1 split, the phases' dispatch and
the superstep's telemetry), over the window, in %."""
from bench import spans


def read(run):
    return spans.idle_share(run, {"engine.split", "engine.dispatch",
                                  "engine.record"})
