"""``idle_share``: ``1 - busy / window`` of the traced window, in %;
busy is the union of the device ops' intervals (``XLA Ops``) inside the
``bench.window`` annotation."""
from bench import trace


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s(run.trace) / trace.window_s(
        run.trace))
