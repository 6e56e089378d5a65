"""``fused_dc_roofline``: the DC stream's least time over its device
time, in %, over the traced window.

Least bytes count the algorithm's work, not its implementation: every
active edge of a DC partition (the engine's ``dc_e`` counter for the
superstep) has to deliver its source's value to its destination, and
the destination can be no less than the layout's 4-byte vertex id.  So
the least bytes are ``4 * dc_e`` summed over the traced supersteps; the
values, the accumulators and every inactive edge that the stream also
touches are left out, which keeps the count a lower bound.  The least
time is those bytes at the chip's peak HBM bandwidth (no flop bound: the
stream does no arithmetic worth counting); the device time is every op
under the ``ppm.fused_dc.*`` scope, the XLA gather of the stream
included.
"""
from bench import trace

BYTES_PER_EDGE = 4


def least_bytes(steps) -> int:
    return sum(BYTES_PER_EDGE * (s["dc_e"] or 0) for s in steps
               if s["dc_parts"] > 0)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = trace.scope_seconds(run.trace, "fused_dc")
    b = least_bytes(s for c in run.traced for s in c["steps"])
    if t <= 0 or b <= 0:
        return None
    return 100.0 * b / run.peaks["hbm_bytes_per_s"] / t
