"""``fold_roofline``: the SC stream's fold (registry kernel ``fold``)
least time over its device time, in %, over the traced window.

Least bytes, from the algorithm's work: each active edge of an SC
partition (the engine's ``sc_e`` counter) is one message that the fold
has to read, and its destination is at least the layout's 4-byte vertex
id; the message values, the padding of the SC budget and the
accumulators are left out, which keeps the count a lower bound.  The
least time is ``4 * sc_e`` bytes at the chip's peak HBM bandwidth; the
device time is every op under ``ppm.fold.*`` (the fold's sort included).
"""
from bench import trace

BYTES_PER_EDGE = 4


def least_bytes(steps) -> int:
    return sum(BYTES_PER_EDGE * (s["sc_e"] or 0) for s in steps
               if s["sc_parts"] > 0)


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = trace.scope_seconds(run.trace, "fold")
    b = least_bytes(s for c in run.traced for s in c["steps"])
    if t <= 0 or b <= 0:
        return None
    return 100.0 * b / run.peaks["hbm_bytes_per_s"] / t
