"""``setup_s``: host seconds from the start of the run to the start of
the window: data generated from the seed, the system's ingest and layout,
device transfer, engine construction and warm-up (compiles, or loads
from the persistent compilation cache)."""


def read(run):
    return run.setup_s
