"""``layout_s``: host seconds of the system's ``build_layout`` in set-up
(the graph-ingest layer), on the benchmark's clock."""


def read(run):
    return run.timings.get("layout_s")
