"""``teps``: the work of every request the window answered, over the
window's wall time (from its start to the end of its last request, the
gaps between calls included).  A request's work is what its traffic
counts: for BFS, the undirected edges of the searched component
(Graph500's TEPS)."""


def read(run):
    return sum(run.work) / run.window_s
