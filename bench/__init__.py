"""On-chip benchmark of the graph engine: see ``run.py`` and
``BENCHMARK.json`` at the root of the repository."""
