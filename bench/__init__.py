"""On-chip benchmark of the GraphScale engine (see BENCHMARK.json and PERF.md).

Everything that measures lives here, apart from the program under test:
graph and query generators, plain references, the partition cache, trace
reduction, peaks and the per-layer metric readers. ``run.py`` is the command.
"""
