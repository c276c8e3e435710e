"""Seconds of set-up spent tracing, lowering and compiling, or reading
compiled programs from the persistent cache: the union of the program's
``compile`` events before the window (nested events counted once)."""
from bench import program_trace
from bench.tracing import _union


def read(ctx):
    events = program_trace.setup_records(ctx, "compile")
    if events is None:
        return None
    return sum(e - s for s, e in _union([(r["t1"] - r["value"], r["t1"]) for r in events]))
