"""Mean host time of one run's label set-up (ms): the program's
``engine.prepare_labels`` spans in the window (initial labels, the stride
permutation and their upload)."""
from bench import program_trace


def read(ctx):
    spans = program_trace.window_records(ctx, "engine.prepare_labels")
    if not spans:
        return None
    return 1e3 * sum(r["t1"] - r["t0"] for r in spans) / len(spans)
