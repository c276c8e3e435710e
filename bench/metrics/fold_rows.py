"""Rows the hub-split fold combined per phase pass: the program's
``engine.fold_rows`` counters in the window (every bucket's rows that moved
a piece out of their block, times the iterations that pulled) over the
window's phase passes (iterations times the partition's phases)."""
from bench import program_trace


def read(ctx):
    folds = program_trace.window_records(ctx, "engine.fold_rows")
    runs = (ctx.window or {}).get("runs")
    if not folds or not runs:
        return None
    passes = sum(r["iterations"] for r in runs) * ctx.graph.pg.l
    return sum(r["value"] for r in folds) / passes if passes > 0 else None
