"""Device time of the ops under the program's ``split_fold`` scope
(``combine_split_rows``, the level-2 fold of hub-split rows) as a share of
the device's busy time over the traced window (%)."""
from bench import program_trace


def read(ctx):
    s = program_trace.scope_seconds(program_trace.trace_events(ctx))
    if not s or s["busy_s"] <= 0:
        return None
    return 100.0 * s.get("split_fold", 0.0) / s["busy_s"]
