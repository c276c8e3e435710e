"""Pull-kernel tiles run over the real tiles there were, summed over the
window's runs (%): what dynamic tile skip leaves of the stream (program
counters ``engine.pull_tiles_run`` and ``engine.pull_tiles_real``)."""
from bench import program_trace


def read(ctx):
    run = program_trace.window_records(ctx, "engine.pull_tiles_run")
    real = program_trace.window_records(ctx, "engine.pull_tiles_real")
    total = sum(r["value"] for r in real or ())
    if not run or total <= 0:
        return None
    return 100.0 * sum(r["value"] for r in run) / total
