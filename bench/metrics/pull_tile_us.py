"""The pull kernel's device time per tile it ran (us): its time in the
traced window over the window's ``engine.pull_tiles_run`` counters."""
from bench import program_trace
from bench.tracing import PULL_KERNEL, op_seconds


def read(ctx):
    run = program_trace.window_records(ctx, "engine.pull_tiles_run")
    if not ctx.trace or not run:
        return None
    tiles = sum(r["value"] for r in run)
    kernel_s = op_seconds(ctx.trace, PULL_KERNEL)
    if tiles <= 0 or kernel_s <= 0:
        return None
    return 1e6 * kernel_s / tiles
