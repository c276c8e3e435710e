"""The pull gather-reduce kernel's device time as a share of the device's
busy time over the traced window (%)."""
from bench.tracing import PULL_KERNEL, op_seconds


def read(ctx):
    if not ctx.trace or ctx.trace["busy_s"] <= 0:
        return None
    kernel_s = op_seconds(ctx.trace, PULL_KERNEL)
    return 100.0 * kernel_s / ctx.trace["busy_s"] if kernel_s > 0 else None
