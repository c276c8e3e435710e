"""The pull kernel's share of its HBM roofline (%): the least time the
window's pull passes need at the published HBM bandwidth, over the kernel's
device time. A pass's least bytes come from the graph's sizes
(``stats.pull_pass_bytes``), not from the tiles the kernel fetched."""
from bench import stats
from bench.tracing import PULL_KERNEL, op_seconds


def read(ctx):
    runs = (ctx.window or {}).get("runs")
    if not ctx.trace or not runs or not ctx.peak:
        return None
    kernel_s = op_seconds(ctx.trace, PULL_KERNEL)
    if kernel_s <= 0:
        return None
    g = ctx.graph
    passes = sum(r["iterations"] for r in runs)
    nbytes = passes * stats.pull_pass_bytes(int(g.src.shape[0]), g.num_vertices)
    return 100.0 * stats.least_seconds(nbytes, ctx.peak["hbm_bytes_per_s"]) / kernel_s
