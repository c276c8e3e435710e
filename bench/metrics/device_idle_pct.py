"""Share of the traced window in which no operation ran on the device (%)."""


def read(ctx):
    if not ctx.trace or ctx.trace["busy_s"] <= 0:  # no device ran anything
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
