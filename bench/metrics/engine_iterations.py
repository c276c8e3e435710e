"""Mean ``EngineResult.iterations`` over the window's runs."""


def read(ctx):
    runs = (ctx.window or {}).get("runs")
    if not runs:
        return None
    return sum(r["iterations"] for r in runs) / len(runs)
