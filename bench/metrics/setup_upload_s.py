"""Seconds of set-up spent uploading the partition's arrays to the device:
the program's ``engine.upload`` spans before the window."""
from bench import program_trace


def read(ctx):
    spans = program_trace.setup_records(ctx, "engine.upload")
    if not spans:
        return None
    return sum(r["t1"] - r["t0"] for r in spans)
