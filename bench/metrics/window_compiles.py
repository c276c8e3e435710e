"""Compile events inside the timed window: tracings, lowerings, backend
compiles and persistent-cache reads (the program's ``compile`` counters).
Every shape is warmed up in set-up, so this reads 0."""
from bench import program_trace


def read(ctx):
    events = program_trace.window_records(ctx, "compile")
    return None if events is None else len(events)
