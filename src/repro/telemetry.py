"""The program's own spans and counters, kept in memory.

``span(name, **attrs)`` times a block on the host's ``time.perf_counter``
clock. Its record names the span that encloses it (``parent``) and its run:
the outermost span open on the thread when it started, so every span and
counter under one ``engine.run`` shares that run's id. Each span is also a
``jax.profiler.TraceAnnotation`` named ``graphscale.<name>``, which puts it
on the device ops' clock in a profiler trace and costs next to nothing
while no trace is taken. ``count(name, value, **attrs)`` records a number at
one instant, with the same ``parent`` and ``run``.

Records go into one bounded buffer per process, the oldest dropped first.
``records(t0, t1)`` reads those that lie within a stretch of the same clock
and ``clear()`` empties it. Recording is always on, so a traced run and a
timed run execute the same program.

Compiles are counted as well: a ``jax.monitoring`` listener, registered when
this module is imported, records each tracing, lowering, backend compile and
persistent-cache read as a ``compile`` counter. Its value is the event's
duration in seconds, and the event ended when the counter was recorded.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

import jax

ANNOTATION_PREFIX = "graphscale."
MAX_RECORDS = 1 << 16
# the backend compile includes the cache read; tracing and lowering precede it
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)

_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_open = threading.local()  # this thread's open spans, outermost first


def _open_spans() -> list:
    if not hasattr(_open, "spans"):
        _open.spans = []
    return _open.spans


def _links(stack: list) -> dict:
    if not stack:
        return {"parent": None, "run": None}
    return {"parent": stack[-1]["id"], "run": stack[0]["id"]}


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time the enclosed block; yields the record, which the block may add
    attributes to."""
    stack = _open_spans()
    rec = dict(attrs, kind="span", name=name, id=next(_ids), **_links(stack))
    if rec["run"] is None:
        rec["run"] = rec["id"]
    stack.append(rec)
    with jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name):
        rec["t0"] = time.perf_counter()
        try:
            yield rec
        finally:
            rec["t1"] = time.perf_counter()
            stack.pop()
            _records.append(rec)


def count(name: str, value, **attrs):
    """Record ``value`` under ``name`` at this instant."""
    t = time.perf_counter()
    _records.append(dict(attrs, kind="count", name=name, value=value, t0=t, t1=t,
                         **_links(_open_spans())))


def records(t0: float | None = None, t1: float | None = None) -> list[dict]:
    """The buffered records that start at or after ``t0`` and end at or
    before ``t1`` (``perf_counter`` seconds), in the order they ended."""
    return [r for r in list(_records)
            if (t0 is None or r["t0"] >= t0) and (t1 is None or r["t1"] <= t1)]


def clear():
    _records.clear()


def _on_duration(event: str, duration: float, **kwargs):
    if event in COMPILE_EVENTS:
        count("compile", duration, event=event.rsplit("/", 1)[-1],
              fun=kwargs.get("fun_name"))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
