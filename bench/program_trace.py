"""What the program records of itself, for the metric readers: its spans and
counters (``repro.telemetry``), and its device ops grouped by the named
scopes it traces them under.

Program records live in the harness's process, on the same
``time.perf_counter`` clock as the benchmark's own spans: ``window_records``
reads those of the timed window and ``setup_records`` those that ended
before it. A program without ``repro.telemetry`` gives None.

The device trace is the one the harness leaves under
``bench/.cache/trace/<cell>``. ``extract`` keeps each ``XLA Ops`` event with
the program scope it ran under: the innermost name of ``SCOPES`` in the
op's ``op_name`` metadata (a fusion carries its root op's), or ``""`` for
ops outside them, such as the pull kernel. It also keeps the program's host
spans (``graphscale.<name>``) beside the benchmark's (``bench.<name>``).
``scope_seconds`` and ``gaps`` reduce that over the benchmark's window.

    python3 -m bench.program_trace bench/.cache/trace/<cell>

prints both for the newest trace in that directory.
"""
from __future__ import annotations

import sys
from collections import defaultdict
from pathlib import Path

from bench import graphs, tracing

SCOPES = ("gather", "coverage", "split_fold", "merge", "frontier")
PROGRAM_PREFIX = "graphscale."


def telemetry():
    """``repro.telemetry``, or None where the program has none."""
    try:
        from repro import telemetry as t
    except ImportError:
        return None
    return t


def _window(ctx) -> tuple[float, float] | None:
    win = ctx.spans.of("window")
    return (win[0]["t0"], win[0]["t1"]) if win else None


def window_records(ctx, name: str) -> list[dict] | None:
    """The program's records named ``name`` inside the timed window."""
    t, win = telemetry(), _window(ctx)
    if t is None or win is None:
        return None
    return [r for r in t.records(*win) if r["name"] == name]


def setup_records(ctx, name: str) -> list[dict] | None:
    """The program's records named ``name`` that ended before the window."""
    t, win = telemetry(), _window(ctx)
    if t is None or win is None:
        return None
    return [r for r in t.records(t1=win[0]) if r["name"] == name]


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or ``""``. The
    path's last part is the primitive (``gather`` among them), not a scope."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    return next((p for p in reversed(parts) if p in SCOPES), "")


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field, bytes otherwise."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(msg, field: int = 2) -> str:
    return next((bytes(v).decode() for f, v in _fields(msg) if f == field), "")


def op_names(path) -> dict[str, dict[str, str]]:
    """For each device plane of an ``.xplane.pb`` file, each op's HLO text
    (the name its events carry) -> its ``op_name`` metadata.

    The profiler keeps the ``op_name`` only in the op's event metadata, as
    its ``tf_op`` stat (``<op_name>:<type>``), which ``ProfileData`` does
    not read. The file is a ``tsl.profiler.XSpace`` message: planes (field
    1), each with a name (2), event metadata (4) and stat metadata (5), both
    maps from an id (1) to the message (2); an ``XEventMetadata`` has its
    name (2) and stats (5), an ``XStat`` its stat id (1) and text (5)."""
    out = {}
    for f, plane in _fields(memoryview(Path(path).read_bytes())):
        name = _text(plane) if f == 1 else ""
        if not name.startswith("/device:"):
            continue
        # map entries: (field, id, message)
        entries = [(g, e.get(1, 0), e.get(2, b""))
                   for g, e in ((g, dict(_fields(v))) for g, v in _fields(plane) if g in (4, 5))]
        tf_op = {i for g, i, m in entries if g == 5 and _text(m) == "tf_op"}
        ops = {}
        for g, _, meta in entries:
            if g != 4:
                continue
            for h, stat in _fields(meta):
                stat = dict(_fields(stat)) if h == 5 else {}
                if stat.get(1) in tf_op and 5 in stat:
                    ops[_text(meta)] = bytes(stat[5]).decode().rsplit(":", 1)[0]
        out[name] = ops
    return out


_cache: dict = {}  # the last trace read: (path, mtime) -> its events


def extract(trace_dir) -> dict | None:
    """Events of the newest trace under ``trace_dir``: for each device plane
    its ``XLA Ops`` events as ``[name, start_ns, duration_ns, scope]``, and
    the benchmark's and the program's host spans as ``[name, start_ns,
    duration_ns]``. None where there is no trace."""
    import jax

    paths = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        return None
    key = (str(paths[-1]), paths[-1].stat().st_mtime_ns)
    if key in _cache:
        return _cache[key]
    pd = jax.profiler.ProfileData.from_file(str(paths[-1]))
    names = op_names(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name == tracing.DEVICE_LINE:
                ops = names.get(plane.name, {})
                devices[plane.name] = [
                    [tracing.short_name(e.name), e.start_ns, e.duration_ns,
                     scope_of(ops.get(e.name, ""))]
                    for e in line.events
                ]
            elif plane.name.startswith("/host:"):
                host += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                         if e.name.startswith((tracing.SPAN_PREFIX, PROGRAM_PREFIX))]
    _cache.clear()
    _cache[key] = {"devices": devices, "host": host}
    return _cache[key]


def trace_events(ctx) -> dict | None:
    """``extract`` of the trace of ``ctx``'s traced window, or None where
    there is none: an untraced run, or a trace left by another run than this
    one (its window differs from the harness's)."""
    if not ctx.trace:
        return None
    events = extract(graphs.CACHE_DIR.parent / "trace" / ctx.cell["name"])
    win = [h for h in (events or {"host": []})["host"]
           if h[0] == tracing.SPAN_PREFIX + "window"]
    if not win or abs(win[0][2] / 1e9 - ctx.trace["window_s"]) > 1e-6:
        return None
    return events


def scope_seconds(events: dict | None) -> dict | None:
    """Device seconds by program scope over the benchmark's window (each op
    charged its own time, ``""`` for ops outside the scopes) and
    ``busy_s``; None where no device op carries a scope."""
    if not events or not any(e[3] for v in events["devices"].values() for e in v):
        return None
    by_scope = {p: [[e[3], e[1], e[2]] for e in v] for p, v in events["devices"].items()}
    s = tracing.reduce_events({"devices": by_scope, "host": events["host"]})
    return dict(s["op_s"], busy_s=s["busy_s"])


def gaps(events: dict | None) -> dict | None:
    """Idle seconds of the device over the benchmark's window, summed by
    the innermost span that covers each gap's middle: the program's
    (``graphscale.*``) where one does, else the benchmark's."""
    if not events or not any(events["devices"].values()):
        return None
    named = {p: [e[:3] for e in v] for p, v in events["devices"].items()}
    out = defaultdict(float)
    for label, s in tracing.reduce_events({"devices": named, "host": events["host"]})["gaps"]:
        out[label] += s
    return dict(out)


def main(argv) -> int:
    events = extract(argv[0]) if argv else None
    if events is None:
        print("usage: python3 -m bench.program_trace <trace dir holding a trace>",
              file=sys.stderr)
        return 2
    for title, table in (("device seconds by scope", scope_seconds(events)),
                         ("idle seconds by span", gaps(events))):
        print(title)
        for k, v in sorted((table or {}).items(), key=lambda kv: -kv[1]):
            print(f"  {k or '(outside the scopes)':32s} {v:.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
