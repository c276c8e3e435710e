"""The benchmark's own spans, the profiler capture, and the
reduction from a device trace to busy time, idle gaps and per-op time.

Spans are timed on the host clock around calls into the program. While a
trace is taken they are also written into it as ``TraceAnnotation``s, so
that idle gaps on the device can be put down to what the host was doing.

The reduction runs in two steps: ``extract`` reads the profiler's
``.xplane.pb`` into plain event lists (the form kept under
``bench/tests/data``), and ``reduce_events`` turns those into numbers.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import time
from collections import defaultdict

SPAN_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"  # one event per executed HLO op on a TPU plane
# each Pallas call is one op named after its pallas_call: the pull kernel's
# ``gather_reduce_cores_pallas.9``, the push kernel's ``scatter_reduce_...``
PULL_KERNEL = re.compile(r"gather_reduce_cores_pallas(\.\d+)*")


class Spans:
    """Host-clock spans of one run."""

    def __init__(self):
        self.records: list[dict] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        import jax

        rec = dict(attrs, name=name, t0=time.perf_counter())
        ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name) if self.annotate else None
        if ann is not None:
            ann.__enter__()
        try:
            yield rec
        finally:
            if ann is not None:
                ann.__exit__(None, None, None)
            rec["t1"] = time.perf_counter()
            self.records.append(rec)

    def of(self, name: str) -> list[dict]:
        return [r for r in self.records if r["name"] == name]


def start_trace(out_dir: str):
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the Python tracer would slow the host
    opts.host_tracer_level = 1  # the benchmark's annotations, not every host op
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def stop_trace():
    import jax

    jax.profiler.stop_trace()


def short_name(hlo: str) -> str:
    """An op's name from its HLO text: ``%fusion.11 = u32[..] fusion(..)``
    gives ``fusion.11``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def extract(out_dir: str) -> dict:
    """Events of the newest trace under ``out_dir``: for each device plane
    its ``XLA Ops`` events, and the benchmark's host spans, each as
    ``[name, start_ns, duration_ns]``."""
    import jax

    paths = sorted(glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"devices": {}, "host": []}
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    devices[plane.name] = [
                        [short_name(e.name), e.start_ns, e.duration_ns]
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                ]
    return {"devices": devices, "host": host}


def _self_seconds(events) -> dict:
    """Device seconds by op name, each op charged its own time only: the
    ``XLA Ops`` line nests a loop's body ops inside the loop op."""
    out = defaultdict(float)
    stack = []  # open [name, end, child time] ancestors
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out[done[0]] += done[3]
        if stack:
            stack[-1][3] -= e - s
        stack.append([n, e, s, e - s])
    for done in stack:
        out[done[0]] += done[3]
    return {k: v / 1e9 for k, v in out.items()}


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(events: dict, window: str = SPAN_PREFIX + "window") -> dict:
    """Busy and idle time of the device over the traced window.

    The window is the host span named ``window`` (the whole traced stretch
    when it is missing). Busy is the union of the op intervals inside it,
    averaged over the devices that ran anything; ``op_s`` sums each op
    name's own device time (its nested ops' time left out); ``gaps`` are the idle stretches, each named by the
    innermost benchmark span that covers its middle."""
    host = events["host"]
    win = [h for h in host if h[0] == window]
    devs = {k: v for k, v in events["devices"].items() if v}
    if win:
        w0, w1 = win[0][1], win[0][1] + win[0][2]
    else:
        allev = [e for v in devs.values() for e in v]
        if not allev:
            return {"busy_s": 0.0, "window_s": 0.0, "op_s": {}, "gaps": []}
        w0 = min(e[1] for e in allev)
        w1 = max(e[1] + e[2] for e in allev)
    busy, op_s, gaps = 0.0, defaultdict(float), []
    for evs in devs.values():
        clipped = [
            (max(s, w0), min(s + d, w1), n) for n, s, d in evs
            if s + d > w0 and s < w1
        ]
        for n, sec in _self_seconds([(n, s, e) for s, e, n in clipped]).items():
            op_s[n] += sec / len(devs)
        merged = _union([(s, e) for s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) / 1e9 / len(devs)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    spans = sorted((h for h in host if h[0] != window), key=lambda h: -h[1])
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [h for h in spans if h[1] <= mid < h[1] + h[2]]
        label = min(cover, key=lambda h: h[2])[0] if cover else "outside spans"
        named.append((label.removeprefix(SPAN_PREFIX), (e - s) / 1e9))
    return {
        "busy_s": busy,
        "window_s": (w1 - w0) / 1e9,
        "op_s": dict(op_s),
        "gaps": named,
    }


def op_seconds(summary: dict, pattern: re.Pattern) -> float:
    """Device seconds of the ops whose name matches ``pattern``."""
    return sum(s for name, s in summary["op_s"].items() if pattern.fullmatch(name))


def breakdown(summary: dict, top: int = 10) -> dict:
    """The driver's ``breakdown``: the costliest device ops and the longest
    idle gaps, summed by what the host was doing."""
    by_label = defaultdict(float)
    for label, s in summary["gaps"]:
        by_label[label] += s
    ops = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
