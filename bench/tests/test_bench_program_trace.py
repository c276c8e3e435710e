"""The readers of the program's own records (``bench/program_trace.py`` and
the metrics built on it) against a recorded chip trace: two consecutive BFS
runs cut from a traced ``g500-s20-bfs`` window on one TPU v5e. The fixture
holds each device op with the program scope it ran under, the benchmark's
and the program's host spans (its ``bench.window`` is the two runs), and
the program's records of set-up and of those runs, on the process clock
(``window`` gives the two runs' stretch on that clock)."""
import gzip
import json
import types

import numpy as np
import pytest
from bench_testkit import REPO

from bench import harness, program_trace, tracing

DATA = REPO / "bench" / "tests" / "data" / "program_trace_g500-s20-bfs.json.gz"
# readers and the source each needs
DEVICE = ("split_fold_pct", "pull_tile_us")
PROGRAM = ("active_tile_pct", "pull_tile_us", "label_prep_ms", "setup_upload_s",
           "setup_compile_s", "window_compiles")
# what the recorded runs read
SPLIT_FOLD_PCT = 42.84802255877206
PULL_TILE_US = 13.739591845100229


@pytest.fixture(scope="module")
def fx():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


class _Telemetry:
    """Stands in for ``repro.telemetry``, holding the recorded records."""

    def __init__(self, recs):
        self.recs = recs

    def records(self, t0=None, t1=None):
        return [r for r in self.recs
                if (t0 is None or r["t0"] >= t0) and (t1 is None or r["t1"] <= t1)]


def _ops(fx):
    return {p: [e[:3] for e in v] for p, v in fx["devices"].items()}


def _ctx(fx, monkeypatch, traced=True, program=True, scoped=True):
    events = {"devices": fx["devices"], "host": fx["host"]}
    if not scoped:  # a program that names no scope
        events["devices"] = {p: [e[:3] + [""] for e in v] for p, v in _ops(fx).items()}
    monkeypatch.setattr(program_trace, "trace_events",
                        lambda ctx: events if ctx.trace else None)
    monkeypatch.setattr(program_trace, "telemetry",
                        lambda: _Telemetry(fx["records"]) if program else None)
    spans = tracing.Spans()
    spans.records.append({"name": "window", "t0": fx["window"][0], "t1": fx["window"][1]})
    trace = tracing.reduce_events({"devices": _ops(fx), "host": fx["host"]}) if traced else None
    return types.SimpleNamespace(cell={"name": "g500-s20-bfs"}, trace=trace, spans=spans)


def _read(name, ctx):
    return harness.Bench().reader(name)(ctx)


def _window_ns(fx):
    (w,) = [h for h in fx["host"] if h[0] == "bench.window"]
    return w[1], w[1] + w[2]


def _in_window(fx, name):
    t0, t1 = fx["window"]
    return [r for r in fx["records"] if r["name"] == name and r["t0"] >= t0 and r["t1"] <= t1]


def _busy_us(fx):
    """Independent timeline: one flag per microsecond of the window."""
    w0, w1 = _window_ns(fx)
    us = np.zeros(int((w1 - w0) // 1000) + 2, dtype=bool)
    (evs,) = fx["devices"].values()
    for _, st, d, _ in evs:
        a, b = max(st, w0), min(st + d, w1)
        if b > a:
            us[int((a - w0) // 1000): int(np.ceil((b - w0) / 1000))] = True
    return us


def test_split_fold_pct_against_hand_sums(fx, monkeypatch):
    w0, w1 = _window_ns(fx)
    (evs,) = fx["devices"].values()
    # the fold's ops are leaves of the loop body: their own time is their length
    fold = sum(min(s + d, w1) - max(s, w0) for _, s, d, sc in evs
               if sc == "split_fold" and s + d > w0 and s < w1) / 1e9
    busy = _busy_us(fx).sum() / 1e6
    got = _read("split_fold_pct", _ctx(fx, monkeypatch))
    assert got == pytest.approx(100 * fold / busy, rel=1e-3)
    scopes = program_trace.scope_seconds({"devices": fx["devices"], "host": fx["host"]})
    assert set(scopes) - {"busy_s"} <= set(program_trace.SCOPES) | {""}
    assert sum(v for k, v in scopes.items() if k != "busy_s") == pytest.approx(
        scopes["busy_s"], rel=1e-6)  # own times add up to busy: nothing twice
    assert got == pytest.approx(SPLIT_FOLD_PCT)


def test_pull_tile_us_against_hand_sums(fx, monkeypatch):
    (evs,) = fx["devices"].values()
    kernel_ns = sum(d for n, _, d, _ in evs if tracing.PULL_KERNEL.fullmatch(n))
    tiles = sum(r["value"] for r in _in_window(fx, "engine.pull_tiles_run"))
    assert len(_in_window(fx, "engine.pull_tiles_run")) == 2  # one per run
    got = _read("pull_tile_us", _ctx(fx, monkeypatch))
    assert got == pytest.approx(kernel_ns / 1e3 / tiles)
    assert got == pytest.approx(PULL_TILE_US)


def test_program_record_readers_against_hand_sums(fx, monkeypatch):
    ctx = _ctx(fx, monkeypatch)
    run = sum(r["value"] for r in _in_window(fx, "engine.pull_tiles_run"))
    real = sum(r["value"] for r in _in_window(fx, "engine.pull_tiles_real"))
    assert _read("active_tile_pct", ctx) == pytest.approx(100 * run / real)
    prep = _in_window(fx, "engine.prepare_labels")
    assert len(prep) == 2
    assert _read("label_prep_ms", ctx) == pytest.approx(
        1e3 * np.mean([r["t1"] - r["t0"] for r in prep]))
    t0 = fx["window"][0]
    ups = [r for r in fx["records"] if r["name"] == "engine.upload" and r["t1"] <= t0]
    assert _read("setup_upload_s", ctx) == pytest.approx(sum(r["t1"] - r["t0"] for r in ups))
    assert _read("window_compiles", ctx) == len(_in_window(fx, "compile")) == 0


def test_setup_compile_s_counts_nested_events_once(fx, monkeypatch):
    t0 = fx["window"][0]
    spans = sorted((r["t1"] - r["value"], r["t1"]) for r in fx["records"]
                   if r["name"] == "compile" and r["t1"] <= t0)
    # sweep the endpoints, counting how many events are open
    edges = sorted([(s, 1) for s, _ in spans] + [(e, -1) for _, e in spans])
    covered, depth, start = 0.0, 0, None
    for x, step in edges:
        if depth == 0 and step == 1:
            start = x
        depth += step
        if depth == 0:
            covered += x - start
    got = _read("setup_compile_s", _ctx(fx, monkeypatch))
    assert got == pytest.approx(covered)
    assert max(e - s for s, e in spans) <= got < sum(e - s for s, e in spans)


def test_gaps_by_program_span_against_a_timeline(fx):
    """Each idle stretch goes to the innermost span over its middle: the
    program's where one covers it, else the benchmark's."""
    w0, _ = _window_ns(fx)
    idle = ~_busy_us(fx)[:-1]
    # paint spans from the longest to the shortest: the innermost wins
    label = np.full(idle.shape, "outside spans", dtype=object)
    for name, s, d in sorted((h for h in fx["host"] if h[0] != "bench.window"),
                             key=lambda h: -h[2]):
        a, b = max(0, int((s - w0) // 1000)), int((s + d - w0) // 1000)
        label[a:b] = name.removeprefix("bench.")
    flips = np.flatnonzero(np.diff(np.concatenate([[0], idle.astype(int), [0]])))
    want = {}
    for a, b in zip(flips[::2], flips[1::2]):
        k = label[(a + b) // 2]
        want[k] = want.get(k, 0.0) + (b - a) / 1e6
    got = program_trace.gaps({"devices": fx["devices"], "host": fx["host"]})
    for k in set(got) | set(want):  # the timeline misses sub-microsecond gaps
        assert got.get(k, 0.0) == pytest.approx(want.get(k, 0.0), abs=2e-6 * len(flips)), k
    # the recorded runs: BFS's initial labels, then the dispatch of each run
    assert got["graphscale.init"] == pytest.approx(0.000440921)
    assert got["graphscale.engine.execute"] == pytest.approx(0.008943398)


@pytest.mark.parametrize("missing", ["trace", "program", "scopes"])
@pytest.mark.parametrize("name", sorted(set(DEVICE + PROGRAM)))
def test_reader_without_its_source_reads_nothing(fx, monkeypatch, name, missing):
    ctx = _ctx(fx, monkeypatch, traced=missing != "trace",
               program=missing != "program", scoped=missing != "scopes")
    needs = {"trace": DEVICE, "program": PROGRAM, "scopes": ("split_fold_pct",)}[missing]
    got = _read(name, ctx)
    assert (got is None) == (name in needs), got


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_run_jit)/while/body/closed_call/split_fold/jit(take_along_axis)/gather",
     "split_fold"),
    ("jit(_run_jit)/while/body/closed_call/gather/dynamic_slice", "gather"),
    ("jit(_run_jit)/while/body/closed_call/jit(gather_reduce_cores_pallas)/pallas_call", ""),
    ("jit(_run_jit)/while/body/closed_call/frontier/reshape;frontier/reshape", "frontier"),
    ("jit(_run_jit)/while/body/closed_call/coverage/cond", "coverage"),
    ("jit(take_along_axis)/gather", ""),
    ("", ""),
])
def test_scope_of_an_op_name(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


def _varint(x):
    out = bytearray()
    while True:
        out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
        x >>= 7
        if not out[-1] & 0x80:
            return bytes(out)


def _msg(*fields):
    """A protobuf message of ``(field, value)``: ints as varints, the rest
    length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_from_event_metadata(tmp_path):
    """The ``tf_op`` stat of each op's event metadata, in a hand-built
    ``XSpace``: a host plane is skipped, a stat of another kind is not read."""
    text = "%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    stat_meta = [(5, _msg((1, 300), (2, _msg((1, 300), (2, "tf_op"))))),
                 (5, _msg((1, 7), (2, _msg((1, 7), (2, "hlo_category")))))]
    op = _msg((1, 12), (2, text), (5, _msg((1, 7), (5, "loop fusion"))),
              (5, _msg((1, 300), (5, "jit(f)/while/body/split_fold/reduce_min:"))))
    device = _msg((1, 3), (2, "/device:TPU:0"), (4, _msg((1, 12), (2, op))), *stat_meta)
    host = _msg((1, 4), (2, "/host:CPU"), (4, _msg((1, 12), (2, op))), *stat_meta)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device)))
    assert program_trace.op_names(path) == {
        "/device:TPU:0": {text: "jit(f)/while/body/split_fold/reduce_min"}}
    assert program_trace.scope_of("jit(f)/while/body/split_fold/reduce_min") == "split_fold"
