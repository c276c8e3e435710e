"""The ``fold_rows`` reader: the program's ``engine.fold_rows`` counters in
the window over the window's phase passes, and nothing where the program
records no such counter."""
import types

from bench_testkit import REPO  # noqa: F401  (puts the repo on sys.path)

from bench import harness, program_trace, tracing


class _Telemetry:
    def __init__(self, recs):
        self.recs = recs

    def records(self, t0=None, t1=None):
        return [r for r in self.recs
                if (t0 is None or r["t0"] >= t0) and (t1 is None or r["t1"] <= t1)]


def _ctx(monkeypatch, recs):
    monkeypatch.setattr(program_trace, "telemetry", lambda: _Telemetry(recs))
    spans = tracing.Spans()
    spans.records.append({"name": "window", "t0": 10.0, "t1": 20.0})
    return types.SimpleNamespace(
        spans=spans, graph=types.SimpleNamespace(pg=types.SimpleNamespace(l=16)),
        window={"runs": [{"iterations": 5}, {"iterations": 4}]},
    )


def _count(t, value):
    return {"name": "engine.fold_rows", "t0": t, "t1": t, "value": value}


def test_fold_rows_per_phase_pass(monkeypatch):
    recs = [_count(5.0, 999), _count(11.0, 5 * 130), _count(15.0, 4 * 130),
            {"name": "engine.pull_tiles_run", "t0": 12.0, "t1": 12.0, "value": 7}]
    read = harness.Bench().reader("fold_rows")
    # 130 moved rows over 16 phases, whatever the iterations; set-up's left out
    assert read(_ctx(monkeypatch, recs)) == 130 * 9 / (9 * 16)
    assert read(_ctx(monkeypatch, recs[:1] + recs[3:])) is None  # no counter
