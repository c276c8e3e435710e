"""Trace reduction against a recorded chip trace: a traced 8 s window of
``g500-s20-bfs`` on one TPU v5e (3 BFS runs, recorded before the generator
relabelled vertices), device ops and the benchmark's host spans as
``tracing.extract`` keeps them."""
import gzip
import json

import numpy as np
import pytest
from bench_testkit import REPO

from bench import tracing

DATA = REPO / "bench" / "tests" / "data" / "trace_g500-s20-bfs.json.gz"


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return json.load(f)


def test_busy_and_idle_against_a_timeline(events):
    s = tracing.reduce_events(events)
    (w,) = [h for h in events["host"] if h[0] == "bench.window"]
    w0, w1 = w[1], w[1] + w[2]
    # independent: mark every microsecond some op covers
    us = np.zeros(int((w1 - w0) // 1000) + 2, dtype=bool)
    (evs,) = events["devices"].values()
    for _, st, d in evs:
        a, b = max(st, w0), min(st + d, w1)
        if b > a:
            us[int((a - w0) // 1000): int(np.ceil((b - w0) / 1000))] = True
    assert s["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert s["busy_s"] == pytest.approx(us.sum() / 1e6, rel=1e-3)
    assert 0 < s["busy_s"] <= s["window_s"]
    # the recorded window: 3 runs, the device idle 0.23% of it
    assert s["busy_s"] == pytest.approx(10.374161738)
    assert s["window_s"] == pytest.approx(10.39802398)


def test_kernel_time(events):
    s = tracing.reduce_events(events)
    (evs,) = events["devices"].values()
    want = sum(d for n, _, d in evs if tracing.PULL_KERNEL.fullmatch(n)) / 1e9
    assert tracing.op_seconds(s, tracing.PULL_KERNEL) == pytest.approx(want)
    assert want == pytest.approx(5.916284967)
    # own times add up to the busy time: nothing is counted twice
    assert sum(s["op_s"].values()) == pytest.approx(s["busy_s"], rel=1e-6)


def test_breakdown_names_what_the_host_did(events):
    b = tracing.breakdown(tracing.reduce_events(events))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "gather_reduce_cores_pallas.9"
    assert {n for n, _ in b["idle_gaps"]} <= {"engine_run", "prepare_labels", "run",
                                               "outside spans"}
