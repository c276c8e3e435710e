"""The check that decides ``correct`` fails what it must: each cell's
control (the reference with a guarantee broken, or in bfloat16) and each
fault the cell can have (a run that returns its state unchanged, an answer
altered where it is produced), planted under a harness run at a small
size. The cells run on one chip, so there is no exchange between chips to
leave out, and a whole-graph run has no batch to halve."""
import json

import numpy as np
import pytest
from bench_testkit import REPO, run, small_bench

CELLS = [c["name"] for c in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return small_bench(tmp_path_factory.mktemp("bench"), scale=10)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(bench, cell):
    from bench.control import control_checks

    checks = control_checks(bench, cell, seed=2**31 + 11, runs=4,
                            cache_dir=bench.root / "cache" / "graphs")
    assert not all(v <= lim for _, v, lim in checks), checks


def _state_unchanged(monkeypatch, module):
    from repro.core import engine

    def run(problem, g, pg, opts=engine.EngineOptions(), labels=None):
        labels = labels if labels is not None else engine.prepare_labels(problem, g, pg)
        return engine.EngineResult(labels=engine.unpad_labels(labels, pg),
                                   iterations=1, converged=True)

    monkeypatch.setattr(module, "run", run)


def _answer_altered(monkeypatch, module):
    from repro.core import engine

    real = engine.run

    def run(*a, **kw):
        res = real(*a, **kw)
        res.labels = {k: np.array(v) for k, v in res.labels.items()}
        for lab in res.labels.values():
            if lab.ndim >= 1:
                flat = lab.reshape(-1)
                i = int(np.argmin(flat))  # a root: level 0, or the smallest rank
                if flat[i] != np.iinfo(np.uint32).max:  # a warm-up run reaches nothing
                    flat[i] = flat[i] + 1
        return res

    monkeypatch.setattr(module, "run", run)


FAULTS = {"state_unchanged": _state_unchanged, "answer_altered": _answer_altered}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(bench, cell, fault, capsys, monkeypatch):
    from repro.core import engine

    rc, out = run(bench, cell, capsys, monkeypatch, seconds=0.3)
    assert rc == 0 and out["correct"], out
    FAULTS[fault](monkeypatch, engine)
    rc, out = run(bench, cell, capsys, monkeypatch, seconds=0.3)
    assert rc == 0 and not out["correct"], out
