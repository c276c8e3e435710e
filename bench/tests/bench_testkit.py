"""Helpers of the benchmark's CPU tests: a copy of ``bench/`` and
``BENCHMARK.json`` in a temporary directory, shrunk to graphs a test can
build, and one harness run in it."""
from __future__ import annotations

import json
import shutil
import sys
import time
import types
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for _p in (REPO / "src", REPO):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import harness  # noqa: E402

SMALL_SCALE = 9


def small_bench(tmp: Path, scale: int = SMALL_SCALE) -> harness.Bench:
    """``bench/`` and ``BENCHMARK.json`` copied under ``tmp``, every graph
    cut to ``2**scale`` vertices in one phase."""
    shutil.copytree(REPO / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for f in (tmp / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg["scale"] = scale
        cfg["layout"].update(l=1, tile_vb=min(4096, 1 << scale))
        f.write_text(json.dumps(cfg))
    return harness.Bench(tmp, tmp / "bench")


def run(bench: harness.Bench, workload: str, capsys, monkeypatch, *,
        seconds: float = 0.5, trace: int = 0, seed: int = 2**31 + 5) -> tuple[int, dict | None]:
    """One harness run on the CPU; returns the exit code and the result."""
    monkeypatch.setattr(harness, "use_checkout_compile_cache", lambda root: None)
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
    capsys.readouterr()
    rc = harness.run_cell(args, time.perf_counter(), bench=bench, require_tpu=False,
                          cache_dir=bench.root / "cache" / "graphs")
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
