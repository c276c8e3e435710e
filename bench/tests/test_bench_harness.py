"""The harness end to end on the CPU at a small size: every cell of
``BENCHMARK.json`` runs and comes out correct, and a configuration, a traffic
mix and a per-layer metric are added as files alone."""
import json

import pytest
from bench_testkit import REPO, run, small_bench

CELLS = [c["name"] for c in json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]]


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return small_bench(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct(bench, cell, trace, capsys, monkeypatch):
    rc, out = run(bench, cell, capsys, monkeypatch, trace=trace)
    assert rc == 0 and out["correct"], out
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = bench.per_layer(cell) if trace else bench.end_to_end(cell)
    got = set(out["metrics"])
    if trace:  # device readers find no device in a CPU trace
        assert got <= {m["name"] for m in want}
        assert {"partition_build_s"} <= got
    else:
        assert got == {m["name"] for m in want}
    for m in want:
        if m["name"] in got:
            assert out["metrics"][m["name"]]["unit"] == m["unit"]


def test_bfs_window_runs_whole_rounds(bench, capsys, monkeypatch):
    """Every seed times the same search keys, each as often: the window
    ends on a whole round, however short its time."""
    keys = bench.config("graph500-s20")["search_keys"]
    for seconds in (0.01, 0.5):
        rc, out = run(bench, "g500-s20-bfs", capsys, monkeypatch, seconds=seconds)
        assert rc == 0 and out["correct"], out
        assert out["attempted"] >= keys and out["attempted"] % keys == 0


def test_every_named_file_exists():
    from bench import harness

    b = harness.Bench()
    for cell in b.spec["workloads"]:
        assert b.config_file(cell["config"]).exists()
        b.driver(b.traffic(cell["traffic"])["driver"])
    for m in b.spec["per_layer"]:
        assert callable(b.reader(m["name"]))
    for c in b.spec["configs"]:
        assert (REPO / c["file"]).exists()
        assert json.loads((REPO / c["file"]).read_text())["name"] == c["name"]


def test_cell_added_by_files_alone(tmp_path, capsys, monkeypatch):
    """A new configuration, traffic mix and per-layer metric, each only a new
    file (plus its entries in BENCHMARK.json), run without an edit to any
    file the benchmark had."""
    bench = small_bench(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    d = tmp_path / "bench"
    cfg = json.loads((d / "configs" / "graph500-s20.json").read_text())
    cfg.update(name="graph500-tiny-b", scale=8, graph_seed=7, search_keys=4)
    cfg["layout"]["tile_vb"] = 256
    (d / "configs" / "graph500-tiny-b.json").write_text(json.dumps(cfg))
    (d / "traffic" / "bfs-again.json").write_text(json.dumps(
        {"driver": "engine_runs", "problem": "bfs", "limits": {"wrong_levels": 0}}))
    (d / "metrics" / "runs_in_window.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window['runs']))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="graph500-tiny-b",
                                file="bench/configs/graph500-tiny-b.json"))
    spec["workloads"].append({"name": "tiny-bfs", "config": "graph500-tiny-b",
                              "traffic": "bfs-again", "chips": 1, "why": "test"})
    spec["end_to_end"][1]["workloads"].append("tiny-bfs")
    spec["per_layer"].append({"name": "runs_in_window", "unit": "runs", "better": "higher",
                              "source": "program_counter", "layer": "engine loop",
                              "moves": "mteps", "workloads": ["tiny-bfs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    from bench import harness

    bench = harness.Bench(tmp_path, d)
    rc, out = run(bench, "tiny-bfs", capsys, monkeypatch, trace=1)
    assert rc == 0 and out["correct"]
    assert out["metrics"]["runs_in_window"]["value"] == out["attempted"] >= 1
    rc, out = run(bench, "tiny-bfs", capsys, monkeypatch, trace=0)
    assert rc == 0 and set(out["metrics"]) == {"setup_s", "mteps"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_refuses_to_run_off_a_tpu(tmp_path):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s20-bfs", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_metric_without_cells_follows_its_end_to_end_metric(tmp_path):
    """A per-layer metric that lists no cells is read in every cell that
    reports the end-to-end metric it moves, and in no other."""
    from bench import harness

    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "other_rate", "unit": "runs/s", "better": "higher",
                               "bound": 0.05, "source": "host_clock",
                               "workloads": ["elsewhere"]})
    spec["per_layer"] += [
        {"name": "runs_in_window", "unit": "runs", "better": "higher",
         "source": "program_counter", "layer": "engine loop", "moves": "mteps"},
        {"name": "other_layer", "unit": "runs", "better": "higher",
         "source": "program_counter", "layer": "engine loop", "moves": "other_rate"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    b = harness.Bench(tmp_path, REPO / "bench")
    for cell in CELLS:
        names = {m["name"] for m in b.per_layer(cell)}
        assert "runs_in_window" in names and "other_layer" not in names
