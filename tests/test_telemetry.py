"""The program's recorder (``repro.telemetry``) and what the engine records
with it.

  * the recorder: span nesting with parent and run ids, the bounded buffer,
    ``records(t0, t1)``, and the compile listener (one event per retrace,
    none for a call that hits the jit cache).
  * the pull-tile counter on the timed path: ``EngineResult.pull_tiles_run``
    equals the per-iteration ``active_tiles`` of the host-stepped
    ``run_frontier_trace`` summed over its pull iterations (BFS, WCC, SSSP,
    with and without hub-split rows and push iterations), and the static
    schedule's iterations times real tiles (PageRank). Labels and iteration
    counts stay those of the host-stepped run and the XLA oracle.
  * the carry: ``_run_jit``'s while loop gains one int32 scalar on the
    dynamic schedule and nothing on the static one.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.graph as G
from repro import telemetry
from repro.core import engine
from repro.core.engine import EngineOptions, run, run_frontier_trace
from repro.core.partition import PartitionConfig, partition_2d
from repro.core.problems import bfs, pagerank, sssp, wcc
from repro.data.synthetic import path_grid_graph, skewed_graph


def _names(recs):
    return [r["name"] for r in recs]


def test_span_nesting_parent_and_run_ids():
    telemetry.clear()
    with telemetry.span("outer", tag=1) as outer:
        with telemetry.span("inner") as inner:
            telemetry.count("things", 3)
        with telemetry.span("sibling"):
            pass
    with telemetry.span("next"):
        pass
    recs = telemetry.records()
    assert _names(recs) == ["things", "inner", "sibling", "outer", "next"]
    by = {r["name"]: r for r in recs}
    assert outer["parent"] is None and outer["run"] == outer["id"] and outer["tag"] == 1
    assert inner["parent"] == outer["id"] and inner["run"] == outer["id"]
    assert by["sibling"]["parent"] == outer["id"]
    assert by["things"] == dict(by["things"], kind="count", value=3,
                                parent=inner["id"], run=outer["id"])
    assert by["next"]["run"] == by["next"]["id"] != outer["id"]
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= by["sibling"]["t0"] <= outer["t1"]


def test_span_records_when_the_block_raises():
    telemetry.clear()
    with pytest.raises(ValueError):
        with telemetry.span("failing"):
            raise ValueError
    with telemetry.span("after") as after:
        pass
    assert _names(telemetry.records()) == ["failing", "after"]
    assert after["parent"] is None  # the failed span left the stack


def test_records_between_times():
    telemetry.clear()
    with telemetry.span("a"):
        pass
    with telemetry.span("b") as b:
        telemetry.count("in_b", 1)
    with telemetry.span("c"):
        pass
    assert _names(telemetry.records(b["t0"], b["t1"])) == ["in_b", "b"]
    assert _names(telemetry.records(t1=b["t0"])) == ["a"]
    assert _names(telemetry.records(t0=b["t1"])) == ["c"]


def test_buffer_keeps_the_newest_records():
    telemetry.clear()
    n = telemetry.MAX_RECORDS
    for i in range(n + 10):
        telemetry.count("i", i)
    recs = telemetry.records()
    assert len(recs) == n
    assert recs[0]["value"] == 10 and recs[-1]["value"] == n + 9
    telemetry.clear()
    assert telemetry.records() == []


def test_compile_listener_counts_a_retrace_not_a_cached_call():
    @jax.jit
    def twice_plus_one(x):
        return 2 * x + 1

    x, y = jnp.arange(7.0), jnp.ones(7)
    telemetry.clear()
    twice_plus_one(x).block_until_ready()
    first = [r for r in telemetry.records()
             if r["name"] == "compile" and r["fun"] in ("twice_plus_one",
                                                        "jit(twice_plus_one)")]
    assert sorted(r["event"] for r in first) == [
        "backend_compile_duration", "jaxpr_to_mlir_module_duration",
        "jaxpr_trace_duration"]
    assert all(r["value"] >= 0 for r in first)
    telemetry.clear()
    twice_plus_one(y).block_until_ready()  # same shape: the jit cache
    assert [r for r in telemetry.records() if r["name"] == "compile"] == []


# --- the engine's records --------------------------------------------------


def _weighted(g, seed=0):
    w = np.random.default_rng(seed).uniform(0.5, 2.0, g.src.shape[0]).astype(np.float32)
    return dataclasses.replace(g, weights=w)


@pytest.fixture(scope="module")
def rmat():
    g = _weighted(G.symmetrize(G.rmat(9, 6, seed=4)))
    return g, partition_2d(g, PartitionConfig(p=2, l=2, lane=8, stride=100))


@pytest.fixture(scope="module")
def hub():
    g = _weighted(skewed_graph(256, kind="star", hub_in_degree=700,
                               avg_degree=2, seed=7))
    pg = partition_2d(g, PartitionConfig(p=2, l=2, lane=8, tile_vb=32, tile_eb=32))
    assert pg.split_rows > 0
    return g, pg


@pytest.fixture(scope="module")
def path():
    g = path_grid_graph(256, 1, shuffle=True, seed=11)
    return g, partition_2d(g, PartitionConfig(p=2, l=2, lane=8, tile_vb=32, tile_eb=32))


@pytest.mark.parametrize("graph", ["rmat", "hub", "path"])
@pytest.mark.parametrize("make", [lambda: bfs(3), wcc, lambda: sssp(3)],
                         ids=["bfs", "wcc", "sssp"])
@pytest.mark.parametrize("direction", ["auto", "pull", "push"])
def test_pull_tiles_run_matches_the_stepped_schedule(request, graph, make, direction):
    g, pg = request.getfixturevalue(graph)
    prob, opts = make(), EngineOptions(direction=direction)
    trace = run_frontier_trace(prob, g, pg, opts)
    telemetry.clear()
    res = run(prob, g, pg, opts)
    pulled = [a for a, d in zip(trace["active_tiles"], trace["direction"]) if d == "pull"]
    assert res.pull_tiles_run == sum(pulled)
    # the fold runs in every phase of every pull iteration, push ones skip it
    folds = [r["value"] for r in telemetry.records() if r["name"] == "engine.fold_rows"]
    assert folds == [len(pulled) * pg.split_rows]
    if direction == "push":
        assert res.pull_tiles_run == 0
    else:
        assert 0 < res.pull_tiles_run <= res.iterations * int(pg.tile_counts.sum())
    np.testing.assert_array_equal(res.labels["label"], trace["labels"]["label"])
    assert res.iterations == trace["iterations"] and res.converged
    oracle = run(prob, g, pg, EngineOptions(backend="xla"))
    np.testing.assert_array_equal(res.labels["label"], oracle.labels["label"])
    assert res.iterations == oracle.iterations and oracle.pull_tiles_run is None


@pytest.mark.parametrize("graph", ["rmat", "hub"])
def test_static_schedule_runs_every_real_tile(request, graph):
    g, pg = request.getfixturevalue(graph)
    res = run(pagerank(tol=1e-4), g, pg)
    assert res.pull_tiles_run == res.iterations * int(pg.tile_counts.sum()) > 0
    res = run(bfs(3), g, pg, EngineOptions(dynamic_tile_skip=False))
    assert res.pull_tiles_run == res.iterations * int(pg.tile_counts.sum())


def test_run_records_its_spans_and_counters(rmat):
    g, pg = rmat
    labels = engine.prepare_labels(bfs(3), g, pg)
    telemetry.clear()
    res = run(bfs(3), g, pg, labels=labels)
    recs = telemetry.records()
    by = {r["name"]: r for r in recs}
    assert set(by) >= {"engine.run", "engine.execute", "engine.readback",
                       "engine.pull_tiles_run", "engine.pull_tiles_real"}
    assert "engine.prepare_labels" not in by  # the caller prepared them
    root = by["engine.run"]
    assert all(r["run"] == root["id"] for r in recs if r["name"] != "compile")
    assert by["engine.execute"]["parent"] == root["id"]
    assert by["engine.pull_tiles_run"]["value"] == res.pull_tiles_run
    assert by["engine.pull_tiles_real"]["value"] == (
        res.iterations * int(pg.tile_counts.sum()))

    telemetry.clear()
    run(bfs(3), g, pg)  # labels prepared inside the run, under its id
    recs = telemetry.records()
    prep = [r for r in recs if r["name"] == "engine.prepare_labels"]
    root = [r for r in recs if r["name"] == "engine.run"]
    assert len(prep) == 1 and prep[0]["parent"] == root[0]["id"]
    kids = [r["name"] for r in recs if r.get("parent") == prep[0]["id"]]
    assert kids == ["init", "permute", "upload"]


def test_partition_arrays_upload_once_each(rmat):
    g, pg0 = rmat
    pg = dataclasses.replace(pg0)  # a fresh object: a fresh upload cache
    telemetry.clear()
    run(bfs(3), g, pg)
    run(bfs(5), g, pg)
    ups = [r for r in telemetry.records() if r["name"] == "engine.upload"]
    consts = {k: v for k, v in pg.channel_arrays(bfs(3)).items() if v is not None}
    assert len(ups) == len(consts)
    assert sum(r["bytes"] for r in ups) == sum(v.nbytes for v in consts.values())
    engine.evict_from_cache(pg)


def _while_carry(prob, g, pg, opts):
    labels = engine.prepare_labels(prob, g, pg)
    pgw = engine._wrap(pg)
    consts = engine._edge_constants(prob, pg, opts)
    jaxpr = jax.make_jaxpr(
        lambda lab, c: engine._run_jit(engine._wrap(prob), pgw, opts, lab, c)
    )(labels, consts)
    (call,) = jaxpr.jaxpr.eqns  # the jit of _run_jit
    inner = call.params["jaxpr"].jaxpr
    (loop,) = [e for e in inner.eqns if e.primitive.name == "while"]
    body = loop.params["body_jaxpr"].jaxpr
    return [v.aval for v in body.invars[loop.params["body_nconsts"]:]], labels


def test_run_jit_carries_one_tile_count(rmat):
    g, pg = rmat
    i32 = jax.core.ShapedArray((), jnp.int32)
    b1 = jax.core.ShapedArray((), jnp.bool_)
    # dynamic, pull only: labels, frontier words, iteration, changed, tiles
    carry, labels = _while_carry(bfs(3), g, pg, EngineOptions(direction="pull"))
    scalars = [a for a in carry if a.shape == ()]
    assert scalars == [i32, b1, i32]
    assert [a.shape for a in carry if a.shape != ()] == [
        labels["label"].shape, (pg.p, pg.l, pg.sub_size // 32)]
    # direction switch: the direction flag rides between changed and tiles,
    # and the pull iterations (the fold's count) follow the tiles
    carry, _ = _while_carry(bfs(3), g, pg, EngineOptions())
    assert [a for a in carry if a.shape == ()] == [i32, b1, b1, i32, i32]
    # static schedule: no counter in the carry
    carry, _ = _while_carry(pagerank(tol=1e-4), g, pg, EngineOptions())
    assert [a for a in carry if a.shape == ()] == [i32, b1]
