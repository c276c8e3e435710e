"""Delta ingest (ISSUE 9): ``apply_edge_deltas`` re-tiles ONLY the dirty
(core, phase) buckets, yet the result is BIT-IDENTICAL to a from-scratch
``partition_2d`` of the grown edge list (docs/tile_layout.md §10).

The equivalence argument under test: the cold path sorts the whole edge list
with one stable argsort on (bucket, lidx); a dirty bucket's merged slice —
old dst-sorted slice ++ delta edges in insertion order, stably re-sorted by
lidx — reproduces exactly that tie order, and per-bucket layout decisions
(row placement, 'auto' capacity, E_pad rounding) are local, so clean
buckets never move. Composition across flushes means N incremental flushes
== one cold repartition of the final graph.
"""
import dataclasses

import numpy as np
import pytest

import repro.core.graph as G
from repro.core.engine import EngineOptions, run
from repro.core.partition import (
    PartitionConfig,
    apply_edge_deltas,
    bucket_coords,
    partition_2d,
)
from repro.core.problems import bfs, sssp, wcc
from repro.data.synthetic import edge_insertion_stream, skewed_graph
from repro.serve import DeltaBuffer


def _weighted(g, seed=0):
    w = (np.random.default_rng(seed).random(g.num_edges) + 0.1).astype(np.float32)
    return G.COOGraph(src=g.src, dst=g.dst, num_vertices=g.num_vertices, weights=w)


def _grown(g, src, dst, w=None):
    return G.COOGraph(
        src=np.concatenate([g.src, np.asarray(src, g.src.dtype)]),
        dst=np.concatenate([g.dst, np.asarray(dst, g.dst.dtype)]),
        num_vertices=g.num_vertices,
        weights=(
            np.concatenate([g.weights, np.asarray(w, np.float32)])
            if g.weights is not None else None
        ),
    )


def assert_partitions_identical(pa, pb):
    """Every field of the two PartitionedGraphs, bit for bit."""
    for f in dataclasses.fields(pa):
        a, b = getattr(pa, f.name), getattr(pb, f.name)
        if f.name == "config":
            assert a == b, "config"
            continue
        if a is None or b is None:
            assert a is None and b is None, f.name
            continue
        if isinstance(a, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, (
                f.name, a.dtype, b.dtype, a.shape, b.shape
            )
            assert np.array_equal(a, b), f.name
        else:
            assert a == b, (f.name, a, b)


# ---------------------------------------------------------------------------
# bit-identity vs a from-scratch repartition


def test_single_flush_bit_identity_weighted():
    g = _weighted(G.symmetrize(G.rmat(7, 4, seed=1)), seed=2)
    cfg = PartitionConfig(p=4, l=2)
    pg = partition_2d(g, cfg)
    rng = np.random.default_rng(3)
    src = rng.integers(0, g.num_vertices, 40)
    dst = rng.integers(0, g.num_vertices, 40)
    w = rng.random(40).astype(np.float32)
    new_pg, report = apply_edge_deltas(pg, src, dst, w)
    assert report.edges_added == 40
    assert 0 < report.buckets_retiled <= report.total_buckets
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst, w), cfg))


def test_multi_flush_composes():
    """Two sequential flushes == one cold repartition of the final graph."""
    g = _weighted(G.symmetrize(G.rmat(7, 4, seed=2)), seed=4)
    cfg = PartitionConfig(p=2, l=2)
    pg = partition_2d(g, cfg)
    cur_g, cur_pg = g, pg
    for batch_seed in (5, 6):
        rng = np.random.default_rng(batch_seed)
        src = rng.integers(0, g.num_vertices, 24)
        dst = rng.integers(0, g.num_vertices, 24)
        w = rng.random(24).astype(np.float32)
        cur_pg, _ = apply_edge_deltas(cur_pg, src, dst, w)
        cur_g = _grown(cur_g, src, dst, w)
    assert_partitions_identical(cur_pg, partition_2d(cur_g, cfg))


def test_stride_permutation_flush():
    """The stride perm is applied to delta endpoints exactly as partition_2d
    applies it to the base edges."""
    g = G.symmetrize(G.rmat(7, 4, seed=3))
    cfg = PartitionConfig(p=2, l=2, stride=10)
    pg = partition_2d(g, cfg)
    assert pg.perm is not None
    rng = np.random.default_rng(7)
    src = rng.integers(0, g.num_vertices, 32)
    dst = rng.integers(0, g.num_vertices, 32)
    new_pg, _ = apply_edge_deltas(pg, src, dst)
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst), cfg))


def test_hub_split_bucket_flush():
    """Deltas landing on a hub bucket that already moved rows re-run the
    placement with the recomputed 'auto' capacity — still bit-identical."""
    g = skewed_graph(256, kind="star", hub_in_degree=700, avg_degree=2, seed=7)
    cfg = PartitionConfig(p=2, l=2, lane=8, tile_vb=32, tile_eb=32)
    pg = partition_2d(g, cfg)
    assert pg.split_rows > 0, "precondition: the hub's block must overflow"
    hub = int(np.argmax(np.bincount(g.dst, minlength=g.num_vertices)))
    rng = np.random.default_rng(8)
    src = rng.integers(0, g.num_vertices, 64)
    dst = np.full(64, hub, dtype=np.int64)  # pile onto the hub
    new_pg, report = apply_edge_deltas(pg, src, dst)
    assert report.buckets_retiled < report.total_buckets
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst), cfg))


def test_pos_to_split_mode_transition():
    """A delta that pushes one row block over its capacity moves rows for
    the first time: the partition gains fold arrays (None before), clean
    buckets keep theirs empty, and the result still matches cold."""
    g = G.symmetrize(G.erdos_renyi(128, 300, seed=4))
    cfg = PartitionConfig(p=2, l=2, lane=8, tile_vb=32, tile_eb=32)
    pg = partition_2d(g, cfg)
    assert pg.tile_fold_rows is None, "precondition: nothing moved before the delta"
    rng = np.random.default_rng(9)
    src = rng.integers(0, g.num_vertices, 600)
    dst = np.zeros(600, dtype=np.int64)  # one monster row
    new_pg, report = apply_edge_deltas(pg, src, dst)
    assert new_pg.tile_fold_rows is not None and new_pg.split_rows > 0
    assert report.buckets_retiled < report.total_buckets
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst), cfg))


def test_edge_pad_growth():
    """A delta overflowing a bucket's E_pad grows the flat arrays by the cold
    rounding rule."""
    g = G.symmetrize(G.rmat(6, 3, seed=5))
    cfg = PartitionConfig(p=2, l=2, edge_pad=8)
    pg = partition_2d(g, cfg)
    rng = np.random.default_rng(10)
    n = 2 * pg.edge_pad  # guaranteed past any per-bucket slack
    src = rng.integers(0, g.num_vertices, n)
    dst = rng.integers(0, g.num_vertices, n)
    new_pg, report = apply_edge_deltas(pg, src, dst)
    assert report.grew_edge_pad and new_pg.edge_pad > pg.edge_pad
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst), cfg))


def test_label_equality_after_streamed_insertions():
    """The acceptance criterion: BFS/WCC/SSSP labels on the delta-retiled
    partition are bit-identical to a cold repartition — on a hub graph where
    the insertions hit the split bucket."""
    g0 = skewed_graph(192, kind="star", hub_in_degree=500, avg_degree=2, seed=11)
    g = _weighted(g0, seed=12)
    cfg = PartitionConfig(p=2, l=2, lane=8, tile_vb=32, tile_eb=32)
    pg = partition_2d(g, cfg)
    assert pg.split_rows > 0
    cur_g, cur_pg = g, pg
    for batch in edge_insertion_stream(
        48, g.num_vertices, num_batches=2, hub_bias=0.7, weighted=True, seed=13
    ):
        src, dst, w = batch
        cur_pg, _ = apply_edge_deltas(cur_pg, src, dst, w)
        cur_g = _grown(cur_g, src, dst, w)
    cold_pg = partition_2d(cur_g, cfg)
    assert_partitions_identical(cur_pg, cold_pg)
    for prob in (bfs(0), wcc(), sssp(0)):
        ra = run(prob, cur_g, cur_pg, EngineOptions())
        rb = run(prob, cur_g, cold_pg, EngineOptions())
        assert ra.iterations == rb.iterations, prob.name
        for k in ra.labels:
            assert np.array_equal(ra.labels[k], rb.labels[k]), (prob.name, k)


# ---------------------------------------------------------------------------
# O(B): a flush touching B buckets rebuilds O(B) packed bytes, not O(p*l)


def test_flush_is_o_dirty_buckets():
    g = G.symmetrize(G.rmat(8, 6, seed=6))
    cfg = PartitionConfig(p=4, l=4)
    pg = partition_2d(g, cfg)
    assert pg.p * pg.l == 16
    vpc, sub = pg.vertices_per_core, pg.sub_size
    # confine the delta to bucket (core 0, phase 0): dst < vpc, src < sub
    rng = np.random.default_rng(14)
    src = rng.integers(0, sub, 20)
    dst = rng.integers(0, vpc, 20)
    core, phase, _, _ = bucket_coords(pg, src, dst)
    assert set(zip(core.tolist(), phase.tolist())) == {(0, 0)}
    new_pg, report = apply_edge_deltas(pg, src, dst)
    assert report.buckets_retiled == 1 and report.total_buckets == 16
    # bytes-level witness: one bucket's slice of the stacked stream
    assert report.tile_bytes_repacked < report.tile_bytes_total
    assert report.repacked_fraction == pytest.approx(1 / 16, rel=0.05)
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst), cfg))


def test_empty_delta_is_identity():
    g = G.symmetrize(G.rmat(6, 3, seed=7))
    pg = partition_2d(g, PartitionConfig(p=2, l=2))
    new_pg, report = apply_edge_deltas(pg, np.zeros(0, np.int64), np.zeros(0, np.int64))
    assert new_pg is pg
    assert report.edges_added == 0 and report.buckets_retiled == 0


# ---------------------------------------------------------------------------
# validation + DeltaBuffer


def test_delta_validation():
    g = _weighted(G.symmetrize(G.rmat(6, 3, seed=8)), seed=15)
    cfg = PartitionConfig(p=2, l=2)
    pg = partition_2d(g, cfg)
    with pytest.raises(ValueError):  # out-of-range vertex id
        apply_edge_deltas(pg, [0], [g.num_vertices], [1.0])
    with pytest.raises(ValueError):  # weighted partition, unweighted delta
        apply_edge_deltas(pg, [0], [1])
    gu = G.symmetrize(G.rmat(6, 3, seed=8))
    pgu = partition_2d(gu, cfg)
    with pytest.raises(ValueError):  # unweighted partition, weighted delta
        apply_edge_deltas(pgu, [0], [1], [1.0])
    bare = dataclasses.replace(pgu, config=None)
    with pytest.raises(ValueError):  # no partition_2d provenance
        apply_edge_deltas(bare, [0], [1])
    with pytest.raises(ValueError):
        DeltaBuffer(bare)


def test_delta_buffer_staging():
    g = G.symmetrize(G.rmat(6, 3, seed=9))
    pg = partition_2d(g, PartitionConfig(p=2, l=2))
    buf = DeltaBuffer(pg, auto_flush_edges=8)
    assert buf.pending_edges == 0 and not buf.should_flush()
    assert buf.stage([1, 2], [3, 4]) == 2
    core, phase, _, _ = bucket_coords(pg, np.array([1, 2]), np.array([3, 4]))
    assert buf.dirty_buckets == frozenset(zip(core.tolist(), phase.tolist()))
    assert buf.stage([5] * 6, [6] * 6) == 6
    assert buf.pending_edges == 8 and buf.should_flush()
    src, dst, w = buf.pending()  # read-only: does NOT clear
    assert src.tolist() == [1, 2, 5, 5, 5, 5, 5, 5] and w is None
    assert buf.pending_edges == 8
    new_pg, report = buf.flush(pg)
    assert report.edges_added == 8
    assert buf.pending_edges == 0 and buf.dirty_buckets == frozenset()
    assert_partitions_identical(new_pg, partition_2d(_grown(g, src, dst), pg.config))
    with pytest.raises(ValueError):  # bad edges fail at stage time
        buf.stage([0], [g.num_vertices])


def test_in_neighbors_matches_coo():
    g = G.symmetrize(G.rmat(6, 4, seed=10))
    for cfg in (PartitionConfig(p=2, l=2), PartitionConfig(p=2, l=2, stride=10)):
        pg = partition_2d(g, cfg)
        for v in (0, 1, 17, g.num_vertices - 1):
            got = np.sort(pg.in_neighbors(v))
            want = np.sort(g.src[g.dst == v])
            assert np.array_equal(got, want.astype(got.dtype)), (cfg.stride, v)
