"""Out-of-core streaming partition build — ISSUE 10.

The streaming contract (docs/tile_layout.md §11): ``partition_2d_streaming``
over any chunking of the same edge sequence is BIT-IDENTICAL to
``partition_2d`` over the materialized array — every packed/flat field, with
and without moved rows (the ``fold_rows``/``fold_pos`` arrays), both src-bit
regimes, weighted or not, memmap-backed or RAM. Plus: the
two-pass chunk protocol (re-iterable required, one-shot generators rejected,
deterministic replay verified), the seeded graph500-style RMAT stream
(``repro.data.rmat``), the ``choose_src_bits`` 16→32 boundary at exactly
``p * sub_size == 2**16``, delta-flush compatibility with memmap-backed
partitions, and ``memory_report`` accounting.
"""
import numpy as np
import pytest

import repro.core.graph as G
from repro.core.engine import EngineOptions, run
from repro.core.partition import (
    PartitionConfig,
    coo_edge_chunks,
    partition_2d,
    partition_2d_streaming,
)
from repro.core.problems import bfs
from repro.data.rmat import RMATStream, materialize, rmat_chunks
from repro.data.synthetic import skewed_graph
from repro.kernels.csr_gather_reduce.ops import (
    DSTB16_LIMIT,
    SRC16_LIMIT,
    choose_src_bits,
)

# every array field whose bit-identity defines streaming == in-memory
IDENTITY_FIELDS = (
    "src_gidx", "dst_lidx", "valid", "weights", "bucket_sizes",
    "tile_word", "tile_word_hi", "tile_counts", "tile_weights",
    "tile_coverage", "tile_fold_rows", "tile_fold_pos",
    "push_word", "push_word_hi", "push_counts", "push_weights",
    "push_coverage",
)


def assert_identical(a, b):
    for name in IDENTITY_FIELDS:
        va, vb = getattr(a, name), getattr(b, name)
        assert (va is None) == (vb is None), name
        if va is not None:
            assert np.array_equal(np.asarray(va), np.asarray(vb)), name
    for name in ("p", "l", "sub_size", "num_vertices", "num_edges",
                 "src_bits", "split_rows", "push_block"):
        assert getattr(a, name) == getattr(b, name), name


def _hub_graph():
    """Two dominant hubs on a small vertex set: triggers hub-row splitting
    under a low threshold while staying sub-second to partition."""
    return skewed_graph(96, kind="star", hub_in_degree=600, num_hubs=2, seed=5)


def _sparse_graph(num_vertices, num_edges, seed=0, weighted=False):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, num_vertices, num_edges).astype(np.uint32)
    dst = rng.integers(0, num_vertices, num_edges).astype(np.uint32)
    w = rng.random(num_edges).astype(np.float32) if weighted else None
    return G.COOGraph(src=src, dst=dst, num_vertices=num_vertices, weights=w)


class TestChooseSrcBits:
    def test_src_boundary_exact(self):
        # 16-bit holds up to and INCLUDING 2**16 gathered offsets (the field
        # stores offsets 0..2**16-1; gathered_size is the exclusive bound)
        assert choose_src_bits(SRC16_LIMIT, 128) == 16
        assert choose_src_bits(SRC16_LIMIT + 1, 128) == 32

    def test_vb_boundary_exact(self):
        assert choose_src_bits(1024, DSTB16_LIMIT) == 16
        assert choose_src_bits(1024, DSTB16_LIMIT + 1) == 32

    def test_end_to_end_boundary(self):
        # p * sub_size == V / l. V = 2**17, l = 2 → gathered EXACTLY 2**16:
        # the last 16-bit layout. Doubling V crosses into the 32-bit regime
        # (hi words appear). Streaming must agree in both regimes.
        cfg = PartitionConfig(p=2, l=2, tile_vb=1024, build_push=False)
        for v_log2, bits in ((17, 16), (18, 32)):
            g = _sparse_graph(1 << v_log2, 400, seed=3)
            pg = partition_2d(g, cfg)
            assert pg.p * pg.sub_size == (1 << v_log2) // 2
            assert pg.src_bits == bits
            assert (pg.tile_word_hi is not None) == (bits == 32)
            ps = partition_2d_streaming(
                coo_edge_chunks(g, 64), g.num_vertices, cfg
            )
            assert_identical(ps, pg)


class TestStreamingIdentity:
    def test_split_map_mode(self):
        # forced low capacity → rows move to appended blocks → fold arrays
        g = _hub_graph()
        cfg = PartitionConfig(p=2, l=2, tile_vb=16, tile_eb=16,
                              split_threshold=16)
        pg = partition_2d(g, cfg)
        assert pg.tile_fold_rows is not None and pg.split_rows > 0
        ps = partition_2d_streaming(coo_edge_chunks(g, 97), g.num_vertices, cfg)
        assert_identical(ps, pg)

    def test_row_pos_mode(self):
        # no capacity → identity placement, no fold arrays
        g = _hub_graph()
        cfg = PartitionConfig(p=2, l=2, tile_vb=16, tile_eb=16,
                              split_threshold=None)
        pg = partition_2d(g, cfg)
        assert pg.tile_fold_rows is None and pg.split_rows == 0
        ps = partition_2d_streaming(coo_edge_chunks(g, 97), g.num_vertices, cfg)
        assert_identical(ps, pg)

    @pytest.mark.parametrize("split_threshold", [16, None])
    def test_engine_labels_agree(self, split_threshold):
        g = _hub_graph()
        cfg = PartitionConfig(p=2, l=2, tile_vb=16, tile_eb=16,
                              split_threshold=split_threshold)
        pg = partition_2d(g, cfg)
        ps = partition_2d_streaming(coo_edge_chunks(g, 97), g.num_vertices, cfg)
        prob = bfs(3)
        opts = EngineOptions(backend="xla")
        ra = run(prob, g, pg, opts)
        rb = run(prob, g, ps, opts)
        assert ra.iterations == rb.iterations
        assert np.array_equal(
            np.asarray(ra.labels["label"]), np.asarray(rb.labels["label"])
        )

    def test_chunk_size_invariance(self):
        g = _sparse_graph(256, 900, seed=7, weighted=True)
        cfg = PartitionConfig(p=2, l=2, tile_vb=32)
        ref = partition_2d_streaming(coo_edge_chunks(g, 1 << 20),
                                     g.num_vertices, cfg)
        for chunk in (1, 7, 113):
            ps = partition_2d_streaming(coo_edge_chunks(g, chunk),
                                        g.num_vertices, cfg)
            assert_identical(ps, ref)

    def test_stride_permutation(self):
        g = _sparse_graph(300, 700, seed=9)
        cfg = PartitionConfig(p=2, l=2, stride=10)
        ps = partition_2d_streaming(coo_edge_chunks(g, 41), g.num_vertices, cfg)
        assert_identical(ps, partition_2d(g, cfg))


class TestChunkProtocol:
    def test_one_shot_generator_rejected(self):
        g = _sparse_graph(64, 100)
        gen = ((g.src[i:i + 10], g.dst[i:i + 10]) for i in range(0, 100, 10))
        with pytest.raises(TypeError, match="replay"):
            partition_2d_streaming(gen, 64, PartitionConfig(p=2, l=2))

    def test_list_of_chunks_accepted(self):
        g = _sparse_graph(64, 100, seed=2)
        chunks = [(g.src[i:i + 33], g.dst[i:i + 33]) for i in range(0, 100, 33)]
        cfg = PartitionConfig(p=2, l=2)
        assert_identical(
            partition_2d_streaming(chunks, 64, cfg), partition_2d(g, cfg)
        )

    def test_empty_graph_one_empty_chunk(self):
        g = G.COOGraph(src=np.zeros(0, np.uint32), dst=np.zeros(0, np.uint32),
                       num_vertices=64)
        cfg = PartitionConfig(p=2, l=2)
        ps = partition_2d_streaming(coo_edge_chunks(g), 64, cfg)
        assert ps.num_edges == 0
        assert_identical(ps, partition_2d(g, cfg))

    def test_mixed_weighted_chunks_rejected(self):
        s = np.arange(8, dtype=np.int64)
        w = np.ones(8, np.float32)
        chunks = [(s, s, w), (s, s)]  # second chunk drops the weights
        with pytest.raises(ValueError, match="weight"):
            partition_2d_streaming(chunks, 64, PartitionConfig(p=2, l=2))

    def test_out_of_range_vertex_rejected(self):
        s = np.array([0, 70], dtype=np.int64)
        with pytest.raises(ValueError):
            partition_2d_streaming([(s, s)], 64, PartitionConfig(p=2, l=2))


class TestRMATStream:
    def test_deterministic_and_replayable(self):
        st = rmat_chunks(8, 8, seed=11, chunk_edges=500)
        a = [(s.copy(), d.copy()) for s, d in st()]
        b = list(st())
        assert len(a) == st.num_chunks
        for (sa, da), (sb, db) in zip(a, b):
            assert np.array_equal(sa, sb) and np.array_equal(da, db)
        other = rmat_chunks(8, 8, seed=12, chunk_edges=500)
        assert not all(
            np.array_equal(x[0], y[0]) for x, y in zip(a, other())
        )

    def test_counts_and_bounds(self):
        st = RMATStream(scale=7, edge_factor=4, seed=3, symmetric=True)
        g = materialize(st)
        assert st.num_vertices == 1 << 7
        assert g.num_edges == st.num_edges == 2 * 4 * (1 << 7)
        assert int(g.src.max()) < st.num_vertices
        assert int(g.dst.max()) < st.num_vertices

    def test_stream_is_valid_chunks_argument(self):
        st = rmat_chunks(8, 6, seed=5, chunk_edges=300, weighted=True)
        cfg = PartitionConfig(p=2, l=2, tile_vb=32)
        ps = partition_2d_streaming(st, st.num_vertices, cfg)
        assert_identical(ps, partition_2d(materialize(st), cfg))


class TestMemmapAndDelta:
    def test_memmap_identical_and_runs(self, tmp_path):
        st = rmat_chunks(8, 8, seed=1, chunk_edges=400)
        cfg = PartitionConfig(p=2, l=2, tile_vb=32)
        g = materialize(st)
        pg = partition_2d(g, cfg)
        pm = partition_2d_streaming(st, st.num_vertices, cfg,
                                    memmap_dir=str(tmp_path))
        assert isinstance(pm.tile_word, np.memmap)
        assert_identical(pm, pg)
        prob, opts = bfs(3), EngineOptions(backend="xla")
        ra, rb = run(prob, g, pg, opts), run(prob, g, pm, opts)
        assert np.array_equal(
            np.asarray(ra.labels["label"]), np.asarray(rb.labels["label"])
        )

    def test_delta_flush_against_memmap_partition(self, tmp_path):
        # the serving contract (serve/delta.py): a flush against a
        # memmap-backed partition must equal a cold rebuild of the grown
        # edge list — apply_edge_deltas reads bucket slices (memmap is an
        # ndarray subclass) and emits plain RAM arrays
        from repro.serve.delta import DeltaBuffer

        st = rmat_chunks(8, 8, seed=4, chunk_edges=300)
        cfg = PartitionConfig(p=2, l=2, tile_vb=32)
        pm = partition_2d_streaming(st, st.num_vertices, cfg,
                                    memmap_dir=str(tmp_path))
        buf = DeltaBuffer(pm)
        new_src = np.array([1, 33, 200, 7], dtype=np.int64)
        new_dst = np.array([250, 2, 9, 7], dtype=np.int64)
        buf.stage(new_src, new_dst)
        new_pg, report = buf.flush(pm)
        assert report.edges_added == 4
        # the flushed partition must not alias the on-disk build artifacts
        # (serve/delta.py promises they are deletable after the flush)
        assert not any(
            isinstance(getattr(new_pg, f), np.memmap)
            for f in IDENTITY_FIELDS
            if getattr(new_pg, f) is not None
        )

        g = materialize(st)
        grown = G.COOGraph(
            src=np.concatenate([np.asarray(g.src, np.int64), new_src]).astype(np.uint32),
            dst=np.concatenate([np.asarray(g.dst, np.int64), new_dst]).astype(np.uint32),
            num_vertices=g.num_vertices,
        )
        assert_identical(new_pg, partition_2d(grown, cfg))


class TestMemoryReport:
    def test_totals_and_fields(self):
        g = _sparse_graph(256, 800, seed=6)
        pg = partition_2d(g, PartitionConfig(p=2, l=2, tile_vb=32))
        rep = pg.memory_report()
        assert rep["device_total_bytes"] == sum(rep["device"].values())
        assert rep["host_flat_total_bytes"] == sum(rep["host_flat"].values())
        assert rep["total_bytes"] == (
            rep["device_total_bytes"] + rep["host_flat_total_bytes"]
        )
        assert rep["device"]["tile_word"] == pg.tile_word.nbytes
        assert rep["bytes_per_edge"] > rep["device_bytes_per_edge"] > 0
        assert "push_word" in rep["device"]

    def test_pull_only_drops_push_fields(self):
        g = _sparse_graph(256, 800, seed=6)
        pg = partition_2d(
            g, PartitionConfig(p=2, l=2, tile_vb=32, build_push=False)
        )
        rep = pg.memory_report()
        assert not any(k.startswith("push") for k in rep["device"])
