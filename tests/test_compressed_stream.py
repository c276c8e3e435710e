"""Compressed edge-stream equivalence suite.

Three implementations of the phase reduction must agree:

  1. compressed-Pallas  — ``gather_reduce_cores_pallas`` on the bit-packed
     word stream with scalar-prefetched tile-count skipping (the engine hot
     path),
  2. uncompressed-Pallas — ``gather_reduce_pallas`` on the raw
     (src, dstb, valid) tile arrays (runs every tile, padding included),
  3. XLA oracle — ``gather_reduce_reference`` / the engine's ``backend='xla'``.

Min reductions (BFS/WCC/SSSP) must be BIT-IDENTICAL everywhere. Sum (PR) is
bit-identical between the two Pallas paths (identical tile binning; skipped
tiles only ever add the exact 0.0 identity) and tight-tolerance vs the oracle
(different summation order by design).

Also pins down the packed word format itself (roundtrip + field-overflow
rejection) and the 16->32-bit regime fallback when the gathered crossbar
block outgrows the 16-bit src field (p * sub_size > 2^16).
"""
import numpy as np
import pytest

import jax.numpy as jnp

import repro.core.graph as G
from repro.core.engine import EngineOptions, prepare_labels, run
from repro.core.partition import PartitionConfig, _bucket_split_threshold, partition_2d
from repro.core.problems import bfs, pagerank, sssp, wcc
from repro.kernels.csr_gather_reduce.kernel import (
    gather_reduce_cores_pallas,
    gather_reduce_pallas,
)
from repro.kernels.csr_gather_reduce.ops import (
    DSTB16_LIMIT,
    SRC16_LIMIT,
    choose_src_bits,
    pack_edge_words,
    prepare_tiles,
    stack_packed_tiles,
)
from repro.kernels.csr_gather_reduce.ref import gather_reduce_reference

PROBLEMS = ["bfs", "wcc", "sssp", "pagerank"]


# ---------------------------------------------------------------------------
# Packed word format
# ---------------------------------------------------------------------------


def _unpack_np(word, word_hi, src_bits):
    """Numpy mirror of the in-kernel shift/mask decode."""
    if src_bits == 16:
        w = word.view(np.uint32)
        return w & 0xFFFF, (w >> 16) & 0x7FFF, word < 0
    hi = word_hi.view(np.uint32)
    return word.view(np.uint32), hi & 0x7FFFFFFF, word_hi < 0


@pytest.mark.parametrize("src_bits", [16, 32])
def test_pack_roundtrip(src_bits, rng):
    n = 4096
    src_max = SRC16_LIMIT if src_bits == 16 else 1 << 20
    dst_max = DSTB16_LIMIT if src_bits == 16 else 1 << 18
    src = rng.integers(0, src_max, n).astype(np.int64)
    dstb = rng.integers(0, dst_max, n).astype(np.int64)
    valid = rng.random(n) < 0.7
    # force the boundary values so the field widths are actually exercised
    src[0], dstb[0], valid[0] = src_max - 1, dst_max - 1, True
    src[1], dstb[1], valid[1] = 0, 0, False
    word, word_hi = pack_edge_words(src, dstb, valid, src_bits=src_bits)
    assert word.dtype == np.int32
    assert (word_hi is None) == (src_bits == 16)
    s, d, v = _unpack_np(word, word_hi, src_bits)
    np.testing.assert_array_equal(s, src)
    np.testing.assert_array_equal(d, dstb)
    np.testing.assert_array_equal(v, valid)


def test_pack_rejects_field_overflow():
    ok = np.zeros(2, np.int64)
    with pytest.raises(ValueError, match="16-bit"):
        pack_edge_words(np.array([SRC16_LIMIT]), ok[:1], np.ones(1, bool), src_bits=16)
    with pytest.raises(ValueError, match="15-bit"):
        pack_edge_words(ok[:1], np.array([DSTB16_LIMIT]), np.ones(1, bool), src_bits=16)
    with pytest.raises(ValueError, match="16 or 32"):
        pack_edge_words(ok, ok, np.ones(2, bool), src_bits=8)


def test_choose_src_bits_thresholds():
    assert choose_src_bits(SRC16_LIMIT, 8) == 16
    assert choose_src_bits(SRC16_LIMIT + 1, 8) == 32
    assert choose_src_bits(100, DSTB16_LIMIT) == 16
    assert choose_src_bits(100, DSTB16_LIMIT + 1) == 32


# ---------------------------------------------------------------------------
# Kernel-level three-way equivalence on random buckets (property-style)
# ---------------------------------------------------------------------------


def _random_cores(rng, p, v, e, g_sz, vb, eb, weighted):
    """Per-core random dst-sorted buckets -> (tiles list, packed cores stack)."""
    tiles = []
    for _ in range(p):
        dst = np.sort(rng.integers(0, v, e)).astype(np.int32)
        src = rng.integers(0, g_sz, e).astype(np.int32)
        valid = rng.random(e) < 0.8
        w = rng.random(e).astype(np.float32) if weighted else None
        tiles.append(
            prepare_tiles(src, dst, valid, num_rows=v, vb=vb, eb=eb, weights=w)
        )
    src_bits = choose_src_bits(g_sz, vb)
    word, hi, counts, weights = stack_packed_tiles(tiles, src_bits=src_bits)
    return tiles, word, hi, counts, weights, src_bits


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize(
    "kind,edge_op,weighted",
    [("min", "none", False), ("sum", "none", False),
     ("min", "add", True), ("min", "add", False)],
)
def test_kernel_three_way_random(seed, kind, edge_op, weighted):
    rng = np.random.default_rng(seed)
    p, v, e, g_sz, vb, eb = 2, 32, 300, 64, 8, 16
    identity = np.float32(np.finfo(np.float32).max) if kind == "min" else 0.0
    tiles, word, hi, counts, weights, src_bits = _random_cores(
        rng, p, v, e, g_sz, vb, eb, weighted
    )
    assert src_bits == 16
    payload = rng.random(g_sz).astype(np.float32)
    jp = jnp.asarray(payload)

    compressed = gather_reduce_cores_pallas(
        jp, jnp.asarray(word), jnp.asarray(counts),
        None if hi is None else jnp.asarray(hi),
        None if weights is None else jnp.asarray(weights),
        num_rows=v, vb=vb, src_bits=src_bits, kind=kind, edge_op=edge_op,
        identity=float(identity), interpret=True,
    )
    for i, t in enumerate(tiles):
        uncompressed = gather_reduce_pallas(
            jp, jnp.asarray(t.src), jnp.asarray(t.dstb), jnp.asarray(t.valid),
            None if t.weights is None else jnp.asarray(t.weights),
            num_rows=v, vb=vb, kind=kind, edge_op=edge_op,
            identity=float(identity), interpret=True,
        )
        # identical binning + exact identity padding => bit-identical even for sum
        np.testing.assert_array_equal(
            np.asarray(compressed[i]), np.asarray(uncompressed)
        )
        block_base = np.arange(v // vb, dtype=np.int32)[:, None, None] * vb
        ref_w = None
        if edge_op == "add":  # reference needs explicit unit weights
            ref_w = (
                jnp.asarray(t.weights).reshape(-1)
                if t.weights is not None
                else jnp.ones(t.src.size, jnp.float32)
            )
        oracle = gather_reduce_reference(
            jp,
            jnp.asarray(t.src).reshape(-1),
            jnp.asarray(t.dstb + block_base).reshape(-1),
            jnp.asarray(t.valid).reshape(-1),
            v, kind=kind, identity=float(identity),
            weights=ref_w,
        )
        if kind == "min":
            np.testing.assert_array_equal(np.asarray(compressed[i]), np.asarray(oracle))
        else:
            np.testing.assert_allclose(
                np.asarray(compressed[i]), np.asarray(oracle), rtol=1e-6, atol=1e-9
            )


def test_kernel_32bit_src_beyond_16bit_range(rng):
    """Real 32-bit-regime run whose src offsets genuinely exceed 2^16 — the
    fallback must address the full gathered block."""
    g_sz, p, v, e, vb, eb = 70_000, 1, 16, 64, 8, 8
    dst = np.sort(rng.integers(0, v, e)).astype(np.int32)
    src = rng.integers(0, g_sz, e).astype(np.int32)
    src[0] = g_sz - 1  # force an offset that cannot fit 16 bits
    valid = np.ones(e, bool)
    tiles = prepare_tiles(src, dst, valid, num_rows=v, vb=vb, eb=eb)
    src_bits = choose_src_bits(g_sz, vb)
    assert src_bits == 32
    word, hi, counts, _ = stack_packed_tiles([tiles], src_bits=32)
    payload = rng.random(g_sz).astype(np.float32)
    out = gather_reduce_cores_pallas(
        jnp.asarray(payload),
        jnp.asarray(word),
        jnp.asarray(counts),
        jnp.asarray(hi),
        None,
        num_rows=v, vb=vb, src_bits=32, kind="min",
        identity=float(np.finfo(np.float32).max), interpret=True,
    )
    block_base = np.arange(v // vb, dtype=np.int32)[:, None, None] * vb
    oracle = gather_reduce_reference(
        jnp.asarray(payload),
        jnp.asarray(tiles.src).reshape(-1),
        jnp.asarray(tiles.dstb + block_base).reshape(-1),
        jnp.asarray(tiles.valid).reshape(-1),
        v, kind="min", identity=float(np.finfo(np.float32).max),
    )
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(oracle))


# ---------------------------------------------------------------------------
# Engine-level three-way equivalence across the four problems
# ---------------------------------------------------------------------------


def _make_case(pname, rng):
    if pname == "sssp":
        g0 = G.rmat(8, 6, seed=11)
        w = rng.random(g0.num_edges).astype(np.float32)
        g = G.COOGraph(src=g0.src, dst=g0.dst, num_vertices=g0.num_vertices, weights=w)
        return sssp(1), g
    if pname == "pagerank":
        return pagerank(), G.rmat(8, 6, seed=12)
    g = G.symmetrize(G.rmat(8, 6, seed=13))
    return (bfs(3), g) if pname == "bfs" else (wcc(), g)


@pytest.mark.parametrize("pname", PROBLEMS)
@pytest.mark.parametrize("force_bits", [None, 32])
def test_engine_three_way(pname, force_bits, rng):
    """Full engine runs (compressed-Pallas vs XLA oracle) plus a per-phase
    sweep against the uncompressed-Pallas kernel, in both packing regimes
    (auto-16 and forced-32 fallback)."""
    prob, g = _make_case(pname, rng)
    pg = partition_2d(g, PartitionConfig(p=2, l=2, lane=4, pack_src_bits=force_bits))
    assert pg.src_bits == (force_bits or 16)
    assert (pg.tile_word_hi is not None) == (pg.src_bits == 32)

    res_p = run(prob, g, pg, EngineOptions(backend="pallas"))
    res_x = run(prob, g, pg, EngineOptions(backend="xla"))
    assert res_p.iterations == res_x.iterations
    if prob.reduce_kind == "min":
        np.testing.assert_array_equal(res_p.labels["label"], res_x.labels["label"])
    else:
        np.testing.assert_allclose(
            res_p.labels["label"], res_x.labels["label"], rtol=1e-6, atol=1e-9
        )

    # per-phase: compressed cores stream vs uncompressed per-bucket tiles on
    # the INITIAL labels (any fixed payload works — the kernels are pure)
    labels = prepare_labels(prob, g, pg)
    payload = np.asarray(prob.src_transform(labels))
    eb = pg.tile_word.shape[-1]
    for m in range(pg.l):
        gathered = jnp.asarray(
            payload[:, m * pg.sub_size : (m + 1) * pg.sub_size].reshape(-1)
        )
        w_m = (
            jnp.asarray(pg.tile_weights[:, m])
            if prob.edge_op == "add" and pg.tile_weights is not None
            else None
        )
        compressed = gather_reduce_cores_pallas(
            gathered,
            jnp.asarray(pg.tile_word[:, m]),
            jnp.asarray(pg.tile_counts[:, m]),
            jnp.asarray(pg.tile_word_hi[:, m]) if pg.tile_word_hi is not None else None,
            w_m,
            num_rows=pg.packed_rows_per_core, vb=pg.tile_vb, src_bits=pg.src_bits,
            kind=prob.reduce_kind, edge_op=prob.edge_op,
            identity=prob.identity, interpret=True,
        )
        for i in range(pg.p):
            tiles = prepare_tiles(
                pg.src_gidx[i, m], pg.dst_lidx[i, m], pg.valid[i, m],
                num_rows=pg.vertices_per_core, vb=pg.tile_vb, eb=eb,
                weights=pg.weights[i, m] if pg.weights is not None else None,
                split_threshold=_bucket_split_threshold(
                    pg.config, int(pg.bucket_sizes[i, m]),
                    pg.vertices_per_core // pg.tile_vb,
                ),
            )
            rows = tiles.src.shape[0] * pg.tile_vb
            uncompressed = gather_reduce_pallas(
                gathered,
                jnp.asarray(tiles.src), jnp.asarray(tiles.dstb),
                jnp.asarray(tiles.valid),
                jnp.asarray(tiles.weights)
                if tiles.weights is not None and prob.edge_op == "add"
                else None,
                num_rows=rows, vb=pg.tile_vb,
                kind=prob.reduce_kind, edge_op=prob.edge_op,
                identity=prob.identity, interpret=True,
            )
            np.testing.assert_array_equal(
                np.asarray(compressed[i, :rows]), np.asarray(uncompressed)
            )


def _expected_stream_bpe(pg):
    """Mirror of the accounting contract: pull packed words plus the push
    stream's packed words amortized over pull edge slots."""
    pull = 4.0 * (1 if pg.tile_word_hi is None else 2)
    if pg.push_word is None:
        return pull
    push = 4.0 * (1 if pg.push_word_hi is None else 2)
    return pull + push * pg.push_word.size / pg.tile_word.size


def test_partition_auto_selects_32bit_fallback():
    """p * sub_size > 2^16 flips the regime without being asked to."""
    g = G.rmat(17, 1, seed=3)  # 131072 vertices
    pg = partition_2d(g, PartitionConfig(p=2, l=1))  # gathered block = 131072
    assert pg.gathered_size > SRC16_LIMIT
    assert pg.src_bits == 32
    assert pg.tile_word_hi is not None
    # push stream built by default: bytes/edge = pull 8.0 + amortized push
    assert pg.push_word is not None
    assert pg.stream_bytes_per_edge == _expected_stream_bpe(pg)
    assert pg.stream_bytes_per_edge > 8.0
    # opting out of the push layout restores the exact pull-only figure
    pg_pull = partition_2d(g, PartitionConfig(p=2, l=1, build_push=False))
    assert pg_pull.push_word is None
    assert pg_pull.stream_bytes_per_edge == 8.0


def test_stream_metrics_16bit_regime():
    g = G.symmetrize(G.rmat(9, 8, seed=5))
    pg = partition_2d(g, PartitionConfig(p=4, l=4, lane=8))
    assert pg.src_bits == 16 and pg.tile_word_hi is None
    assert pg.stream_bytes_per_edge == _expected_stream_bpe(pg)
    assert 0.0 <= pg.skipped_tile_fraction < 1.0
    # counts never exceed the uniform T the stream was padded to
    assert int(pg.tile_counts.max()) <= pg.tile_word.shape[3]
    pg_pull = partition_2d(g, PartitionConfig(p=4, l=4, lane=8,
                                              build_push=False))
    assert pg_pull.stream_bytes_per_edge == 4.0
    # push coverage words are charged to the coverage overhead metric
    assert pg.coverage_bytes_per_edge > pg_pull.coverage_bytes_per_edge
