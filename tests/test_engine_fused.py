"""Fused-path equivalence suite: the engine's Pallas (interpret) backend vs
the XLA oracle across all four problems x update schemes x partition shapes.

Min problems (BFS/WCC/SSSP) must be BIT-IDENTICAL: gather, saturating add,
and min-reduce are order-independent, so any divergence is a real bug.
PageRank (sum reduce) is checked to tight tolerance plus identical iteration
counts — the fused kernel reduces per (row-block, tile) while the oracle
segment-sums the flat edge list, so float summation order differs by design.

Also proves the bandwidth claim structurally: the jaxpr of a fused iteration
contains NO (p, E_pad) intermediate (the materialize-then-reduce array the
XLA path builds) and no decompressed full-size edge-index arrays — the only
full-size per-edge intermediate is the bit-packed word stream — while the
oracle's jaxpr keeps the (p, E_pad) array. See test_compressed_stream.py for
the word-format and three-way kernel equivalence suite.
"""
import numpy as np
import pytest

import jax

import repro.core.graph as G
from repro.core.engine import EngineOptions, _make_iteration, prepare_labels, run
from repro.core.partition import PartitionConfig, partition_2d
from repro.core.problems import bfs, pagerank, sssp, wcc

PROBLEMS = ["bfs", "wcc", "sssp", "pagerank"]


def _make_case(pname, rng):
    """(problem, graph) pairs sized for interpret-mode grids."""
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
@pytest.mark.parametrize("immediate", [True, False])
@pytest.mark.parametrize("p", [1, 2, 4])
@pytest.mark.parametrize("l", [1, 3])
def test_fused_matches_xla(pname, immediate, p, l, rng):
    prob, g = _make_case(pname, rng)
    pg = partition_2d(g, PartitionConfig(p=p, l=l, lane=4))
    res_x = run(prob, g, pg, EngineOptions(immediate_updates=immediate, backend="xla"))
    res_p = run(prob, g, pg, EngineOptions(immediate_updates=immediate, backend="pallas"))
    assert res_p.iterations == res_x.iterations
    assert res_p.converged == res_x.converged
    if prob.reduce_kind == "min":
        np.testing.assert_array_equal(res_p.labels["label"], res_x.labels["label"])
    else:
        np.testing.assert_allclose(
            res_p.labels["label"], res_x.labels["label"], rtol=1e-6, atol=1e-9
        )


@pytest.mark.parametrize("stride", [None, 7])
def test_fused_matches_xla_with_stride_and_packing_off(stride, rng):
    """Moving overflow out of a block and stride mapping are layout choices
    — results must not change."""
    g = G.symmetrize(G.rmat(8, 6, seed=21))
    for threshold in ("auto", None):
        pg = partition_2d(
            g,
            PartitionConfig(p=2, l=2, lane=4, stride=stride, split_threshold=threshold),
        )
        a = run(bfs(0), g, pg, EngineOptions(backend="pallas"))
        b = run(bfs(0), g, pg, EngineOptions(backend="xla"))
        np.testing.assert_array_equal(a.labels["label"], b.labels["label"])
        assert a.iterations == b.iterations


def _iteration_avals(problem, g, pg, backend):
    """(shape, dtype-name) of every intermediate in one traced iteration,
    including sub-jaxprs (fori_loop bodies, pallas_call kernels)."""
    labels = prepare_labels(problem, g, pg)
    opts = EngineOptions(backend=backend)
    iteration = _make_iteration(problem, pg, opts)
    jaxpr = jax.make_jaxpr(iteration)(labels)

    avals = []

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                    avals.append(
                        (tuple(v.aval.shape), str(getattr(v.aval, "dtype", "")))
                    )
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)

    walk(jaxpr.jaxpr)
    return avals


def test_fused_path_materializes_no_contributions_array():
    """Bandwidth property, checked structurally: a fused iteration's jaxpr has
    no (p, E_pad) intermediate (the materialize-then-reduce array the XLA path
    builds) and no decompressed full-size edge-index array — the only
    (p, R, T, Eb) int32 intermediate is the packed word stream itself, and no
    (p, R, T, Eb) bool valid mask exists at all. The oracle's jaxpr keeps the
    (p, E_pad) array (positive control, so the check cannot rot into
    vacuity)."""
    g = G.symmetrize(G.rmat(9, 8, seed=5))
    pg = partition_2d(g, PartitionConfig(p=2, l=2, lane=4))
    contrib_shape = (pg.p, pg.edge_pad)
    fused = _iteration_avals(bfs(0), g, pg, "pallas")
    oracle = _iteration_avals(bfs(0), g, pg, "xla")
    assert contrib_shape not in {s for s, _ in fused}
    assert contrib_shape in {s for s, _ in oracle}

    # compressed-stream property: exactly ONE full-size (p, R, T, Eb) int32
    # intermediate (the phase-sliced packed word) — an unpacked src/dstb pair
    # would add more — and no full-size bool valid array anywhere.
    tile_shape = (pg.p,) + pg.tile_word.shape[2:]
    int32_tiles = [d for s, d in fused if s == tile_shape and d == "int32"]
    bool_tiles = [d for s, d in fused if s == tile_shape and d == "bool"]
    assert len(int32_tiles) == 1, int32_tiles
    assert not bool_tiles


def test_fused_kernel_runs_all_cores_in_one_launch():
    """One pallas_call (or interpreter equivalent) per phase covers all p
    cores: the stacked tile arrays carry the core dimension."""
    g = G.symmetrize(G.rmat(8, 6, seed=6))
    pg = partition_2d(g, PartitionConfig(p=4, l=2, lane=4))
    assert pg.tile_word.shape[:2] == (4, 2)
    assert pg.tile_counts.shape == pg.tile_word.shape[:3]
    assert pg.tile_vb > 0 and pg.vertices_per_core % pg.tile_vb == 0


def test_degree_aware_packing_reduces_tile_padding():
    """Moving an overfull block's excess must never do worse than the
    identity placement, and on a skew-clustered graph (R-MAT low-id hubs)
    it must do strictly better."""
    g = G.symmetrize(G.rmat(12, 8, seed=2))
    cfg = dict(p=4, l=2, lane=4, tile_vb=32)
    packed = partition_2d(g, PartitionConfig(**cfg))
    plain = partition_2d(g, PartitionConfig(**cfg, split_threshold=None))
    assert packed.tile_word.shape[3] < plain.tile_word.shape[3]  # T shrinks
    assert packed.tile_padding_ratio < plain.tile_padding_ratio


def test_row_pos_is_a_permutation():
    """Natural rows stay in place: the first Vl kernel-output rows of every
    bucket are its natural rows, in order, and each moved row is named once
    in its bucket's fold."""
    from repro.kernels.csr_gather_reduce.ops import FOLD_PAD

    g = G.symmetrize(G.rmat(9, 6, seed=7))
    pg = partition_2d(g, PartitionConfig(p=2, l=2, lane=4, tile_vb=16))
    assert pg.tile_fold_rows is not None
    vpc, vb = pg.vertices_per_core, pg.tile_vb
    r0 = vpc // vb
    word = pg.tile_word.reshape(*pg.tile_word.shape[:4], -1)
    for i in range(pg.p):
        for m in range(pg.l):
            n = int(pg.bucket_sizes[i, m])
            w = word[i, m, :r0]
            valid = w < 0
            row = ((w >> 16) & 0x7FFF) + np.arange(r0)[:, None, None] * vb
            kept = np.bincount(row[valid], minlength=vpc)
            full = np.bincount(pg.dst_lidx[i, m, :n], minlength=vpc)
            moved = np.flatnonzero(kept < full)
            assert (kept <= full).all()
            rows = pg.tile_fold_rows[i, m]
            np.testing.assert_array_equal(rows[rows != FOLD_PAD], moved)


def test_sssp_unit_weights_without_weight_array(rng):
    """edge_op='add' on an unweighted graph: the fused path synthesizes unit
    weights and must match the oracle (which adds 1.0 in edge_map)."""
    g = G.symmetrize(G.rmat(8, 6, seed=8))
    pg = partition_2d(g, PartitionConfig(p=2, l=2, lane=4))
    a = run(sssp(0), g, pg, EngineOptions(backend="pallas"))
    b = run(sssp(0), g, pg, EngineOptions(backend="xla"))
    np.testing.assert_array_equal(a.labels["label"], b.labels["label"])
    assert a.iterations == b.iterations
