"""Two-level reduce suite: natural rows stay in place, an overfull block's
excess moves to appended blocks, and the fold gathers only the moved rows.

Covers the placement invariants (every natural row's in-place piece at its
own output position, no block above the capacity C so T <= C / Eb, fold
arrays sized by the moved rows and not by V) on a hub graph and on an R-MAT
whose hubs sit at low ids; the prepare_tiles edge cases (a row holding most
of the bucket, a many-way split of one row, empty buckets); a jaxpr check
that a pull phase gathers and scatters nothing larger than N x S;
equivalence of moved-Pallas vs identity-Pallas vs the XLA oracle across
BFS/WCC/SSSP/PR; the identity-element regression (a min problem's fold must
read INF for padding, never the sum identity 0); and ``split_threshold=None``
(the identity placement, no fold).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.core.graph as G
from repro.core.engine import (
    EngineOptions,
    _edge_constants,
    _phase_reduce_pallas,
    prepare_labels,
    run,
)
from repro.core.partition import (
    PartitionConfig,
    _bucket_split_threshold,
    apply_permutation,
    partition_2d,
)
from repro.core.problems import INF_U32, bfs, pagerank, sssp, wcc
from repro.data.synthetic import skewed_graph
from repro.kernels.csr_gather_reduce.ops import (
    FOLD_PAD,
    combine_split_rows,
    gather_reduce,
    prepare_tiles,
    stack_packed_tiles,
)

PROBLEMS = ["bfs", "wcc", "sssp", "pagerank"]

# sum (PR) reassociates across virtual-row chunks — tight tolerance; min
# problems must be bit-identical (same contract as the rest of the suite).
PR_TOL = dict(rtol=2e-5, atol=1e-8)


def _hub_graph(rng, n=512, hub=3, hub_deg=3000, bg=1000):
    """Multigraph with one dominant in-degree hub + uniform background."""
    src = np.concatenate([
        rng.integers(0, n, hub_deg), rng.integers(0, n, bg)
    ]).astype(np.uint32)
    dst = np.concatenate([
        np.full(hub_deg, hub, np.int64), rng.integers(0, n, bg)
    ]).astype(np.uint32)
    return G.COOGraph(src=src, dst=dst, num_vertices=n)


def _weighted(g, rng):
    w = rng.random(g.num_edges).astype(np.float32)
    return G.COOGraph(src=g.src, dst=g.dst, num_vertices=g.num_vertices, weights=w)


def _problem(pname, g, rng):
    if pname == "bfs":
        return bfs(1), g
    if pname == "wcc":
        return wcc(), g
    if pname == "sssp":
        return sssp(1), _weighted(g, rng)
    return pagerank(tol=1e-4), g


# ---------------------------------------------------------------------------
# prepare_tiles splitting edge cases
# ---------------------------------------------------------------------------


def _check_placement(t, dst, cap):
    """The placement contract for one bucket laid out by prepare_tiles from
    the dst-sorted rows ``dst``: in-place pieces at their own position,
    moved pieces only at their row's fold positions, every block at most
    ``cap`` edges, and fold arrays sized by the moved rows."""
    r_base = t.num_rows // t.vb
    pos = t.dstb + np.arange(t.src.shape[0])[:, None, None] * t.vb
    row = dst[t.gather_idx]  # natural row of every slot
    natural = pos < t.num_rows
    assert (pos[natural & t.valid] == row[natural & t.valid]).all()
    moved = t.valid & ~natural
    fold = dict(zip(t.fold_rows.tolist(), t.fold_pos.tolist()))
    for p_, r_ in zip(pos[moved].tolist(), row[moved].tolist()):
        assert p_ in fold[r_]
    loads = t.valid.sum(axis=(1, 2))
    assert loads.max() <= cap and t.src.shape[1] <= cap // t.src.shape[2]
    # every edge once; the fold names exactly the rows with a moved piece
    np.testing.assert_array_equal(
        np.bincount(row[t.valid], minlength=t.num_rows),
        np.bincount(dst, minlength=t.num_rows),
    )
    np.testing.assert_array_equal(t.fold_rows, np.unique(row[moved]))
    pieces = (t.fold_pos != FOLD_PAD).sum(axis=1)
    assert t.fold_pos.shape == (t.fold_rows.shape[0], pieces.max(initial=0))
    assert t.src.shape[0] == max(r_base, int(pos[moved].max(initial=-1)) // t.vb + 1)


def test_split_layout_invariants():
    """A hub row and a crowded block: both give up their excess, the hub
    in several pieces; the fold covers every moved row and nothing else."""
    rng = np.random.default_rng(0)
    v, e, thr = 64, 900, 40
    dst = np.sort(rng.integers(0, v, e)).astype(np.int32)
    dst[: e // 2] = 5  # hub row
    dst = np.sort(dst)
    src = rng.integers(0, 128, e).astype(np.int32)
    t = prepare_tiles(src, dst, np.ones(e, bool), num_rows=v, vb=8, eb=16,
                      split_threshold=thr)
    cap = 48  # thr rounded up to whole tiles
    _check_placement(t, dst, cap)
    assert 5 in t.fold_rows.tolist() and t.num_split_rows >= 1
    hub = t.fold_rows.tolist().index(5)
    # the hub's block keeps cap edges, the hub's own share of them in place
    kept = int(((t.dstb[0] == 5) & t.valid[0]).sum())
    moved = int((dst == 5).sum()) - kept
    assert int((t.fold_pos[hub] != FOLD_PAD).sum()) == -(-moved // cap)


@pytest.mark.parametrize("graph", ["hub", "rmat_low_id_hubs"])
def test_placement_invariants(graph, rng):
    """Every bucket of a hub graph and of an R-MAT whose hubs sit at low ids
    (not relabelled): in-place pieces at their own rows, every block at most
    C edges (so T <= C / Eb), and fold arrays sized by the moved rows."""
    if graph == "hub":
        g = _hub_graph(rng)
        cfg = PartitionConfig(p=2, l=2, lane=8, tile_vb=32, tile_eb=32)
    else:
        g = G.symmetrize(G.rmat(11, 8, seed=3))
        cfg = PartitionConfig(p=2, l=2, lane=8, tile_vb=64, tile_eb=32)
    pg = partition_2d(g, cfg)
    vpc, vb = pg.vertices_per_core, pg.tile_vb
    moved = []
    for i in range(pg.p):
        for m in range(pg.l):
            n = int(pg.bucket_sizes[i, m])
            dst = pg.dst_lidx[i, m, :n]
            thr = _bucket_split_threshold(cfg, n, vpc // vb)
            t = prepare_tiles(pg.src_gidx[i, m], pg.dst_lidx[i, m],
                              pg.valid[i, m], num_rows=vpc, vb=vb, eb=32,
                              split_threshold=thr)
            _check_placement(t, dst, -(-thr // 32) * 32)
            moved.append(t.fold_rows.shape[0])
    assert pg.split_rows == sum(moved) > 0
    n_max = max(moved)
    assert pg.tile_fold_rows.shape == (pg.p, pg.l, n_max)
    assert pg.tile_fold_pos.shape[:3] == (pg.p, pg.l, n_max)
    assert n_max < vpc // 4  # sized by the moved rows, not by V
    assert pg.tile_word.shape[3] < partition_2d(
        g, PartitionConfig(**{**cfg.__dict__, "split_threshold": None})
    ).tile_word.shape[3]


def test_pull_phase_gathers_only_moved_rows():
    """On a Graph500-like layout (relabelled Kronecker graph, scale 12,
    l = 16) the traced pull phase gathers and scatters nothing larger than
    the fold's N x S: the natural rows are a slice, not a gather."""
    g = G.symmetrize(G.rmat(12, 16, seed=5))
    perm = np.random.default_rng(6).permutation(g.num_vertices)
    g = apply_permutation(g, perm)
    pg = partition_2d(g, PartitionConfig(p=1, l=16, lane=8, tile_vb=64,
                                         tile_eb=128, build_push=False))
    assert pg.tile_fold_rows is not None
    n, s = pg.tile_fold_pos.shape[2:]
    assert n * s < pg.vertices_per_core // 8
    for prob in (bfs(0), pagerank()):
        opts = EngineOptions()
        consts = _edge_constants(prob, pg, opts)
        labels = prepare_labels(prob, g, pg)
        jaxpr = jax.make_jaxpr(
            lambda lab, c: _phase_reduce_pallas(prob, pg, c, lab, 3, opts)
        )(labels, consts)
        sizes = []

        def walk(jp):
            for eqn in jp.eqns:
                name = eqn.primitive.name
                if name == "gather" or name.startswith("scatter"):
                    # index tuples: the indices' last axis is the tuple
                    sizes.append(int(np.prod(eqn.invars[1].aval.shape[:-1])))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub.jaxpr if hasattr(sub, "jaxpr") else sub)

        walk(jaxpr.jaxpr)
        # the fold is there (a gather of N x S pieces, a scatter of N rows)
        # and nothing indexes more than N x S elements
        assert sizes and max(sizes) == n * s, sizes


def test_single_row_majority_of_edges():
    """A row holding > 50% of the bucket's edges must split and T must drop
    vs the unsplit layout; reductions stay correct."""
    rng = np.random.default_rng(1)
    v, vb, eb = 32, 8, 8
    hub_e, bg_e = 600, 200
    dst = np.sort(np.concatenate([
        np.full(hub_e, 9), rng.integers(0, v, bg_e)
    ]).astype(np.int32))
    e = dst.shape[0]
    src = rng.integers(0, 64, e).astype(np.int32)
    un = prepare_tiles(src, dst, np.ones(e, bool), num_rows=v, vb=vb, eb=eb)
    sp = prepare_tiles(src, dst, np.ones(e, bool), num_rows=v, vb=vb, eb=eb,
                       split_threshold=64)
    assert sp.src.shape[1] < un.src.shape[1]  # T shrinks
    assert sp.t_tiles_unsplit == un.src.shape[1]
    payload = jnp.asarray(rng.random(64).astype(np.float32))
    for kind, ident in (("min", float(np.finfo(np.float32).max)), ("sum", 0.0)):
        a = gather_reduce(payload, sp, kind=kind, identity=ident)
        b = gather_reduce(payload, un, kind=kind, identity=ident)
        if kind == "min":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), **PR_TOL)


def test_multiway_split_row_bigger_than_tmax_eb():
    """A hub bigger than the whole rest of the bucket times T_max*Eb forces a
    many-way split; R must grow past num_rows/vb to hold the pieces."""
    rng = np.random.default_rng(2)
    v, vb, eb = 16, 8, 8
    hub_e = 1000
    dst = np.sort(np.concatenate([
        np.full(hub_e, 2), rng.integers(0, v, 50)
    ]).astype(np.int32))
    src = rng.integers(0, 32, dst.shape[0]).astype(np.int32)
    sp = prepare_tiles(src, dst, np.ones(dst.shape[0], bool), num_rows=v,
                       vb=vb, eb=eb, split_threshold=eb)
    _check_placement(sp, dst, eb)
    # block 0 keeps eb edges: the hub moves what the block holds above that
    kept = int(((sp.dstb[0] == 2) & sp.valid[0]).sum())
    n_chunks = -(-(int((dst == 2).sum()) - kept) // eb)  # ~125 pieces
    hub = sp.fold_rows.tolist().index(2)
    assert (sp.fold_pos[hub] != FOLD_PAD).sum() == n_chunks
    assert sp.src.shape[0] > v // vb  # R grew
    payload = jnp.asarray(rng.random(32).astype(np.float32))
    out = gather_reduce(payload, sp, kind="min",
                        identity=float(np.finfo(np.float32).max))
    ref = prepare_tiles(src, dst, np.ones(dst.shape[0], bool), num_rows=v,
                        vb=vb, eb=eb)
    expect = gather_reduce(payload, ref, kind="min",
                           identity=float(np.finfo(np.float32).max))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_empty_bucket_and_empty_blocks():
    """Empty (core, phase) buckets and rows with zero edges survive the
    placement: counts 0, nothing moved, identity outputs."""
    t = prepare_tiles(np.zeros(0, np.int32), np.zeros(0, np.int32),
                      np.zeros(0, bool), num_rows=16, vb=4, eb=4,
                      split_threshold=2)
    assert t.fold_rows.size == 0 and t.num_split_rows == 0  # nothing moved
    out = gather_reduce(jnp.ones(8, jnp.float32), t, kind="min", identity=7.0)
    np.testing.assert_array_equal(np.asarray(out), np.full(16, 7.0, np.float32))


# ---------------------------------------------------------------------------
# engine-level equivalence: split-Pallas vs unsplit-Pallas vs XLA oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pname", PROBLEMS)
def test_engine_split_three_way(pname, rng):
    g0 = _hub_graph(rng)
    prob, g = _problem(pname, g0, rng)
    cfg = dict(p=2, l=2, lane=8, tile_vb=32, tile_eb=32)
    pg_split = partition_2d(g, PartitionConfig(**cfg))
    pg_none = partition_2d(g, PartitionConfig(**cfg, split_threshold=None))
    assert pg_split.split_rows > 0, "hub graph must move rows"
    assert pg_split.tile_fold_rows is not None and pg_none.tile_fold_rows is None
    assert pg_split.tile_word.shape[3] < pg_none.tile_word.shape[3]
    # the fold names each moved row once per bucket, in ascending order
    for i in range(pg_split.p):
        for m in range(pg_split.l):
            rows = pg_split.tile_fold_rows[i, m]
            real = rows[rows != FOLD_PAD]
            assert (np.diff(real) > 0).all()
            assert (pg_split.tile_fold_pos[i, m][rows == FOLD_PAD] == FOLD_PAD).all()

    res_s = run(prob, g, pg_split, EngineOptions(backend="pallas"))
    res_u = run(prob, g, pg_none, EngineOptions(backend="pallas"))
    res_x = run(prob, g, pg_none, EngineOptions(backend="xla"))
    if prob.reduce_kind == "min":
        np.testing.assert_array_equal(res_s.labels["label"], res_x.labels["label"])
        np.testing.assert_array_equal(res_u.labels["label"], res_x.labels["label"])
        assert res_s.iterations == res_u.iterations == res_x.iterations
    else:
        np.testing.assert_allclose(
            res_s.labels["label"], res_x.labels["label"], **PR_TOL
        )
        np.testing.assert_allclose(
            res_u.labels["label"], res_x.labels["label"], **PR_TOL
        )


@pytest.mark.parametrize("immediate", [True, False])
def test_engine_split_update_schemes(immediate, rng):
    """Async (immediate) and sync phases both run the level-2 combine."""
    g = _hub_graph(rng, n=256, hub_deg=1500, bg=600)
    pg = partition_2d(
        g, PartitionConfig(p=2, l=2, lane=8, tile_vb=16, tile_eb=16)
    )
    assert pg.split_rows > 0
    a = run(bfs(0), g, pg, EngineOptions(immediate_updates=immediate,
                                         backend="pallas"))
    b = run(bfs(0), g, pg, EngineOptions(immediate_updates=immediate,
                                         backend="xla"))
    np.testing.assert_array_equal(a.labels["label"], b.labels["label"])
    assert a.iterations == b.iterations


def test_engine_split_32bit_regime(rng):
    """Splitting composes with the 32-bit packed-word fallback."""
    gs = G.symmetrize(_hub_graph(rng, n=256, hub_deg=1500, bg=600))
    pgs = partition_2d(gs, PartitionConfig(p=2, l=2, lane=8, tile_vb=16,
                                           tile_eb=16, pack_src_bits=32))
    assert pgs.split_rows > 0 and pgs.src_bits == 32
    assert pgs.tile_word_hi is not None
    a = run(wcc(), gs, pgs, EngineOptions(backend="pallas"))
    b = run(wcc(), gs, pgs, EngineOptions(backend="xla"))
    np.testing.assert_array_equal(a.labels["label"], b.labels["label"])


# ---------------------------------------------------------------------------
# identity-element regression (satellite: the level-2 combine must use the
# problem's reduce identity — min folds with INF, sum with 0)
# ---------------------------------------------------------------------------


def test_combine_uses_reduce_identity_not_zero():
    """FOLD_PAD positions contribute the problem's identity: a wrong
    0-identity in a min fold would zero the row; a wrong INF in a sum fold
    would blow it up; reusing a real position would double-count. FOLD_PAD
    rows are dropped, and rows that did not move read their own position."""
    # natural rows 0..2; row 0 moved one piece (pos 3), row 2 two (4, 5)
    rows = jnp.asarray(np.array([[0, 2, FOLD_PAD]], np.int32))
    pos = jnp.asarray(np.array(
        [[[3, FOLD_PAD], [4, 5], [FOLD_PAD, FOLD_PAD]]], np.int32))
    reduced = jnp.asarray(np.array([[5.0, 7.0, 11.0, 2.0, 13.0, 3.0]], np.float32))
    kw = dict(num_rows=3)
    out_min = combine_split_rows(reduced, rows, pos, kind="min",
                                 identity=float(np.inf), **kw)
    np.testing.assert_array_equal(np.asarray(out_min), [[2.0, 7.0, 3.0]])
    out_sum = combine_split_rows(reduced, rows, pos, kind="sum", identity=0.0, **kw)
    np.testing.assert_array_equal(np.asarray(out_sum), [[7.0, 7.0, 27.0]])
    # uint32 min path (BFS/WCC labels): identity INF_U32 survives the cast
    red_u = jnp.asarray(np.array([[3, INF_U32, 9, 1, INF_U32, 8]], np.uint32))
    out_u = combine_split_rows(red_u, rows, pos, kind="min",
                               identity=float(INF_U32), **kw)
    np.testing.assert_array_equal(np.asarray(out_u), [[1, INF_U32, 8]])
    # word OR with a lane axis: identity 0, every lane folded alike
    red_w = jnp.asarray(np.array([[[1, 16], [2, 0], [4, 0], [8, 0], [0, 32],
                                   [64, 0]]], np.uint32))
    out_w = combine_split_rows(red_w, rows, pos, kind="or", identity=0.0, **kw)
    np.testing.assert_array_equal(np.asarray(out_w), [[[9, 16], [2, 0], [68, 32]]])
    # nothing moved: the fold is the natural blocks' slice
    np.testing.assert_array_equal(
        np.asarray(combine_split_rows(reduced, None, None, kind="min",
                                      identity=float(np.inf), **kw)),
        [[5.0, 7.0, 11.0]],
    )


def test_bfs_unreached_hub_row_stays_inf(rng):
    """Regression: a split hub row NOT reached by BFS must stay INF_U32 —
    any stray 0/sum-identity in the level-2 fold would mark it reached."""
    n = 128
    # hub 5 receives many edges from sources that BFS (rooted in a separate
    # component) never reaches; component {0, 1} is root's.
    hub_src = rng.integers(2, n, 800).astype(np.uint32)
    src = np.concatenate([hub_src, np.array([0], np.uint32)])
    dst = np.concatenate([np.full(800, 5, np.uint32), np.array([1], np.uint32)])
    g = G.COOGraph(src=src, dst=dst, num_vertices=n)
    pg = partition_2d(g, PartitionConfig(p=2, l=2, lane=8, tile_vb=8, tile_eb=8))
    assert pg.split_rows > 0
    res = run(bfs(0), g, pg, EngineOptions(backend="pallas"))
    assert res.labels["label"][1] == 1
    assert res.labels["label"][5] == INF_U32  # hub unreached: identity held
    oracle = run(bfs(0), g, pg, EngineOptions(backend="xla"))
    np.testing.assert_array_equal(res.labels["label"], oracle.labels["label"])


def test_pagerank_split_conserves_mass():
    """Sum identity regression: virtual-row partials must add each edge
    exactly once — total rank mass is conserved under splitting.

    Uses a private rng (NOT the shared session fixture): the graph must not
    depend on how many draws earlier tests made, or the reassociation
    tolerance turns order-dependent (seen as a full-suite-only flake)."""
    g = _hub_graph(np.random.default_rng(12), n=256, hub_deg=2000, bg=500)
    cfg = dict(p=2, l=2, lane=8, tile_vb=16, tile_eb=16)
    pg = partition_2d(g, PartitionConfig(**cfg))
    assert pg.split_rows > 0
    res = run(pagerank(tol=1e-5), g, pg, EngineOptions(backend="pallas"))
    ref = run(pagerank(tol=1e-5), g, pg, EngineOptions(backend="xla"))
    np.testing.assert_allclose(
        res.labels["label"].sum(), ref.labels["label"].sum(), rtol=1e-5
    )
    np.testing.assert_allclose(res.labels["label"], ref.labels["label"], **PR_TOL)


# ---------------------------------------------------------------------------
# metrics + disable switch
# ---------------------------------------------------------------------------


def test_star_t_max_halved_and_metrics():
    """Acceptance shape: on a star-like graph the split layout's T_max is
    <= 50% of the unsplit layout's, and the metrics record it."""
    g = skewed_graph(2048, kind="star", hub_in_degree=6000, avg_degree=2, seed=7)
    cfg = dict(p=4, l=2, lane=8, tile_vb=64)
    pg_split = partition_2d(g, PartitionConfig(**cfg))
    pg_none = partition_2d(g, PartitionConfig(**cfg, split_threshold=None))
    assert pg_split.tile_word.shape[3] <= 0.5 * pg_none.tile_word.shape[3]
    assert pg_split.t_max_unsplit == pg_none.tile_word.shape[3]
    assert pg_split.t_max_reduction <= 0.5
    assert 0.0 < pg_split.split_row_fraction < 1.0
    assert pg_split.skipped_tile_fraction < pg_none.skipped_tile_fraction
    # splitting also shrinks the stacked stream itself
    assert pg_split.tile_word.size < pg_none.tile_word.size
    assert pg_none.t_max_reduction == 1.0 and pg_none.split_row_fraction == 0.0


def test_split_threshold_none_preserves_old_layout():
    """``split_threshold=None`` is the identity placement even on a graph
    whose default partition moves rows: no fold arrays, R = Vl / vb, every
    block holds exactly its own rows' edges in bucket order — byte for byte
    what a manual identity prepare_tiles stack gives."""
    g = skewed_graph(512, kind="star", hub_in_degree=2000, avg_degree=2, seed=3)
    cfg = dict(p=2, l=2, lane=8, tile_vb=32, tile_eb=32)
    pg_auto = partition_2d(g, PartitionConfig(**cfg))
    pg_none = partition_2d(g, PartitionConfig(**cfg, split_threshold=None))
    assert pg_auto.split_rows > 0
    assert pg_none.tile_fold_rows is None and pg_none.tile_fold_pos is None
    assert pg_none.split_rows == 0
    vpc, vb = pg_none.vertices_per_core, pg_none.tile_vb
    assert pg_none.tile_word.shape[2] == vpc // vb
    layouts = [
        prepare_tiles(
            pg_none.src_gidx[i, m], pg_none.dst_lidx[i, m], pg_none.valid[i, m],
            num_rows=vpc, vb=vb, eb=32,
        )
        for i in range(pg_none.p)
        for m in range(pg_none.l)
    ]
    for t in layouts:  # natural rows, in place
        row = t.dstb + np.arange(t.src.shape[0])[:, None, None] * vb
        n = int(t.valid.sum())
        order = np.argsort(t.gather_idx[t.valid], kind="stable")
        assert (np.diff(row[t.valid][order]) >= 0).all() and n == order.size
    word, _, counts, _ = stack_packed_tiles(layouts, src_bits=pg_none.src_bits)
    np.testing.assert_array_equal(
        pg_none.tile_word, word.reshape(pg_none.tile_word.shape)
    )
    np.testing.assert_array_equal(
        pg_none.tile_counts, counts.reshape(pg_none.tile_counts.shape)
    )


def test_auto_threshold_no_hub_is_identical_to_disabled():
    """'auto' on a graph whose blocks all fit their capacity moves nothing,
    so the layout equals the identity placement exactly and the fold is the
    slice alone — a balanced graph pays nothing for the default."""
    g = G.symmetrize(G.erdos_renyi(1024, 1000, seed=13))
    cfg = dict(p=2, l=2, lane=4, tile_vb=64)
    pg_auto = partition_2d(g, PartitionConfig(**cfg))
    pg_none = partition_2d(g, PartitionConfig(**cfg, split_threshold=None))
    assert pg_auto.split_rows == 0 and pg_auto.tile_fold_rows is None
    np.testing.assert_array_equal(pg_auto.tile_word, pg_none.tile_word)
    np.testing.assert_array_equal(pg_auto.tile_counts, pg_none.tile_counts)
    assert pg_auto.channel_arrays()["fold_rows"] is None


def test_skewed_graph_generator_deterministic():
    a = skewed_graph(256, kind="powerlaw", hub_in_degree=500, seed=5)
    b = skewed_graph(256, kind="powerlaw", hub_in_degree=500, seed=5)
    np.testing.assert_array_equal(a.src, b.src)
    np.testing.assert_array_equal(a.dst, b.dst)
    assert np.bincount(a.dst, minlength=256).max() <= 500
    with pytest.raises(ValueError, match="star"):
        skewed_graph(16, kind="ring")
