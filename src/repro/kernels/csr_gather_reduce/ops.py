"""Host-side tile preparation + jitted wrapper for the accumulator kernel.

``prepare_tiles`` bins a (dst-sorted) edge bucket into (R, T, Eb) row-block
tiles at partition time (numpy). Every natural row keeps its own output
position; with ``split_threshold`` set, a row block holding more than that
capacity moves its excess (its heaviest rows' edges) into appended blocks,
so no block, and so no T, exceeds the capacity however skewed the rows. The
kernel reduces every packed row independently (level 1) and
``combine_split_rows`` folds the moved pieces back into their rows with the
problem's reduce op (level 2), touching only the rows that moved: the TPU
analogue of the paper's two-level crossbar absorbing power-law skew.
``pack_edge_words`` bit-packs the (src, dstb, valid) index triple of each
edge slot into the compressed word stream the fused engine path reads (see
``kernel.py`` for the word format and ``choose_src_bits`` for the 16/32-bit
regime rule).
``gather_reduce`` runs the Pallas kernel; ``segment_reduce_rows`` is the
reduce-only variant used when contributions are already materialized (engine
fallback path).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.csr_gather_reduce.kernel import gather_reduce_pallas, tile_shape
from repro.kernels.csr_gather_reduce.ref import gather_reduce_reference

__all__ = [
    "TileLayout",
    "TilePlan",
    "PushTileLayout",
    "plan_tiles",
    "plan_push_tiles",
    "prepare_tiles",
    "prepare_push_tiles",
    "choose_src_bits",
    "pack_edge_words",
    "stack_packed_tiles",
    "stack_push_tiles",
    "tile_coverage_words",
    "stack_fold_arrays",
    "combine_split_rows",
    "gather_reduce",
    "segment_reduce_rows",
]

# packed-word field bounds (see kernel.py "Compressed edge stream" docstring)
SRC16_LIMIT = 1 << 16  # gathered-block offsets that fit the 16-bit src field
DSTB16_LIMIT = 1 << 15  # row-block offsets that fit next to a 16-bit src
# fold-array padding: past the end of every axis it indexes, so a gather
# reads the fill value and a scatter drops it (a negative index would wrap)
FOLD_PAD = np.iinfo(np.int32).max


def choose_src_bits(gathered_size: int, vb: int) -> int:
    """Packed-word regime rule: 16-bit src iff every gathered-block offset fits
    16 bits AND the row-block offset fits the remaining 15 bits (bit 31 is the
    valid flag). Otherwise fall back to a two-word (32-bit src) stream."""
    return 16 if gathered_size <= SRC16_LIMIT and vb <= DSTB16_LIMIT else 32


def pack_edge_words(
    src: np.ndarray,  # (...,) int, gathered-block offsets in [0, G)
    dstb: np.ndarray,  # (...,) int, row offsets WITHIN the row block [0, vb)
    valid: np.ndarray,  # (...,) bool
    *,
    src_bits: int,
) -> tuple[np.ndarray, np.ndarray | None]:
    """Bit-pack edge-slot index triples into the compressed stream (numpy,
    partition time). Returns ``(word, word_hi)`` int32 arrays of ``src.shape``;
    ``word_hi`` is None in the 16-bit regime.

      src_bits=16: word    = valid<<31 | dstb<<16 | src          (4 B/edge)
      src_bits=32: word    = src                                  (8 B/edge)
                   word_hi = valid<<31 | dstb

    Padding slots (valid=False) pack to words with bit 31 clear, so the
    in-kernel validity test is simply ``word < 0`` (resp. ``word_hi < 0``).
    """
    src64 = np.asarray(src, dtype=np.int64)
    dstb64 = np.asarray(dstb, dtype=np.int64)
    # 32-bit bounds are the int32-REPRESENTABLE ranges: the kernel reads the
    # words back as int32, so src in [2^31, 2^32) would gather at a negative
    # index and dstb's bit 31 is the valid flag.
    src_limit = SRC16_LIMIT if src_bits == 16 else 1 << 31
    dstb_limit = DSTB16_LIMIT if src_bits == 16 else 1 << 31
    if src_bits not in (16, 32):
        raise ValueError(f"src_bits must be 16 or 32, got {src_bits}")
    if src64.size and not (0 <= int(src64.min()) and int(src64.max()) < src_limit):
        raise ValueError(
            f"src offsets [{int(src64.min())}, {int(src64.max())}] do not fit "
            f"the {src_bits}-bit field"
            + ("; use src_bits=32" if src_bits == 16 else "")
        )
    if dstb64.size and not (0 <= int(dstb64.min()) and int(dstb64.max()) < dstb_limit):
        raise ValueError(
            f"dstb offsets [{int(dstb64.min())}, {int(dstb64.max())}] do not fit "
            f"the {15 if src_bits == 16 else 31}-bit field"
            + ("; use src_bits=32" if src_bits == 16 else "")
        )
    src_u = src64.astype(np.uint32)
    dstb_u = dstb64.astype(np.uint32)
    vbit = np.asarray(valid, dtype=np.uint32) << 31
    if src_bits == 16:
        return (vbit | (dstb_u << 16) | src_u).view(np.int32), None
    return src_u.view(np.int32), (vbit | dstb_u).view(np.int32)


def stack_packed_tiles(
    layouts: list[TileLayout], *, src_bits: int
) -> tuple[np.ndarray, np.ndarray | None, np.ndarray, np.ndarray | None]:
    """Pack each layout's (src, dstb, valid) triple and stack to one
    uniform-(R, T) compressed stream: ``(word, word_hi, counts, weights)``
    with shapes (n, R_max, T_max, *tile_shape(Eb)) / (n, R_max). Layouts shorter than
    R_max / T_max are padded with all-invalid words that ``counts`` (0 for
    padded row blocks) tells the kernel to skip. The
    single source of truth for the stream layout the engine, benchmarks, and
    tests consume."""
    n = len(layouts)
    tile = tile_shape(layouts[0].src.shape[2])
    # hub-row splitting can grow R per bucket; pad both R and T to the max
    # (extra blocks have counts 0, so the kernel's early-out skips them).
    r_max = max(t.src.shape[0] for t in layouts)
    t_max = max(t.src.shape[1] for t in layouts)
    word = np.zeros((n, r_max, t_max, *tile), np.int32)
    word_hi = np.zeros((n, r_max, t_max, *tile), np.int32) if src_bits == 32 else None
    counts = np.zeros((n, r_max), np.int32)
    any_w = any(t.weights is not None for t in layouts)
    weights = np.zeros((n, r_max, t_max, *tile), np.float32) if any_w else None
    for i, t in enumerate(layouts):
        rr, tt = t.src.shape[:2]
        w0, w1 = pack_edge_words(t.src, t.dstb, t.valid, src_bits=src_bits)
        word[i, :rr, :tt] = w0.reshape(rr, tt, *tile)
        if word_hi is not None:
            word_hi[i, :rr, :tt] = w1.reshape(rr, tt, *tile)
        counts[i, :rr] = t.tile_counts
        if weights is not None and t.weights is not None:
            weights[i, :rr, :tt] = t.weights.reshape(rr, tt, *tile)
    return word, word_hi, counts, weights


def tile_coverage_words(
    word: np.ndarray,  # (..., *tile_shape(Eb)) int32 packed edge words
    word_hi: np.ndarray | None,  # like word, int32 in the 32-bit regime
    *,
    src_bits: int,
    p: int,
    sub_size: int,
) -> np.ndarray:
    """Per-tile source-coverage bitmaps for frontier-aware dynamic skipping.

    Decodes each tile's packed words (numpy, partition time — the ONLY place
    the compressed stream is ever unpacked outside the kernel) and records, at
    frontier-WORD granularity, which 32-source groups of the phase's gathered
    block the tile reads: coverage bit ``j`` is set iff some valid edge's
    gathered src index lands in frontier word ``j`` (``j = src_core * Ws +
    (src mod sub_size) // 32`` with ``Ws = ceil(sub_size / 32)`` — the layout
    contract shared with ``core.frontier_words``). Returns (..., Wc) uint32
    with ``Wc = ceil(p * Ws / 32)``: 32x smaller than per-source bitmaps, and
    conservative only — a tile whose coverage misses every live frontier word
    provably reads no changed source. All-invalid (padding) tiles get
    all-zero coverage, so they stay dead under any frontier.
    """
    word = np.asarray(word)
    ws = -(-sub_size // 32)
    wc = -(-(p * ws) // 32)
    if src_bits == 16:
        valid = word < 0
        src = (word.view(np.uint32) & np.uint32(0xFFFF)).astype(np.int64)
    else:
        valid = np.asarray(word_hi) < 0
        src = word.view(np.uint32).astype(np.int64)
    # gathered index -> frontier-word slot in the phase's gathered block
    widx = (src // sub_size) * ws + (src % sub_size) // 32
    lead = word.shape[:-2]  # the last two axes are one tile's slots
    cov = np.zeros(lead + (wc,), dtype=np.uint32)
    flat = cov.reshape(-1, wc)
    tile_of_slot = np.repeat(
        np.arange(flat.shape[0]), word.shape[-2] * word.shape[-1]
    )
    keep = valid.reshape(-1)
    ti, wsel = tile_of_slot[keep], widx.reshape(-1)[keep]
    np.bitwise_or.at(
        flat,
        (ti, wsel // 32),
        np.left_shift(np.uint32(1), (wsel % 32).astype(np.uint32)),
    )
    return cov


@dataclasses.dataclass(frozen=True)
class PushTileLayout:
    """One bucket's CSC-style push (scatter) tiles, binned by SOURCE block.

    The pull layout bins edges by destination row block so the kernel's
    accumulator is a pure function of the grid; the push layout bins the SAME
    edge set by source block ``b = gidx // block_sources`` so a NARROW
    frontier maps to few tiles: every out-edge of the 32-aligned source group
    ``[b * bs, (b+1) * bs)`` lives in block b's tiles, and a frontier that
    touches no source of a block never streams it. ``dst`` carries the FULL
    local destination index in [0, num_rows) — the scatter kernel's output is
    the whole per-core label row, so there is no row-block offset to strip.
    """

    src: np.ndarray  # (B, Tp, Eb) int32 gathered-block offsets
    dst: np.ndarray  # (B, Tp, Eb) int32 FULL local dst in [0, num_rows)
    valid: np.ndarray  # (B, Tp, Eb) bool
    weights: np.ndarray | None  # (B, Tp, Eb) f32
    tile_counts: np.ndarray  # (B,) int32 real edge tiles per source block
    block_sources: int
    num_rows: int


def prepare_push_tiles(
    src_gidx: np.ndarray,  # (E,) int32 gathered-block offsets
    dst_lidx: np.ndarray,  # (E,) int32 local dst in [0, num_rows)
    valid: np.ndarray,  # (E,) bool
    *,
    gathered_size: int,
    block_sources: int,
    num_rows: int,
    eb: int,
    weights: np.ndarray | None = None,
) -> PushTileLayout:
    """Bin one (core, phase) bucket's edges by source block for the push
    (scatter) stream. ``block_sources`` must be a multiple of 32 so every
    block covers whole frontier words and the coverage-word activity test
    (``tile_coverage_words`` on the push stream) is exact at block
    granularity. Edges inside a block are ordered (src, dst) — the order is
    irrelevant for the min/or reduces the push path admits (associative,
    commutative, idempotent), but a deterministic layout keeps partitions
    reproducible."""
    assert block_sources % 32 == 0, block_sources
    keep = np.asarray(valid)
    src = np.asarray(src_gidx)[keep].astype(np.int64)
    dst = np.asarray(dst_lidx)[keep].astype(np.int64)
    w = np.asarray(weights)[keep] if weights is not None else None
    n_blocks = max(1, -(-gathered_size // block_sources))
    blk = src // block_sources
    order = np.lexsort((dst, src))  # blk is src // bs, so this is block-major
    src, dst, blk = src[order], dst[order], blk[order]
    if w is not None:
        w = w[order]
    counts = np.bincount(blk, minlength=n_blocks)
    t_tiles = max(1, int(-(-counts.max() // eb))) if counts.size else 1
    src_t = np.zeros((n_blocks, t_tiles, eb), dtype=np.int32)
    dst_t = np.zeros((n_blocks, t_tiles, eb), dtype=np.int32)
    val_t = np.zeros((n_blocks, t_tiles, eb), dtype=bool)
    w_t = (
        np.zeros((n_blocks, t_tiles, eb), dtype=np.float32)
        if w is not None
        else None
    )
    starts = np.zeros(n_blocks + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for b in range(n_blocks):
        s, e = int(starts[b]), int(starts[b + 1])
        n = e - s
        src_t[b].reshape(-1)[:n] = src[s:e]
        dst_t[b].reshape(-1)[:n] = dst[s:e]
        val_t[b].reshape(-1)[:n] = True
        if w_t is not None:
            w_t[b].reshape(-1)[:n] = w[s:e]
    return PushTileLayout(
        src=src_t, dst=dst_t, valid=val_t, weights=w_t,
        tile_counts=(-(-counts // eb)).astype(np.int32),
        block_sources=block_sources, num_rows=num_rows,
    )


def stack_push_tiles(
    layouts: list[PushTileLayout], *, src_bits: int
) -> tuple[np.ndarray, np.ndarray | None, np.ndarray, np.ndarray | None]:
    """Pack + stack per-bucket push layouts to one uniform (n, B, Tp, *tile)
    compressed scatter stream: ``(word, word_hi, counts, weights)``. Every
    bucket shares B (the gathered block size is phase-invariant); Tp is
    padded to the max, and ``counts`` tells the kernel which tiles are real —
    the exact mirror of ``stack_packed_tiles`` for the pull stream. The
    packed ``dstb`` field holds the FULL local destination row, so the
    16-bit regime additionally requires ``num_rows <= 2^15`` (the caller
    picks ``src_bits`` via ``choose_src_bits(gathered_size, num_rows)``)."""
    n = len(layouts)
    tile = tile_shape(layouts[0].src.shape[2])
    b_max = max(t.src.shape[0] for t in layouts)
    t_max = max(t.src.shape[1] for t in layouts)
    word = np.zeros((n, b_max, t_max, *tile), np.int32)
    word_hi = np.zeros((n, b_max, t_max, *tile), np.int32) if src_bits == 32 else None
    counts = np.zeros((n, b_max), np.int32)
    any_w = any(t.weights is not None for t in layouts)
    weights = np.zeros((n, b_max, t_max, *tile), np.float32) if any_w else None
    for i, t in enumerate(layouts):
        bb, tt = t.src.shape[:2]
        w0, w1 = pack_edge_words(t.src, t.dst, t.valid, src_bits=src_bits)
        word[i, :bb, :tt] = w0.reshape(bb, tt, *tile)
        if word_hi is not None:
            word_hi[i, :bb, :tt] = w1.reshape(bb, tt, *tile)
        counts[i, :bb] = t.tile_counts
        if weights is not None and t.weights is not None:
            weights[i, :bb, :tt] = t.weights.reshape(bb, tt, *tile)
    return word, word_hi, counts, weights


@dataclasses.dataclass(frozen=True)
class TileLayout:
    """(R, T, Eb) row-block binned edges; padding slots have valid=False.

    Natural row ``v`` owns kernel-output position ``v``: blocks ``0 ..
    num_rows / vb - 1`` are the natural blocks. A natural block that holds
    more edges than the bucket's capacity C moves its excess, heaviest rows
    first, into appended blocks (R then exceeds ``num_rows / vb``); each
    moved row keeps its first edges in place and sends the rest as pieces of
    at most C edges. ``fold_rows`` / ``fold_pos`` name those rows and their
    pieces' packed positions, and ``combine_split_rows`` folds the pieces'
    partials back into the rows (level 2). Both are empty when nothing moved.
    """

    src: np.ndarray  # (R, T, Eb) int32
    dstb: np.ndarray  # (R, T, Eb) int32 in [0, vb)
    valid: np.ndarray  # (R, T, Eb) bool
    weights: np.ndarray | None  # (R, T, Eb) f32
    vb: int
    num_rows: int  # NATURAL rows (fold output size); packed rows = R * vb
    # slot -> index into the ORIGINAL (pre-binning) edge arrays, 0 on padding.
    # Lets runtime-traced per-edge values (e.g. GAT scores) be laid out into
    # tile order with one static gather.
    gather_idx: np.ndarray | None = None  # (R, T, Eb) int64
    # real edge tiles per row block: ceil(real_edges[r] / Eb). Tiles with
    # t >= tile_counts[r] are all-padding; the fused kernel skips them.
    tile_counts: np.ndarray | None = None  # (R,) int32
    # natural rows that moved a piece (ascending) and each one's pieces'
    # packed positions, FOLD_PAD-padded: (N,) and (N, S), N = S = 0 when no
    # block overflowed
    fold_rows: np.ndarray | None = None  # (N,) int32
    fold_pos: np.ndarray | None = None  # (N, S) int32
    # T of the identity placement (the natural blocks' own loads) — the
    # denominator of the t_max_reduction metric
    t_tiles_unsplit: int = 0

    @property
    def num_split_rows(self) -> int:
        """Natural rows that moved a piece out of their block."""
        return 0 if self.fold_rows is None else int(self.fold_rows.shape[0])

    @property
    def tile_padding_ratio(self) -> float:
        total = self.valid.size
        return 1.0 - float(self.valid.sum()) / max(total, 1)


def _pack_pieces(sizes: np.ndarray, cap: int, vb: int) -> np.ndarray:
    """LPT over the moved pieces: heaviest first, each into the least-loaded
    appended block that still has room for it (load <= ``cap`` edges, at most
    ``vb`` rows), a new block when none has. Returns each piece's packed
    position relative to the first appended block."""
    pos = np.empty(sizes.shape[0], dtype=np.int64)
    load = np.zeros(sizes.shape[0], dtype=np.int64)
    slots = np.zeros(sizes.shape[0], dtype=np.int64)
    opened = 0
    for i in np.argsort(-sizes, kind="stable"):
        size = int(sizes[i])
        fit = (slots[:opened] < vb) & (load[:opened] + size <= cap)
        if fit.any():
            b = int(np.where(fit, load[:opened], cap + 1).argmin())
        else:
            b, opened = opened, opened + 1
        pos[i] = b * vb + slots[b]
        slots[b] += 1
        load[b] += size
    return pos


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Shape + row-placement decisions of one bucket's tile layout, computed
    from the per-row edge counts ALONE — no edge data needed.

    This is the single source of truth for everything about a bucket's layout
    that does not depend on which concrete edges fill the slots: the
    out-of-core streaming partitioner (``partition_2d_streaming``) calls
    ``plan_tiles`` during its counting pass to pre-size the stacked packed
    buffers before any edge is placed, and ``prepare_tiles`` consumes the
    same plan to place edges — so the two paths cannot disagree on shapes or
    placement. Moved row ``fold_rows[n]`` keeps its first ``count -
    fold_moved[n]`` edges at its own position and splits the rest into even
    pieces whose positions ``fold_pos[n]`` lists.
    """

    r_blocks: int  # natural blocks + appended overflow blocks
    t_tiles: int  # max real edge tiles over the row blocks
    t_tiles_unsplit: int  # T of the identity placement
    fold_rows: np.ndarray  # (N,) int32 natural rows that moved a piece
    fold_moved: np.ndarray  # (N,) int64 edges each of them moved
    fold_pos: np.ndarray  # (N, S) int32 packed piece positions, FOLD_PAD pad


def plan_tiles(
    row_counts: np.ndarray,  # (num_rows,) real edges per natural row
    *,
    num_rows: int,
    vb: int,
    eb: int,
    split_threshold: int | None = None,
) -> TilePlan:
    """Decide one bucket's tile-layout shape from row counts alone.

    ``split_threshold`` is the bucket's block capacity, rounded up to whole
    tiles: C edges. A natural block holding more than C gives up its excess,
    taken from its heaviest rows first (ties: lower row), so it keeps exactly
    C; the moved edges leave in pieces of at most C, which ``_pack_pieces``
    puts into appended blocks of at most C edges. A row above C always
    overflows, and no block holds more than C, so T <= C / Eb. A bucket
    whose blocks all fit moves nothing: identity placement, no fold.
    ``None`` is the identity placement whatever the loads."""
    assert num_rows % vb == 0, (num_rows, vb)
    r_base = num_rows // vb
    row_counts = np.asarray(row_counts, dtype=np.int64)
    loads = row_counts.reshape(r_base, vb).sum(axis=1)
    t_identity = max(1, int(-(-int(loads.max()) // eb))) if loads.size else 1
    cap = None
    if split_threshold is not None:
        cap = -(-max(int(split_threshold), 1) // eb) * eb
    over = np.zeros(0, np.int64) if cap is None else np.flatnonzero(loads > cap)
    if not over.size:
        return TilePlan(
            r_blocks=r_base, t_tiles=t_identity, t_tiles_unsplit=t_identity,
            fold_rows=np.zeros(0, np.int32), fold_moved=np.zeros(0, np.int64),
            fold_pos=np.zeros((0, 0), np.int32),
        )
    # each overfull block's rows, heaviest first (stable: lower row on ties);
    # a row gives up what the block still holds above C after the heavier
    # rows before it, at most its whole count
    cnt = row_counts.reshape(r_base, vb)[over]
    order = np.argsort(-cnt, axis=1, kind="stable")
    cnt = np.take_along_axis(cnt, order, axis=1)
    before = np.cumsum(cnt, axis=1) - cnt
    take = np.clip((loads[over] - cap)[:, None] - before, 0, cnt)
    moved_rows = (over[:, None] * vb + order)[take > 0]
    moved = take[take > 0]
    o = np.argsort(moved_rows, kind="stable")
    moved_rows, moved = moved_rows[o], moved[o]
    # k even pieces per moved row: piece c of a row that moves t edges holds
    # the edges q with q * k // t == c, ceil((c+1)*t/k) - ceil(c*t/k) of them
    k = -(-moved // cap)
    owner = np.repeat(np.arange(moved_rows.shape[0]), k)
    c = np.arange(owner.shape[0]) - np.repeat(np.cumsum(k) - k, k)
    t, kk = moved[owner], k[owner]
    sizes = (-(-((c + 1) * t) // kk)) - (-(-(c * t) // kk))
    pos = _pack_pieces(sizes, cap, vb) + r_base * vb
    fold_pos = np.full((moved_rows.shape[0], int(k.max())), FOLD_PAD, np.int32)
    fold_pos[owner, c] = pos
    r_blocks = int(pos.max()) // vb + 1
    app_loads = np.bincount(pos // vb - r_base, weights=sizes.astype(np.float64),
                            minlength=r_blocks - r_base)
    top = max(int(np.minimum(loads, cap).max()), int(app_loads.max()))
    return TilePlan(
        r_blocks=r_blocks, t_tiles=max(1, -(-top // eb)),
        t_tiles_unsplit=t_identity, fold_rows=moved_rows.astype(np.int32),
        fold_moved=moved, fold_pos=fold_pos,
    )


def plan_push_tiles(
    src_counts: np.ndarray,  # (gathered_size,) real edges per gathered source
    *,
    gathered_size: int,
    block_sources: int,
    eb: int,
) -> tuple[int, int]:
    """Push-stream shape from per-source counts alone: ``(B, Tp)`` matching
    what ``prepare_push_tiles`` will produce for the same bucket."""
    n_blocks = max(1, -(-gathered_size // block_sources))
    src_counts = np.asarray(src_counts, dtype=np.int64)
    pad = n_blocks * block_sources - src_counts.shape[0]
    if pad:
        src_counts = np.concatenate([src_counts, np.zeros(pad, np.int64)])
    counts = src_counts.reshape(n_blocks, block_sources).sum(axis=1)
    t_tiles = max(1, int(-(-int(counts.max()) // eb))) if counts.size else 1
    return n_blocks, t_tiles


def prepare_tiles(
    src_gidx: np.ndarray,  # (E,) int32
    dst_lidx: np.ndarray,  # (E,) int32, sorted ascending
    valid: np.ndarray,  # (E,) bool
    num_rows: int,
    vb: int,
    eb: int,
    weights: np.ndarray | None = None,
    *,
    split_threshold: int | None = None,
    plan: TilePlan | None = None,
) -> TileLayout:
    """Bin one (dst-sorted) edge bucket into (R, T, Eb) row-block tiles.

    ``split_threshold`` is the block capacity of ``plan_tiles``: every
    natural row stays at its own output position, and a block above the
    capacity moves its excess into appended blocks (R grows past ``num_rows
    / vb``); the layout's ``fold_rows`` / ``fold_pos`` then tell the caller
    what the second-level combine (``combine_split_rows``) folds. A moved
    row keeps its first edges (in bucket order) in place. When no block is
    above the capacity, or ``split_threshold`` is None, the output is the
    identity placement.

    ``plan``: a ``TilePlan`` previously computed by ``plan_tiles`` for THIS
    bucket's row counts under the same (vb, eb, split_threshold) — skips the
    re-plan. The caller owns the consistency; the t_tiles assertion below
    catches a mismatched plan.
    """
    assert num_rows % vb == 0, (num_rows, vb)
    keep = np.asarray(valid)
    orig_idx = np.nonzero(keep)[0]
    src_r = np.asarray(src_gidx)[keep]
    dst_r = np.asarray(dst_lidx)[keep]
    w_r = weights[keep] if weights is not None else None
    row_counts = np.bincount(dst_r, minlength=num_rows)
    if plan is None:
        plan = plan_tiles(
            row_counts, num_rows=num_rows, vb=vb, eb=eb,
            split_threshold=split_threshold,
        )
    r_blocks = plan.r_blocks
    pdst = dst_r.astype(np.int64)
    if plan.fold_rows.size:
        # edge j of moved row v (bucket order) stays at v while j < count -
        # moved; the rest split into the plan's even chunks: the q-th moved
        # edge of k pieces goes to piece q * k // moved
        which = np.full(num_rows, -1, dtype=np.int64)
        which[plan.fold_rows] = np.arange(plan.fold_rows.shape[0])
        row_starts = np.cumsum(row_counts) - row_counts
        n = which[dst_r]
        q = (np.arange(dst_r.shape[0], dtype=np.int64) - row_starts[dst_r]
             - (row_counts[dst_r] - np.where(n >= 0, plan.fold_moved[n], 0)))
        mv = (n >= 0) & (q >= 0)
        n, q = n[mv], q[mv]
        k = (plan.fold_pos != FOLD_PAD).sum(axis=1)
        pdst[mv] = plan.fold_pos[n, q * k[n] // plan.fold_moved[n]]
        # regroup by block, keeping bucket order inside each block (stable)
        order = np.argsort(pdst // vb, kind="stable")
        src_r, pdst, orig_idx = src_r[order], pdst[order], orig_idx[order]
        if w_r is not None:
            w_r = w_r[order]
    block = pdst // vb
    counts = np.bincount(block, minlength=r_blocks)
    t_tiles = max(1, int(-(-counts.max() // eb))) if counts.size else 1
    assert t_tiles == plan.t_tiles, (t_tiles, plan.t_tiles)
    src_t = np.zeros((r_blocks, t_tiles, eb), dtype=np.int32)
    dst_t = np.zeros((r_blocks, t_tiles, eb), dtype=np.int32)
    val_t = np.zeros((r_blocks, t_tiles, eb), dtype=bool)
    gat_t = np.zeros((r_blocks, t_tiles, eb), dtype=np.int64)
    w_t = np.zeros((r_blocks, t_tiles, eb), dtype=np.float32) if w_r is not None else None
    starts = np.zeros(r_blocks + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for r in range(r_blocks):
        s, e = int(starts[r]), int(starts[r + 1])
        n = e - s
        src_t[r].reshape(-1)[:n] = src_r[s:e]
        dst_t[r].reshape(-1)[:n] = pdst[s:e] - r * vb
        val_t[r].reshape(-1)[:n] = True
        gat_t[r].reshape(-1)[:n] = orig_idx[s:e]
        if w_t is not None:
            w_t[r].reshape(-1)[:n] = w_r[s:e]
    return TileLayout(
        src=src_t, dstb=dst_t, valid=val_t, weights=w_t, vb=vb,
        num_rows=num_rows, gather_idx=gat_t,
        tile_counts=(-(-counts // eb)).astype(np.int32),
        fold_rows=plan.fold_rows, fold_pos=plan.fold_pos,
        t_tiles_unsplit=plan.t_tiles_unsplit,
    )


def stack_fold_arrays(
    rows: list[np.ndarray], pos: list[np.ndarray], lead: tuple[int, ...]
) -> tuple[np.ndarray | None, np.ndarray | None]:
    """Stack per-bucket ``fold_rows`` (N_b,) / ``fold_pos`` (N_b, S_b) to
    uniform (*lead, N) / (*lead, N, S) arrays, FOLD_PAD-padded, N and S the
    maxima over the buckets (``lead`` orders them: (p, l) for a partition).
    ``(None, None)`` when no bucket moved a row: no fold at all."""
    n_max = max(r.shape[0] for r in rows)
    if n_max == 0:
        return None, None
    s_max = max(p.shape[1] for p in pos)
    fold_rows = np.full((len(rows), n_max), FOLD_PAD, np.int32)
    fold_pos = np.full((len(rows), n_max, s_max), FOLD_PAD, np.int32)
    for i, (r, p) in enumerate(zip(rows, pos)):
        fold_rows[i, : r.shape[0]] = r
        fold_pos[i, : p.shape[0], : p.shape[1]] = p
    return fold_rows.reshape(*lead, n_max), fold_pos.reshape(*lead, n_max, s_max)


@jax.named_scope("split_fold")
def combine_split_rows(
    reduced: jnp.ndarray,  # (n, R*vb[, K]) level-1 kernel output, packed rows
    fold_rows: jnp.ndarray | None,  # (n, N) moved natural rows, FOLD_PAD pad
    fold_pos: jnp.ndarray | None,  # (n, N, S) their pieces' packed positions
    *,
    num_rows: int,  # natural rows per channel (the natural blocks' rows)
    kind: str,  # 'min' | 'sum' | 'or' — the problem's reduce UDF
    identity: float,  # the SAME problem's identity (INF for min, 0 for sum/or)
) -> jnp.ndarray:
    """Level-2 reduce: natural rows out of the packed kernel output.

    The natural blocks are the output's first ``num_rows`` rows, taken as a
    slice. Only the N moved rows gather anything: their S pieces' partials,
    folded with the problem's own reduce op — a FOLD_PAD position reads the
    problem's ``identity``, so a min problem never sees a stray 0 and a sum
    problem never counts a partial twice — and combined into the row's
    in-place partial; FOLD_PAD rows are dropped. Min problems stay
    bit-identical to the oracle (min over partial mins is the total min).
    Lane-batched problems (docs/tile_layout.md §8) carry a trailing lane
    axis, which every index broadcasts over. Returns (n, num_rows[, K]).
    """
    out = reduced[:, :num_rows]
    if fold_rows is None:
        return out
    n, rows = fold_rows.shape
    s = fold_pos.shape[-1]
    ident = np.asarray(identity).astype(reduced.dtype).item()
    lanes = reduced.shape[2:]
    idx = fold_pos.reshape(n, rows * s, *([1] * len(lanes)))
    vals = jnp.take_along_axis(reduced, idx, axis=1, mode="fill", fill_value=ident)
    vals = vals.reshape(n, rows, s, *lanes)
    at = out.at[jnp.arange(n)[:, None], fold_rows]
    if kind == "min":
        return at.min(jnp.min(vals, axis=2), mode="drop")
    if kind == "sum":
        return at.add(jnp.sum(vals, axis=2), mode="drop")
    folded = at.get(mode="fill", fill_value=ident)
    for j in range(s):  # S is small and static: an unrolled word-OR fold
        folded = folded | vals[:, :, j]
    return at.set(folded, mode="drop")


def gather_reduce(
    payload: jnp.ndarray,
    tiles: TileLayout,
    *,
    kind: str = "min",
    edge_op: str = "none",
    identity: float = 0.0,
    interpret: bool | None = None,
    use_reference: bool = False,
) -> jnp.ndarray:
    """Run the accumulator over one (core, phase) bucket."""
    # the kernel reduces PACKED rows — natural blocks plus any appended
    # overflow blocks — and level 2 folds the moved pieces back
    packed_rows = tiles.src.shape[0] * tiles.vb
    if use_reference:
        r_blocks = tiles.src.shape[0]
        block_base = np.arange(r_blocks, dtype=np.int32)[:, None, None] * tiles.vb
        ref_w = None
        if edge_op == "add":
            # the kernel treats missing weights as unit weights; the reference
            # skips the add when weights is None, so make units explicit
            ref_w = (
                jnp.asarray(tiles.weights).reshape(-1)
                if tiles.weights is not None
                else jnp.ones(tiles.src.size, jnp.float32)
            )
        out = gather_reduce_reference(
            payload,
            jnp.asarray(tiles.src).reshape(-1),
            jnp.asarray(tiles.dstb + block_base).reshape(-1),
            jnp.asarray(tiles.valid).reshape(-1),
            packed_rows,
            kind=kind,
            identity=identity,
            weights=ref_w,
        )
    else:
        out = gather_reduce_pallas(
            payload,
            jnp.asarray(tiles.src),
            jnp.asarray(tiles.dstb),
            jnp.asarray(tiles.valid),
            jnp.asarray(tiles.weights) if tiles.weights is not None else None,
            num_rows=packed_rows,
            vb=tiles.vb,
            kind=kind,
            edge_op=edge_op,
            identity=identity,
            interpret=interpret,
        )
    rows, pos = stack_fold_arrays([tiles.fold_rows], [tiles.fold_pos], (1,))
    return combine_split_rows(
        out[None], None if rows is None else jnp.asarray(rows),
        None if pos is None else jnp.asarray(pos),
        num_rows=tiles.num_rows, kind=kind, identity=identity,
    )[0]


def segment_reduce_rows(
    contrib: jnp.ndarray,  # (p, E) pre-mapped contributions (identity-padded)
    dst: jnp.ndarray,  # (p, E) sorted local rows
    *,
    num_rows: int,
    kind: str,
    identity: float,
    interpret: bool = True,
) -> jnp.ndarray:
    """Reduce-only helper for already-materialized contributions (traced dst
    => no host binning). The engine no longer routes through this — its XLA
    oracle calls segment ops directly and its primary path is the fused
    ``gather_reduce_cores_pallas`` — kept as public API for model code."""
    def seg(c, d):
        if kind == "min":
            return jax.ops.segment_min(c, d, num_segments=num_rows, indices_are_sorted=True)
        return jax.ops.segment_sum(c, d, num_segments=num_rows, indices_are_sorted=True)

    return jax.vmap(seg)(contrib, dst)
