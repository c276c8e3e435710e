"""Pallas TPU kernel: the GraphScale graph-core accumulator.

FPGA -> TPU translation of paper Fig. 4/5: the e-edges/cycle pipeline with a
Ladner-Fischer prefix-adder + sequential stage becomes, per (row-block r,
edge-tile t) grid cell, a loop over the tile's edge slots that the scalar
core drives:

  1. *scratch-pad read*: the slot's packed word is read from SMEM and decoded
     with shifts and masks; its source offset names one row of the phase's
     gathered label block, resident in VMEM (the label scratch pad), which is
     loaded with a dynamic sublane slice and rotated so the source's lane
     lines up with the destination's;
  2. *map UDF*: optional saturating weight add (SSSP);
  3. *reduce UDF*: the value is merged into its destination row of the
     accumulator with the problem's reduce (min / sum / or), one
     read-modify-write of one (1, 128) or (1, L) vector row;
  4. *buffered writer*: the accumulator lives in the revisited output VMEM
     block across the row-block's tiles and is written to HBM once.

Edges are pre-binned by destination row block (host-side, partition time), so
the output BlockSpec is a pure function of the grid — the same trick as the
paper's two-dimensional partitioning, one level down.

Layouts the TPU compiler (Mosaic) accepts
-----------------------------------------
* An edge tile of ``Eb`` slots is stored as a ``tile_shape(Eb) = (Eb // c,
  c)`` block with ``c = min(Eb, 128)``: the partitioner lays the stream out as
  (..., T, Eb // c, c), so a tile's block equals the array's last two dims
  and is DMA'd whole into SMEM by the pipeline.
* A laneless gathered block (G,) is presented as (ceil(G / 128), 128) rows;
  slot ``src`` lives at row ``src >> 7``, lane ``src & 127``. The row is
  rotated by ``(dst - src) & 127`` lanes and merged at lane ``dst & 127``
  with a select — there is no in-kernel 1-D gather, which Mosaic does not
  lower. A lane payload (G, L) gathers whole (1, L) rows.
* uint32 labels (BFS, WCC, packed reach words) are reduced as int32: for
  ``min`` through the order-preserving map ``x ^ 0x80000000`` at the kernel
  boundary (Mosaic has no unsigned min), for ``or`` as a plain bitcast. The
  result is bit-identical to the unsigned reduce.

Compressed edge stream (paper §III: "compressed graph representation")
----------------------------------------------------------------------
Each slot is ONE bit-packed int32 word, decoded in scalar registers:

  16-bit regime (``src_bits=16``, when p * sub_size <= 2^16 and vb <= 2^15):
      word = valid<<31 | dstb<<16 | src                       4 B/edge
      unpack: src = word & 0xFFFF; dstb = (word >> 16) & 0x7FFF;
              valid = word < 0   (bit 31 is the int32 sign bit)
  32-bit fallback (``src_bits=32``):
      word = src;  word_hi = valid<<31 | dstb                 8 B/edge

On top of the packed words, a scalar-prefetched per-(core, row-block) tile
count (``counts``, SMEM-resident before the kernel body runs) lets the kernel
skip all-padding tiles entirely via ``@pl.when(t < counts[c, r])``: skipped
tiles are never decoded or reduced, and their blocks are never fetched.

``interpret`` defaults to the backend: compiled on a TPU, the Pallas
interpreter elsewhere (CPU tests). Nothing falls back silently.

Three entry points share the tile body:

  * ``gather_reduce_cores_pallas`` — the engine's fused pull hot path: a
    leading core grid dimension runs ALL ``p`` graph cores of one phase in a
    single ``pallas_call`` over grid (p, R, T), reading the compressed word
    stream. Per-edge state never exists outside scalar registers and one
    accumulator row, so neither a (p, E_pad) contributions array nor an
    unpacked per-edge index array is ever materialized in HBM.
  * ``scatter_reduce_cores_pallas`` — the push (scatter) mirror over the
    source-binned stream.
  * ``gather_reduce_pallas`` — one (core, phase) bucket given as UNCOMPRESSED
    (src, dstb, valid) tile arrays; packs them and runs the cores kernel. The
    uncompressed correctness reference for tests and model code.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "tile_shape",
    "gather_reduce_pallas",
    "gather_reduce_cores_pallas",
    "scatter_reduce_cores_pallas",
]

_LANES = 128
_SIGN = -(1 << 31)  # int32 bit pattern 0x80000000
# scoped-VMEM ceiling for one kernel: v5e has 128 MiB of VMEM per core.
_VMEM_CAP = 100 << 20
_UNROLL = 16  # edge slots per straight-line block of the tile loop


def tile_shape(eb: int) -> tuple[int, int]:
    """(rows, lanes) block of one ``eb``-slot edge tile in the packed stream.

    A tile is one (Eb // c, c) block, c = min(Eb, 128): its block equals the
    array's last two dims, which is what Mosaic requires of a block that is
    not (8, 128)-aligned. Eb above 128 must be a multiple of 128."""
    cols = min(int(eb), _LANES)
    if eb < 1 or eb % cols:
        raise ValueError(f"edge tile width must be < 128 or a multiple of 128, got {eb}")
    return eb // cols, cols


def _interpret(interpret):
    return jax.default_backend() != "tpu" if interpret is None else bool(interpret)


def _kernel_domain(dtype, kind: str, identity: float):
    """(kernel dtype, kernel identity, to_kernel, from_kernel) for a payload
    dtype: uint32 is reduced as int32 (sign-flipped for min), floats as is."""
    if jnp.dtype(dtype) != jnp.uint32:
        return jnp.dtype(dtype), identity, (lambda x: x), (lambda y: y)
    flip = _SIGN if kind == "min" else 0
    ident = int(np.uint32(int(identity) & 0xFFFFFFFF).view(np.int32)) ^ flip

    def to_k(x):
        return jax.lax.bitcast_convert_type(x, jnp.int32) ^ jnp.int32(flip)

    def from_k(y):
        return jax.lax.bitcast_convert_type(y ^ jnp.int32(flip), jnp.uint32)

    return jnp.dtype(jnp.int32), ident, to_k, from_k


def _reduce(kind: str, a, b):
    if kind == "min":
        return jnp.minimum(a, b)
    if kind == "sum":
        return a + b
    return a | b


def _edge_loop(word_ref, hi_ref, w_ref, pay_ref, acc_ref, *, src_bits, kind,
               edge_op, identity, lanes):
    """The tile body: for every slot of the (rows, c) tile, decode the packed
    word, read the source's payload row from the scratch pad, apply the map
    UDF and merge into the destination row of ``acc_ref``.

    Branch-free: a padding slot decodes to in-range offsets and its merge is
    masked off, so ``_UNROLL`` consecutive slots form one straight-line block
    the compiler can interleave (on a v5e, 16 slots per block run about six
    times faster than one slot per loop step with a branch)."""
    rows, cols = word_ref.shape
    unroll = math.gcd(cols, _UNROLL)
    dt = acc_ref.dtype
    ident = jnp.asarray(identity, dt)
    lane = None if lanes else jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def slot(i, j):
        wd = word_ref[i, j]
        if src_bits == 16:
            src, dst, valid = wd & 0xFFFF, (wd >> 16) & 0x7FFF, wd < 0
        else:
            hi = hi_ref[i, j]
            src, dst, valid = wd, hi & 0x7FFFFFFF, hi < 0
        if lanes:
            v = pay_ref[pl.ds(src, 1), :]  # (1, L) scratch-pad row
            row, keep = dst, valid
        else:  # rotate the source lane onto the destination lane
            v = pltpu.roll(pay_ref[pl.ds(src >> 7, 1), :], (dst - src) & 127, 1)
            row, keep = dst >> 7, (lane == (dst & 127)) & valid
        if edge_op == "add":  # saturating min-plus map; no weights = unit
            step = jnp.asarray(w_ref[i, j] if w_ref is not None else 1).astype(dt)
            v = jnp.where(v >= ident, ident, v + step)
        a = acc_ref[pl.ds(row, 1), :]
        acc_ref[pl.ds(row, 1), :] = jnp.where(keep, _reduce(kind, a, v), a)

    def block(i, b, carry):
        for u in range(unroll):
            slot(i, b * unroll + u)
        return carry

    jax.lax.fori_loop(
        0, rows,
        lambda i, c: jax.lax.fori_loop(0, cols // unroll, functools.partial(block, i), c),
        0,
    )


def _vmem_bytes(shape, dtype) -> int:
    """VMEM footprint of one block: the last two dims pad to (8, 128)."""
    *lead, r, c = shape
    return int(np.prod(lead, dtype=np.int64)) * (-(-r // 8) * 8) * (
        -(-c // _LANES) * _LANES
    ) * jnp.dtype(dtype).itemsize


def _fused_call(
    payload, word, counts, word_hi, weights, fetch, *, num_rows, vb, src_bits,
    kind, edge_op, identity, interpret, push,
):
    """Shared launch of the pull (``push=False``: grid (p, R, T), output row
    block r) and push (grid (p, B, Tp), whole-row output) kernels."""
    p, n_blocks, t_tiles = word.shape[:3]
    assert (word_hi is not None) == (src_bits == 32), (src_bits, word_hi is None)
    if fetch is not None:
        assert fetch.shape == (p, n_blocks, t_tiles), fetch.shape
    else:
        assert counts.shape == (p, n_blocks), (counts.shape, (p, n_blocks))
    tile = word.shape[3:]
    lane_dim = payload.shape[1] if payload.ndim == 2 else None
    dt, ident, to_k, from_k = _kernel_domain(payload.dtype, kind, identity)
    g = payload.shape[0]
    pay = to_k(payload)
    if lane_dim is None:  # (G,) -> (ceil(G/128), 128) rows of the scratch pad
        g_rows = -(-g // _LANES)
        pay = jnp.pad(pay, (0, g_rows * _LANES - g)).reshape(g_rows, _LANES)
    # accumulator block: pull = one row block (vb rows), push = the whole row
    acc_rows = num_rows if push else vb
    acc_shape = (
        (-(-acc_rows // _LANES), _LANES) if lane_dim is None else (acc_rows, lane_dim)
    )
    has_hi, has_w, has_fetch = word_hi is not None, weights is not None, fetch is not None

    def kern(cnt_ref, *refs):
        refs = list(refs)
        word_ref = refs.pop(0)
        hi_ref = refs.pop(0) if has_hi else None
        w_ref = refs.pop(0) if has_w else None
        pay_ref, out_ref = refs
        c, r, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        first = (r == 0) & (t == 0) if push else t == 0

        @pl.when(first)
        def _init():  # buffered-writer accumulator starts at the identity
            out_ref[...] = jnp.full(out_ref.shape, ident, dt)

        # variable-T early-out (static: skip padding tiles) or frontier
        # early-out (dynamic: also skip real tiles with no active source)
        run = cnt_ref[c, r, t] == t if has_fetch else t < cnt_ref[c, r]

        @pl.when(run)
        def _work():
            _edge_loop(
                word_ref, hi_ref, w_ref, pay_ref, out_ref, src_bits=src_bits,
                kind=kind, edge_op=edge_op, identity=ident,
                lanes=lane_dim is not None,
            )

    # Block-sparse fetch elision: @pl.when only predicates COMPUTE — the
    # pipeline still DMAs whatever block the index map names. Clamping the
    # tile index at the last real tile makes every skipped grid step revisit
    # the previous block, which the pipeline recognizes and does not re-fetch,
    # so padding tiles cost no HBM traffic. The dynamic fetch map generalizes
    # the clamp: skipped steps re-name the LAST ACTIVE block (cummax of
    # active tile indices), preserving the no-refetch property under
    # arbitrary per-iteration skip patterns.
    def edge_idx(c, r, t, cnt):
        if has_fetch:
            return (c, r, jnp.maximum(cnt[c, r, t], 0), 0, 0)
        return (c, r, jnp.minimum(t, jnp.maximum(cnt[c, r] - 1, 0)), 0, 0)

    edge_block = pl.BlockSpec(
        (None, None, None) + tile, edge_idx, memory_space=pltpu.SMEM
    )
    pay_spec = pl.BlockSpec(pay.shape, lambda c, r, t, cnt: (0, 0))
    if push:
        out_spec = pl.BlockSpec((None,) + acc_shape, lambda c, r, t, cnt: (c, 0, 0))
        out_shape = (p,) + acc_shape
    else:
        out_spec = pl.BlockSpec(
            (None, None) + acc_shape, lambda c, r, t, cnt: (c, r, 0, 0)
        )
        out_shape = (p, n_blocks) + acc_shape
    in_specs = [edge_block] * (1 + has_hi + has_w) + [pay_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(p, n_blocks, t_tiles),
        in_specs=in_specs,
        out_specs=out_spec,
    )
    args = (word,) + ((word_hi,) if has_hi else ()) + ((weights,) if has_w else ())
    # double-buffered scratch pad + accumulator, plus headroom for the rest
    vmem = 2 * (_vmem_bytes(pay.shape, dt) + _vmem_bytes(acc_shape, dt)) + (4 << 20)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, dt),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=min(max(vmem, 16 << 20), _VMEM_CAP),
        ),
    )((fetch if has_fetch else counts).astype(jnp.int32), *args, pay)
    if lane_dim is None:  # (…, rows/128, 128) -> (…, rows)
        out = out.reshape(out.shape[: out.ndim - 2] + (-1,))[..., :acc_rows]
    if not push:  # (p, R, vb[, L]) -> (p, R * vb[, L])
        out = out.reshape((p, n_blocks * vb) + out.shape[3:])
    return from_k(out)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_rows", "vb", "src_bits", "kind", "edge_op", "identity", "interpret"
    ),
)
def gather_reduce_cores_pallas(
    payload: jnp.ndarray,  # (G[, L]) phase-gathered crossbar block, shared by cores
    word: jnp.ndarray,  # (p, R, T, *tile_shape(Eb)) int32 packed edge words
    counts: jnp.ndarray,  # (p, R) int32 real edge tiles per (core, row block)
    word_hi: jnp.ndarray | None = None,  # like word, src_bits=32 only
    weights: jnp.ndarray | None = None,  # like word, f32 (edge_op == 'add')
    fetch: jnp.ndarray | None = None,  # (p, R, T) int32 dynamic fetch map
    *,
    num_rows: int,  # rows per core (= R * vb packed rows)
    vb: int,
    src_bits: int = 16,
    kind: str = "min",
    edge_op: str = "none",
    identity: float = 0.0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """All-cores accumulator over the COMPRESSED edge stream: grid (p, R, T)
    -> (p, num_rows[, L]) reductions.

    Each edge slot arrives as one bit-packed word (two in the 32-bit fallback;
    see module docstring) and is decoded in scalar registers. ``counts`` is
    scalar-prefetched (SMEM before the body runs), and tiles with ``t >=
    counts[c, r]`` — pure padding — are skipped without decoding anything.

    Core ``c``'s output rows [r*vb, (r+1)*vb) are revisited across the T edge
    tiles of row block r (buffered writer) and written to HBM once; VMEM holds
    the scratch pad and one row block's accumulator, SMEM one edge tile per
    operand.

    Two-level reduce: output rows are PACKED rows — the natural rows in
    place, then any blocks appended at partition time for the pieces of rows
    whose block was over its capacity, so no row block carries more than
    the capacity and T_max stays near the mean block load. The kernel is
    oblivious: it reduces each packed row independently (level 1; rows it
    never touches keep the ``identity`` written at t == 0). The engine
    folds the pieces into their natural rows afterwards with the problem's
    reduce op (level 2, ``combine_split_rows``).

    Frontier-aware dynamic skipping: passing ``fetch`` (a traced (p, R, T)
    int32 map, ``core.frontier_words.active_fetch_map`` of this iteration's
    active-tile mask) REPLACES ``counts`` as the single scalar-prefetched
    operand. ``fetch[c, r, t]`` names the last ACTIVE tile at or before t
    (-1 before the first); the kernel runs tile t iff ``fetch[c, r, t] == t``
    — which subsumes the static padding early-out, since the engine ANDs
    the frontier hit mask with ``t < counts`` before building the map — and
    the index map fetches block ``max(fetch[c, r, t], 0)``, so every skipped
    grid step re-names an already-fetched block and costs no extra HBM
    traffic. With ``fetch=None`` behavior is bit-for-bit the static path.
    """
    p, r_blocks = word.shape[:2]
    assert r_blocks * vb == num_rows, (word.shape, vb, num_rows)
    return _fused_call(
        payload, word, counts, word_hi, weights, fetch, num_rows=num_rows,
        vb=vb, src_bits=src_bits, kind=kind, edge_op=edge_op,
        identity=identity, interpret=_interpret(interpret), push=False,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_rows", "src_bits", "kind", "edge_op", "identity", "interpret"
    ),
)
def scatter_reduce_cores_pallas(
    payload: jnp.ndarray,  # (G[, L]) phase-gathered crossbar block, shared by cores
    word: jnp.ndarray,  # (p, B, Tp, *tile_shape(Eb)) int32 packed PUSH words
    counts: jnp.ndarray,  # (p, B) int32 real edge tiles per (core, src block)
    word_hi: jnp.ndarray | None = None,  # like word, src_bits=32 only
    weights: jnp.ndarray | None = None,  # like word, f32 (edge_op == 'add')
    fetch: jnp.ndarray | None = None,  # (p, B, Tp) int32 dynamic fetch map
    *,
    num_rows: int,  # rows per core (= vertices_per_core)
    src_bits: int = 16,
    kind: str = "min",
    edge_op: str = "none",
    identity: float = 0.0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Push-mode (scatter) accumulator over the SOURCE-binned stream: grid
    (p, B, Tp) -> (p, num_rows[, L]) reductions.

    The mirror of ``gather_reduce_cores_pallas`` with the binning axis
    flipped: tiles are grouped by 32-aligned SOURCE block instead of
    destination row block, so a narrow frontier activates only the few blocks
    that contain frontier sources, and ``fetch`` (the frontier-ANDed active
    map over the push stream's own coverage words) elides everything else.
    The price is that a tile's destinations are arbitrary: the accumulator is
    the WHOLE per-core label row (num_rows resident in VMEM instead of vb),
    written once after the full (B, Tp) sweep, and the packed dstb field
    carries the full local row index. Only idempotent monotone reduces are
    admitted — scatter order across blocks is arbitrary, and skipped blocks
    rely on their contributions being already merged; both hold for min/or,
    neither for sum (docs/tile_layout.md §9).

    There is no level-2 fold here: moving rows out of a block is a
    pull-layout construct, and the push accumulator's rows are natural rows by
    construction, so the engine consumes this output directly.
    """
    assert kind in ("min", "or"), f"push scatter requires min/or, got {kind!r}"
    return _fused_call(
        payload, word, counts, word_hi, weights, fetch, num_rows=num_rows,
        vb=num_rows, src_bits=src_bits, kind=kind, edge_op=edge_op,
        identity=identity, interpret=_interpret(interpret), push=True,
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "vb", "kind", "edge_op", "identity", "interpret"),
)
def gather_reduce_pallas(
    payload: jnp.ndarray,  # (G,) gathered crossbar block (f32/u32)
    src: jnp.ndarray,  # (R, T, Eb) int32 into payload
    dstb: jnp.ndarray,  # (R, T, Eb) int32 row index WITHIN block [0, Vb)
    valid: jnp.ndarray,  # (R, T, Eb) bool
    weights: jnp.ndarray | None = None,  # (R, T, Eb) f32 (edge_op == 'add')
    *,
    num_rows: int,
    vb: int,
    kind: str = "min",
    edge_op: str = "none",
    identity: float = 0.0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One bucket from UNCOMPRESSED tile arrays: packs the (src, dstb, valid)
    triple into words in the trace and runs every tile (padding included)
    through the cores kernel with p = 1."""
    r_blocks, t_tiles, eb = src.shape
    tile = (1, r_blocks, t_tiles) + tile_shape(eb)
    g = payload.shape[0]
    src_bits = 16 if g <= (1 << 16) and vb <= (1 << 15) else 32
    vbit = jnp.where(valid, jnp.int32(_SIGN), jnp.int32(0))
    src = src.astype(jnp.int32)
    dstb = dstb.astype(jnp.int32)
    if src_bits == 16:
        word, hi = vbit | (dstb << 16) | src, None
    else:
        word, hi = src, vbit | dstb
    counts = jnp.full((1, r_blocks), t_tiles, jnp.int32)
    out = gather_reduce_cores_pallas(
        payload, word.reshape(tile), counts,
        None if hi is None else hi.reshape(tile),
        None if weights is None else weights.reshape(tile), None,
        num_rows=num_rows, vb=vb, src_bits=src_bits, kind=kind,
        edge_op=edge_op, identity=identity, interpret=interpret,
    )
    return out[0]
