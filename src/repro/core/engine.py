"""Asynchronous pull-based vertex-centric engine (paper §III-A/B) —
single-process reference implementation.

The ``p`` graph cores are simulated with a leading array dimension and
``vmap``-style batched ops; the crossbar is the phase-m gathered label block
(see ``core/distributed.py`` for the multi-device shard_map engine whose
numerics match this one exactly — tested).

Execution structure per iteration (paper Fig. 4):
  for phase m in range(l):                  # meta-partition M_m
    1. prefetch: slice sub-interval m of every core's payload and concatenate
       -> gathered block (the label scratch pads, crossbar-visible)
    2. process: gather per-edge source payloads, apply the map UDF, reduce by
       destination (the prefix-adder accumulator), and
    3. apply: min-problems with ``immediate_updates`` merge into the live
       label array NOW (asynchronous — later phases of this iteration see the
       new labels); otherwise contributions accumulate and merge at iteration
       end (synchronous / Jacobi).

Shapes are static; invalid (padding) edges contribute the reduce identity.

Two step-2 backends, selected by ``EngineOptions.backend``:

  * ``'pallas'`` (default — the primary path): one fused ``pallas_call`` per
    phase over grid (p, R, T) executes gather + edge map (incl. the SSSP
    saturating add) + segment reduce per tile, with the phase's gathered
    crossbar block resident in VMEM. The edge stream it reads is COMPRESSED
    (paper §III): each slot is one bit-packed int32 word (src | dstb | valid,
    decoded in-kernel; ``tile_word_hi`` carries the 32-bit-src fallback) and
    scalar-prefetched per-row-block tile counts let the kernel skip padding
    tiles outright. Per-edge values only ever exist in scalar registers —
    neither a (p, E_pad) contributions array nor an unpacked per-edge index
    array is materialized (the bandwidth property the paper's compressed
    accumulator is built around; asserted by jaxpr inspection in tests).
    Consumes the partition-time (p, l, R, T, *tile) packed stream on
    ``PartitionedGraph``. The kernel follows the backend: compiled on a
    TPU, the Pallas interpreter on CPU (correctness-grade timings only).
    Every natural row reduces in place (level 1); the few rows whose block
    overflowed at partition time also reduce pieces in appended blocks, and
    the second-level combine (``combine_split_rows``, the problem's reduce
    op + identity over ``tile_fold_rows``/``tile_fold_pos``) folds those
    pieces into their rows before apply — it gathers N x S partials, never V.
  * ``'xla'`` — the correctness oracle: materializes the (p, E_pad)
    contributions array via take/where and segment-reduces it. Bit-identical
    to the Pallas path for min problems; for sum problems (PageRank) results
    agree to float-summation-order reassociation.

Edge-index constants are converted to device arrays ONCE per trace, outside
the phase ``fori_loop`` body (they used to be re-wrapped per phase).

Frontier-aware dynamic tile scheduling (``EngineOptions.dynamic_tile_skip``,
on by default): min problems on the Pallas backend additionally carry a
frontier bitmap (``core/frontier_words.py``) across iterations — the packed
words of "which sources changed" — and each phase ANDs the partition-time
per-tile coverage bitmaps against the live frontier to skip REAL tiles none
of whose sources changed, on top of the static padding-tile skip. A density
switch (``lax.cond`` on frontier popcount vs ``dynamic_skip_density``) falls
back to the dense all-real-tiles path while the frontier is wide, and the
frontier doubles as the convergence check (empty frontier == converged),
replacing the separate ``not_converged`` label diff. The async path augments
the live frontier per phase with this iteration's merges, which makes the
dynamic schedule BIT-IDENTICAL per iteration to the dense async schedule
(monotone-min argument: every skipped tile's sources are unchanged since the
tile last ran, so its contributions are already merged) — same labels, same
iteration counts, just fewer tiles streamed.

Multi-query lane batching (docs/tile_layout.md §8): a ``Problem`` with
``lanes = K > 0`` carries a trailing lane axis on its payload — packed reach
words for multi-source BFS (``reduce_kind='or'``), a (…, K) label block for
SSSP/PPR — and one ``channel_phase_reduce_pallas`` launch updates all K
queries per tile decode; the compressed 4 B/edge word stream is fetched once
per tile regardless of K. 'or' problems always execute the synchronous
(level-synchronized) schedule — async multi-hop propagation within one
iteration would corrupt the level counter that recovers hop distances — and
stay eligible for dynamic tile skipping (OR is monotone like min); the
frontier words are the UNION over lanes, so a converged lane stops
contributing to the schedule without stopping the batch.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core import frontier_words as fwords
from repro.core.partition import PartitionedGraph
from repro.core.problems import Problem

__all__ = [
    "EngineOptions",
    "EngineResult",
    "prepare_labels",
    "run",
    "run_frontier_trace",
    "unpad_labels",
    "make_iteration",
    "evict_from_cache",
    "dynamic_skip_enabled",
    "push_enabled",
    "channel_phase_reduce_pallas",
    "channel_phase_scatter_pallas",
    "channel_phase_reduce_xla",
]


_BACKENDS = ("pallas", "xla")


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    immediate_updates: bool = True  # paper opt 1: async write-back to scratch
    prefetch_skipping: bool = True  # paper opt 2: skip re-prefetch when l == 1
    max_iters: int = 1000
    # 'pallas': fused gather-map-reduce kernel, the primary path (one launch
    # per phase covers all p cores). 'xla': materialize-then-reduce oracle.
    backend: str = "pallas"
    # frontier-aware dynamic tile scheduling (min problems, pallas backend):
    # skip real tiles whose coverage bitmap misses the live frontier. Safe to
    # leave on: results and iteration counts are identical to the static
    # schedule (see module docstring); it only changes which tiles stream.
    dynamic_tile_skip: bool = True
    # dense fallback: while frontier popcount >= density * total source bits,
    # run the static all-real-tiles schedule (the coverage AND would skip
    # almost nothing on a wide frontier). 0.0 = always dense (static
    # schedule via the dynamic carry); > 1.0 = never dense.
    dynamic_skip_density: float = 0.5
    # multi-query lane batching: expected lane count K. None = accept whatever
    # the problem declares (including laneless); an int pins the batch width —
    # a mismatched problem raises, which is the serving loop's admission check
    # that a batch was assembled to the width the jit cache is warm for.
    lanes: int | None = None
    # direction-optimizing traversal (Beamer push/pull, docs/tile_layout.md
    # §9). 'auto' switches per iteration on the union-frontier popcount:
    # enter push below alpha * total source bits, stay push below beta
    # (hysteresis; both scaled by 1/K for a K-lane batch, since a push pass
    # scatters each vertex's whole lane row). 'push'/'pull' force one
    # direction — 'push' raises unless the problem/partition admit it.
    direction: str = "auto"
    direction_alpha: float = 0.02
    direction_beta: float = 0.1

    def __post_init__(self):
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend must be one of {_BACKENDS}, got {self.backend!r}")
        if self.lanes is not None and self.lanes < 0:
            raise ValueError(f"lanes must be None or >= 0, got {self.lanes}")
        if self.direction not in ("auto", "push", "pull"):
            raise ValueError(
                f"direction must be 'auto', 'push' or 'pull', got {self.direction!r}"
            )
        if not 0.0 <= self.direction_alpha <= self.direction_beta:
            raise ValueError(
                "need 0 <= direction_alpha <= direction_beta, got "
                f"{self.direction_alpha} / {self.direction_beta}"
            )


def dynamic_skip_enabled(problem, pg, opts: EngineOptions) -> bool:
    """Frontier skipping is sound only for monotone reduces — min, and the
    bitwise OR of packed multi-source BFS lanes (a skipped tile's sources
    re-contribute values already merged); sum problems (PageRank) need every
    contribution every iteration and stay dense. Also requires the Pallas
    backend (the oracle materializes everything anyway) and partition-time
    coverage bitmaps."""
    return bool(
        opts.dynamic_tile_skip
        and opts.backend == "pallas"
        and problem.reduce_kind in ("min", "or")
        and getattr(pg, "tile_coverage", None) is not None
    )


def push_enabled(problem, pg, opts: EngineOptions) -> bool:
    """Push (scatter) direction is admissible: an idempotent monotone reduce
    (min/or — a skipped source block's contributions are already merged, and
    scatter order across blocks is arbitrary; sum stays pull-only), the
    Pallas backend, a partition-time push stream, and dynamic scheduling (the
    frontier carry is what both the switch and the push active map read).
    ``direction='pull'`` opts out entirely."""
    return bool(
        opts.direction != "pull"
        and getattr(pg, "push_word", None) is not None
        and dynamic_skip_enabled(problem, pg, opts)
    )


@dataclasses.dataclass
class EngineResult:
    labels: Dict[str, np.ndarray]  # unpadded, original vertex ids
    iterations: int
    converged: bool
    # pull-kernel tiles run, summed over iterations and phases (None: no
    # pull kernel ran, or the engine does not count them)
    pull_tiles_run: int | None = None


def prepare_labels(problem: Problem, g, pg: PartitionedGraph) -> Dict[str, jnp.ndarray]:
    """Init labels on host, apply stride permutation, reshape to (p, Vl).

    Lane-batched label fields arrive as (padded, L) — K vector lanes or
    packed reach words — and become (p, Vl, L): the permutation moves rows,
    the lane axis rides along untouched."""
    with telemetry.span("engine.prepare_labels"):
        padded = pg.padded_vertices
        with telemetry.span("init"):
            labels = problem.init_labels(g, padded)
        out = {}
        for k, v in labels.items():
            with telemetry.span("permute", field=k):
                v = np.asarray(v)
                if v.ndim in (1, 2) and v.shape[0] == padded:
                    if pg.perm is not None:
                        # perm is a bijection on [0, V): every slot < V is
                        # re-assigned, slots >= V keep their padding init.
                        moved = v.copy()
                        moved[pg.perm[: pg.num_vertices]] = v[: pg.num_vertices]
                        v = moved
                    v = v.reshape(pg.p, pg.vertices_per_core, *v.shape[1:])
            with telemetry.span("upload", field=k):
                out[k] = jnp.asarray(v)
        return out


def unpad_labels(
    labels: Dict[str, jnp.ndarray], pg: PartitionedGraph
) -> Dict[str, np.ndarray]:
    """Back to original vertex ids (undo stride permutation + padding)."""
    out = {}
    for k, v in labels.items():
        v = np.asarray(v)
        if v.ndim in (2, 3) and v.shape[:2] == (pg.p, pg.vertices_per_core):
            flat = v.reshape(pg.padded_vertices, *v.shape[2:])
            if pg.perm is not None:
                flat = flat[pg.perm[: pg.num_vertices]]
            else:
                flat = flat[: pg.num_vertices]
            out[k] = flat
        else:
            out[k] = v
    return out


def _segment_reduce(kind: str, contrib, dst, num_segments: int, identity):
    # ``identity`` documents the caller's reduce identity; segment_min fills
    # empty segments with the dtype max (float inf / 0xFFFFFFFF for uint32 ==
    # INF_U32) and segment_sum with 0, which ARE the min/sum identities the
    # problems use — the same pairing the two-level split combine
    # (combine_split_rows) relies on. A kind whose identity is not the dtype
    # extreme would need an explicit fill here.
    if kind == "min":
        return jax.ops.segment_min(
            contrib, dst, num_segments=num_segments, indices_are_sorted=True
        )
    if kind == "or":
        # bitwise-OR segments by bit-plane decomposition: per bit, segment_max
        # of the 0/1 plane (empty segments fill with uint32 min == 0, the OR
        # identity). 32 segment ops — oracle-path only; the Pallas kernel does
        # the word-OR directly.
        out = jnp.zeros((num_segments,) + contrib.shape[1:], dtype=contrib.dtype)
        for b in range(32):
            plane = (contrib >> jnp.uint32(b)) & jnp.uint32(1)
            mx = jax.ops.segment_max(
                plane, dst, num_segments=num_segments, indices_are_sorted=True
            )
            out = out | (mx << jnp.uint32(b))
        return out
    return jax.ops.segment_sum(
        contrib, dst, num_segments=num_segments, indices_are_sorted=True
    )


def _edge_constants(
    problem: Problem, pg: PartitionedGraph, opts: EngineOptions, put=jnp.asarray
):
    """Device-array edge constants, converted ONCE (hoisted out of the traced
    phase body — ``jnp.asarray`` on host numpy used to run inside it).
    ``put`` moves one host array to the device (``run`` passes a cache)."""
    if opts.backend == "pallas":
        # channel_arrays(problem) is the single source of truth for the packed
        # stream layout (word/word_hi/counts/w/fold_rows/fold_pos with a
        # leading core == channel axis) AND the weight-streaming rule; the
        # distributed engine NamedSharding-places the same dict over the mesh.
        # Weightless edge_op streams NO weight array at all: the kernel adds
        # a constant 1.0 in registers.
        arrs = pg.channel_arrays(problem)
        return {k: (put(v) if v is not None else None) for k, v in arrs.items()}
    w = put(pg.weights) if pg.weights is not None else None
    return {
        "src": put(pg.src_gidx),  # (p, l, E_pad)
        "dst": put(pg.dst_lidx),
        "valid": put(pg.valid),
        "w": w,
    }


def phase_consts_at(consts, m):
    """Slice phase ``m`` out of every packed edge constant (axis 1 = phase).

    Works for both layouts of the leading channel axis: the single-process
    engine's full (p, l, ...) stack and a distributed device's (1, l, ...)
    shard — phase slicing is channel-local either way."""
    return {
        k: (
            jax.lax.dynamic_index_in_dim(v, m, axis=1, keepdims=False)
            if v is not None
            else None
        )
        for k, v in consts.items()
    }


def _fetch_map(active):
    if active is None:
        return None
    with jax.named_scope("coverage"):
        return fwords.active_fetch_map(active)


def channel_phase_reduce_pallas(problem, pg, gathered, cm, opts, active=None):
    """THE fused gather-map-reduce primitive (steps 1+2 of a phase), channel
    local: ONE ``pallas_call`` over grid (n, R, T) does unpack + gather + map
    UDF + segment reduce against the phase's gathered crossbar block, reading
    the compressed word stream and skipping padding tiles. ``n`` is whatever
    the caller's channel axis holds — all ``p`` cores in the single-process
    engine, exactly 1 on a distributed device (one core per memory channel) —
    so both engines execute this one implementation.

    ``gathered`` is the (G,) crossbar block (locally sliced in-process;
    ``crossbar_exchange``-all-gathered across devices). ``cm`` is a phase
    slice of the packed constants (``phase_consts_at``). No (n, E_pad)
    per-edge array is materialized. The kernel output is over PACKED rows:
    the natural blocks, read in place, and any appended overflow blocks,
    whose pieces the second-level combine folds into their rows (still no
    per-edge materialization). Returns (n, Vl).

    ``active`` (traced (n, R, T) bool, from ``frontier_active_tiles`` — must
    already be ANDed with the real-tile mask) engages frontier-aware dynamic
    skipping: it is folded to the kernel's scalar-prefetched fetch map, which
    REPLACES the static tile counts, so inactive tiles are never decoded,
    gathered, or reduced this launch. None = the static schedule."""
    from repro.kernels.csr_gather_reduce.kernel import gather_reduce_cores_pallas
    from repro.kernels.csr_gather_reduce.ops import combine_split_rows

    reduced = gather_reduce_cores_pallas(
        gathered,
        cm["word"],
        cm["counts"],
        cm["word_hi"],
        cm["w"],
        _fetch_map(active),
        num_rows=pg.packed_rows_per_core,
        vb=pg.tile_vb,
        src_bits=pg.src_bits,
        kind=problem.reduce_kind,
        edge_op=problem.edge_op,
        identity=problem.identity,
    )  # (n, R*vb) level-1 reductions in packed-row space
    # level 2: the natural blocks as a slice, the moved rows' pieces folded
    # in with the problem's reduce op (padding reads its identity)
    return combine_split_rows(
        reduced, cm["fold_rows"], cm["fold_pos"], num_rows=pg.vertices_per_core,
        kind=problem.reduce_kind, identity=problem.identity,
    )


def channel_phase_scatter_pallas(problem, pg, gathered, cm, opts, active=None):
    """Push-mode counterpart of ``channel_phase_reduce_pallas``: ONE
    ``pallas_call`` over grid (n, B, Tp) scatters the SOURCE-binned push
    stream of phase m into the whole per-core label row. ``cm`` is a phase
    slice of the push constants keyed like the pull ones (``word``/
    ``word_hi``/``counts``/``w``). ``active`` is the frontier-ANDed (n, B,
    Tp) mask over the push stream's own coverage words — on a narrow
    frontier only the blocks containing frontier sources stream, which is
    the whole point of the direction switch. Output rows are natural rows by
    construction (no row packing or hub splitting on the push side), so
    there is no level-2 fold. Returns (n, Vl[, L])."""
    from repro.kernels.csr_gather_reduce.kernel import scatter_reduce_cores_pallas

    return scatter_reduce_cores_pallas(
        gathered,
        cm["word"],
        cm["counts"],
        cm["word_hi"],
        cm["w"],
        _fetch_map(active),
        num_rows=pg.vertices_per_core,
        src_bits=pg.push_src_bits,
        kind=problem.reduce_kind,
        edge_op=problem.edge_op,
        identity=problem.identity,
    )


def channel_phase_reduce_xla(problem, pg, gathered, cm, opts):
    """Oracle form of the channel-local phase reduce: materialize (n, E_pad)
    contributions via take/where, then segment-reduce. ``cm`` holds the flat
    (n, E_pad) src/dst/valid slices of one phase."""
    svals = jnp.take(gathered, cm["src"], axis=0)  # (n, E[, L]) crossbar reads
    contrib = problem.edge_map(svals, cm["w"])
    identity = jnp.asarray(problem.identity, dtype=contrib.dtype)
    valid = cm["valid"]
    if contrib.ndim > valid.ndim:  # trailing lane axis broadcasts
        valid = valid[..., None]
    contrib = jnp.where(valid, contrib, identity)
    return jax.vmap(
        lambda c, d: _segment_reduce(
            problem.reduce_kind, c, d, pg.vertices_per_core, identity
        )
    )(contrib, cm["dst"])  # (n, Vl)


def _gather_local(problem, pg, labels, m):
    """Single-process crossbar: every core's phase-m sub-interval is a local
    slice of the (p, Vl[, L]) payload — concatenating them IS the gathered
    block ((G,) laneless, (G, L) with a multi-query lane axis)."""
    with jax.named_scope("gather"):
        payload = problem.src_transform(labels)  # (p, Vl[, L]) elementwise
        sub = jax.lax.dynamic_slice_in_dim(
            payload, m * pg.sub_size, pg.sub_size, axis=1
        )
        return sub.reshape(pg.gathered_size, *payload.shape[2:])  # (G[, L])


def _phase_reduce_pallas(problem, pg, consts, labels, m, opts, active=None):
    gathered = _gather_local(problem, pg, labels, m)
    return channel_phase_reduce_pallas(
        problem, pg, gathered, phase_consts_at(consts, m), opts, active
    )


def _phase_reduce_xla(problem, pg, consts, labels, m, opts, active=None):
    assert active is None, "dynamic tile skipping requires the pallas backend"
    gathered = _gather_local(problem, pg, labels, m)
    return channel_phase_reduce_xla(
        problem, pg, gathered, phase_consts_at(consts, m), opts
    )


_PUSH_KEYS = (
    "push_word", "push_word_hi", "push_counts", "push_w", "push_coverage"
)


def make_iteration(
    problem: Problem,
    pg: PartitionedGraph,
    opts: EngineOptions,
    reduce_at_phase=None,
    phase_active=None,
    density_fn=None,
    with_stats: bool = False,
    push_reduce_at_phase=None,
    push_phase_active=None,
    push_phase_live=None,
    consts=None,
):
    """Build one engine iteration (the l-phase loop + apply semantics).

    ``reduce_at_phase(m, labels[, active]) -> reduced`` supplies steps 1+2 of
    phase m; ``reduced`` must match ``labels[merge_field]``'s shape. When None
    (the single-process engine) it is built from the packed edge constants and
    the backend's channel phase reduce. ``consts`` supplies those constants
    (the ``_edge_constants`` dict, possibly traced jit arguments — ``run``
    passes them so a full-size stream is never baked into the executable);
    None converts the partition's host arrays in the trace. The distributed engine passes its own
    — crossbar all-gather + the SAME ``channel_phase_reduce_pallas`` on a
    one-channel shard — so apply semantics (async min merge vs synchronous
    accumulate + finalize) exist exactly once.

    The returned ``iteration(labels, frontier=None)`` has two calling modes:

      * ``iteration(labels)`` — the static schedule, exactly the historical
        behavior (every real tile streams); returns the new labels.
      * ``iteration(labels, frontier)`` — frontier-aware dynamic scheduling
        (requires ``dynamic_skip_enabled``). ``frontier`` is the packed label
        -change bitmap of the previous iteration (``(..., l, Ws)`` uint32,
        ``full_frontier_words`` on iteration 0); returns ``(new_labels,
        new_frontier)`` — the new frontier is exactly the words of this
        iteration's label changes, so ``any(new_frontier != 0)`` IS the
        convergence check. With ``with_stats=True`` a third element is
        returned: ``{"active_tiles": int32, "use_dense": int32}``.

    Dynamic-mode hooks (the distributed engine overrides both; defaults are
    the single-process closures): ``phase_active(m, live_frontier, use_dense)
    -> (n, R, T) bool`` builds phase m's active-tile mask from the live
    frontier words; ``density_fn(frontier) -> int32`` is the global frontier
    popcount for the density switch (distributed: psum over channels, so
    every device takes the same ``lax.cond`` branch).

    Direction-optimizing traversal (``push_enabled``, docs/tile_layout.md
    §9): a third calling mode ``iteration(labels, frontier, prev_push)``
    adds the Beamer push/pull switch on top of dynamic scheduling.
    ``prev_push`` is last iteration's direction (bool; False on iteration
    0) and the return gains a trailing ``used_push`` bool BEFORE the stats
    element: ``(new_labels, new_frontier, used_push[, stats])``. The switch
    is taken once per iteration on the same union-frontier popcount the
    density switch reads — enter push below ``direction_alpha`` * total
    source bits, stay while below ``direction_beta`` (both scaled by 1/K
    for a K-lane batch) — and a ``lax.cond`` picks the pull phase loop or
    the push phase loop (same carry, bit-identical labels either way).
    Calls WITHOUT ``prev_push`` keep the exact legacy pull-only behavior
    and arity — the push machinery engages only when the caller threads
    the direction carry — EXCEPT under a forced ``direction='push'``,
    where every dynamic call runs the push loop directly (no cond, no
    pull-side stream in the jaxpr; legacy arity when ``prev_push`` is
    omitted). ``push_reduce_at_phase(m, labels, active)`` /
    ``push_phase_active(m, live_frontier)`` are the distributed overrides,
    mirroring the pull hooks (the push active map has no dense fallback:
    a wide frontier is what the switch itself avoids)."""
    if opts.lanes is not None and opts.lanes != problem.lanes:
        raise ValueError(
            f"EngineOptions.lanes={opts.lanes} but problem "
            f"{problem.name!r} declares lanes={problem.lanes}"
        )
    # 'or' (packed multi-source BFS) always runs the level-synchronized
    # schedule: its finalize recovers hop distances from a per-iteration
    # level counter, which async multi-hop propagation would corrupt. Both
    # immediate_updates settings therefore produce identical results.
    is_min = problem.reduce_kind == "min"
    dyn = dynamic_skip_enabled(problem, pg, opts)
    push_on = push_enabled(problem, pg, opts)
    forced_push = opts.direction == "push"
    if forced_push and not push_on:
        raise ValueError(
            "direction='push' requires an admissible push path: a min/or "
            "problem, the pallas backend, a partition built with "
            "build_push=True, and dynamic scheduling (dynamic_skip_enabled)"
        )
    if reduce_at_phase is None:
        consts = dict(
            consts if consts is not None else _edge_constants(problem, pg, opts)
        )
        # coverage feeds phase_active below, never the phase reduce itself —
        # keep it out of the sliced consts so the static path's jaxpr is
        # untouched and the dynamic path slices it exactly once per phase.
        coverage = consts.pop("coverage", None)
        # the push stream likewise never enters the pull phase reduce; pop it
        # unconditionally so phase_consts_at never slices it on the pull path.
        push_raw = {k: consts.pop(k, None) for k in _PUSH_KEYS}
        reduce_fn = (
            _phase_reduce_pallas if opts.backend == "pallas" else _phase_reduce_xla
        )

        def reduce_at_phase(m, labels, active=None):
            return reduce_fn(problem, pg, consts, labels, m, opts, active)

        if dyn and phase_active is None:
            counts = consts["counts"]

            def phase_active(m, live_fw, use_dense):
                cov_m = jax.lax.dynamic_index_in_dim(
                    coverage, m, axis=1, keepdims=False
                )  # (p, R, T, Wc)
                cnt_m = jax.lax.dynamic_index_in_dim(
                    counts, m, axis=1, keepdims=False
                )  # (p, R)
                # core-major flatten of the cores' phase-m rows IS the
                # gathered-block word order (the layout contract).
                gfw = jax.lax.dynamic_index_in_dim(
                    live_fw, m, axis=-2, keepdims=False
                ).reshape(-1)
                return fwords.frontier_active_tiles(cov_m, gfw, cnt_m, use_dense)

        if push_on and push_phase_active is None:
            # push constants re-keyed to the canonical stream names so
            # phase_consts_at and the scatter primitive read one layout.
            push_cm_all = {
                "word": push_raw["push_word"],
                "word_hi": push_raw["push_word_hi"],
                "counts": push_raw["push_counts"],
                "w": push_raw["push_w"],
            }
            push_cov = push_raw["push_coverage"]
            push_counts = push_raw["push_counts"]

            def push_reduce_at_phase(m, labels, active):
                gathered = _gather_local(problem, pg, labels, m)
                return channel_phase_scatter_pallas(
                    problem, pg, gathered,
                    phase_consts_at(push_cm_all, m), opts, active,
                )

            def push_phase_active(m, live_fw):
                cov_m = jax.lax.dynamic_index_in_dim(
                    push_cov, m, axis=1, keepdims=False
                )  # (p, B, Tp, Wc)
                cnt_m = jax.lax.dynamic_index_in_dim(
                    push_counts, m, axis=1, keepdims=False
                )  # (p, B)
                gfw = jax.lax.dynamic_index_in_dim(
                    live_fw, m, axis=-2, keepdims=False
                ).reshape(-1)
                # no dense fallback: a wide frontier takes the pull branch
                # upstream, so the push map is always frontier-ANDed.
                return fwords.frontier_active_tiles(cov_m, gfw, cnt_m, None)

            def push_phase_live(m, live_fw):
                # phase-level skip: a phase none of whose sources are in the
                # live frontier scatters nothing (its reduce is the identity
                # for min/or), so the push arm drops the whole phase —
                # active map, kernel launch and merge included. This is the
                # coarsest grain of "stream only the frontier's out-tiles".
                return jnp.any(
                    jax.lax.dynamic_index_in_dim(
                        live_fw, m, axis=-2, keepdims=False
                    )
                    != 0
                )

    if push_on and push_phase_active is None:
        # a caller supplying its own reduce hooks (the distributed engine)
        # must supply the push hooks too to opt in; without them the
        # iteration stays pull-only.
        if forced_push:
            raise ValueError(
                "direction='push' with caller-supplied reduce hooks needs "
                "push_reduce_at_phase/push_phase_active"
            )
        push_on = False

    if dyn:
        # dense-fallback threshold over GLOBAL real source bits (the frontier
        # tail bits are never set, so popcount is over real sources only)
        dense_thr = jnp.int32(
            int(pg.p * pg.l * pg.sub_size * opts.dynamic_skip_density)
        )
        if density_fn is None:
            density_fn = fwords.frontier_popcount
    if push_on:
        # Beamer alpha/beta hysteresis over the SAME popcount, scaled by 1/K
        # for a K-lane batch: a push pass scatters each changed vertex's
        # whole lane row, so the per-frontier-bit push cost grows ~K-fold
        # and the crossover shifts down accordingly (switch per batch on the
        # union popcount, never per lane).
        lane_k = max(problem.lanes, 1)
        total_bits = pg.p * pg.l * pg.sub_size
        alpha_thr = jnp.int32(int(total_bits * opts.direction_alpha / lane_k))
        beta_thr = jnp.int32(int(total_bits * opts.direction_beta / lane_k))

    def _words_of(old, new):
        # lane-batched labels carry a trailing lane axis: the frontier is the
        # UNION over lanes (a tile streams iff any live query needs it).
        with jax.named_scope("frontier"):
            return fwords.frontier_words_from_labels(
                old, new, pg.l, pg.sub_size, lanes=problem.lanes > 0
            )

    def _stats(active_tiles, use_dense, use_push=None, pop=None):
        out = {
            "active_tiles": active_tiles,
            "use_dense": use_dense.astype(jnp.int32),
        }
        if use_push is not None:  # push-aware calls only (legacy keys stable)
            out["direction"] = use_push.astype(jnp.int32)  # 1 = push
            out["popcount"] = pop
        return out

    def _choose_push(pop, prev_push):
        """The per-iteration direction decision (one bool for the whole
        batch). Forced 'push' is handled by the callers — they run the push
        loop directly so no pull-side stream enters the jaxpr."""
        use_push = pop < alpha_thr
        if prev_push is not None:  # hysteresis: stay push while below beta
            use_push = use_push | (prev_push & (pop < beta_thr))
        return use_push

    if is_min and opts.immediate_updates:

        def _merge(labels, reduced):
            lab = labels[problem.merge_field]
            with jax.named_scope("merge"):
                merged = jnp.minimum(lab, reduced.astype(lab.dtype))
            new = dict(labels)
            new[problem.merge_field] = merged
            return new, lab, merged

        def _static(labels):
            def phase(m, labels):
                return _merge(labels, reduce_at_phase(m, labels))[0]

            return jax.lax.fori_loop(0, pg.l, phase, labels)

        def _phase_loop(labels, fw_in, reduce_fn_m, active_fn_m,
                        phase_live_fn=None):
            """The async phase sweep, parameterized over direction: the pull
            and push arms differ ONLY in which stream reduces a phase and
            which coverage builds its active map — merge semantics, frontier
            augmentation, and the carry are shared, which is what makes the
            lax.cond arms line up. ``phase_live_fn`` (push arm only) skips a
            whole phase when none of its sources are live: the reduce would
            return the identity, so labels, frontier words and the active
            count are all unchanged — bit-identical, minus the phase's
            fixed cost."""

            def body(m, carry):
                labels, nf, n_act = carry
                with jax.named_scope("coverage"):
                    active = active_fn_m(m, fw_in | nf)
                    n_act = n_act + jnp.sum(active, dtype=jnp.int32)
                new, lab, merged = _merge(labels, reduce_fn_m(m, labels, active))
                nf = nf | _words_of(lab, merged)
                return new, nf, n_act

            def phase(m, carry):
                # live frontier = last iteration's changes OR this
                # iteration's so-far — async phases see fresh labels, so the
                # schedule must track them to stay identical to dense async.
                if phase_live_fn is None:
                    return body(m, carry)
                return jax.lax.cond(
                    phase_live_fn(m, fw_in | carry[1]),
                    lambda c: body(m, c),
                    lambda c: c,
                    carry,
                )

            return jax.lax.fori_loop(
                0, pg.l, phase, (labels, jnp.zeros_like(fw_in), jnp.int32(0))
            )

        def _dynamic(labels, fw_in, prev_push=None):
            pop = density_fn(fw_in)
            use_dense = pop >= dense_thr

            def _pull(labels):
                return _phase_loop(
                    labels, fw_in, reduce_at_phase,
                    lambda m, live: phase_active(m, live, use_dense),
                )

            if not push_on or (prev_push is None and not forced_push):
                # legacy pull-only dynamic call — byte-for-byte the PR 6 path
                labels, nf, n_act = _pull(labels)
                if with_stats:
                    return labels, nf, _stats(n_act, use_dense)
                return labels, nf

            def _push(labels):
                return _phase_loop(
                    labels, fw_in, push_reduce_at_phase,
                    lambda m, live: push_phase_active(m, live),
                    phase_live_fn=push_phase_live,
                )

            if forced_push:
                use_push = jnp.bool_(True)
                labels, nf, n_act = _push(labels)
            else:
                use_push = _choose_push(pop, prev_push)
                labels, nf, n_act = jax.lax.cond(use_push, _push, _pull, labels)
            # monotone min: the union of per-phase change words == the words
            # of (labels in vs labels out) — nf IS the next frontier.
            if prev_push is None:  # forced push, legacy arity
                if with_stats:
                    return labels, nf, _stats(n_act, use_dense, use_push, pop)
                return labels, nf
            if with_stats:
                return labels, nf, use_push, _stats(n_act, use_dense, use_push, pop)
            return labels, nf, use_push

        def iteration(labels, frontier=None, prev_push=None):
            if frontier is None:
                if prev_push is not None:
                    raise ValueError("prev_push requires a frontier")
                return _static(labels)
            if not dyn:
                raise ValueError(
                    "iteration got a frontier but dynamic skipping is "
                    "disabled (see dynamic_skip_enabled)"
                )
            if prev_push is not None and not push_on:
                raise ValueError(
                    "iteration got prev_push but the push direction is not "
                    "admissible (see push_enabled)"
                )
            return _dynamic(labels, frontier, prev_push)

        return iteration

    # synchronous path: accumulate contributions, apply at iteration end
    def iteration(labels, frontier=None, prev_push=None):
        if frontier is None and prev_push is not None:
            raise ValueError("prev_push requires a frontier")
        if frontier is not None and not dyn:
            raise ValueError(
                "iteration got a frontier but dynamic skipping is disabled "
                "(see dynamic_skip_enabled)"
            )
        if prev_push is not None and not push_on:
            raise ValueError(
                "iteration got prev_push but the push direction is not "
                "admissible (see push_enabled)"
            )
        lab = labels[problem.merge_field]
        acc_dtype = jnp.float32 if problem.reduce_kind == "sum" else lab.dtype
        acc0 = jnp.full(lab.shape, problem.identity, dtype=acc_dtype)
        dynamic = frontier is not None
        pop = density_fn(frontier) if dynamic else None
        use_dense = pop >= dense_thr if dynamic else None
        n_act0 = jnp.int32(0)
        push_aware = dynamic and push_on and (prev_push is not None or forced_push)

        def acc_loop(reduce_fn_m, active_fn_m, phase_live_fn=None):
            def body(m, carry):
                acc, n_act = carry
                if dynamic:
                    # synchronous phases only see LAST iteration's labels, so
                    # the input frontier alone is the live frontier.
                    with jax.named_scope("coverage"):
                        active = active_fn_m(m, frontier)
                        n_act = n_act + jnp.sum(active, dtype=jnp.int32)
                    reduced = reduce_fn_m(m, labels, active)
                else:
                    reduced = reduce_fn_m(m, labels)
                with jax.named_scope("merge"):
                    if problem.reduce_kind == "min":
                        return jnp.minimum(acc, reduced.astype(acc.dtype)), n_act
                    if problem.reduce_kind == "or":
                        return acc | reduced.astype(acc.dtype), n_act
                    return acc + reduced.astype(acc.dtype), n_act

            def phase(m, carry):
                # push arm phase-level skip (see _phase_loop): a phase with
                # no live sources contributes the reduce identity
                if phase_live_fn is None:
                    return body(m, carry)
                return jax.lax.cond(
                    phase_live_fn(m, frontier),
                    lambda c: body(m, c),
                    lambda c: c,
                    carry,
                )

            return jax.lax.fori_loop(0, pg.l, phase, (acc0, n_act0))

        def _pull_loop(_=None):
            return acc_loop(
                reduce_at_phase,
                (lambda m, fw: phase_active(m, fw, use_dense)) if dynamic else None,
            )

        use_push = None
        if push_aware:

            def _push_loop(_=None):
                return acc_loop(
                    push_reduce_at_phase,
                    lambda m, fw: push_phase_active(m, fw),
                    phase_live_fn=push_phase_live,
                )

            if forced_push:
                use_push = jnp.bool_(True)
                acc, n_act = _push_loop()
            else:
                use_push = _choose_push(pop, prev_push)
                acc, n_act = jax.lax.cond(use_push, _push_loop, _pull_loop, None)
        else:
            acc, n_act = _pull_loop()

        def _ret(new, nf):
            extras = ()
            if prev_push is not None:
                extras += (use_push,)
            if with_stats:
                extras += (_stats(n_act, use_dense, use_push, pop if push_aware else None),)
            return (new, nf) + extras if extras else (new, nf)

        with jax.named_scope("merge"):
            if problem.reduce_kind == "min":
                new = dict(labels)
                new[problem.merge_field] = jnp.minimum(lab, acc.astype(lab.dtype))
            else:
                new = problem.finalize(labels, acc)
        if dynamic:  # min and 'or' problems: monotone, so the frontier applies
            return _ret(new, _words_of(lab, new[problem.merge_field]))
        return new

    return iteration


# the historical private name (tests and callers predate the public API)
_make_iteration = make_iteration


@partial(jax.jit, static_argnames=("problem", "pg", "opts"))
def _run_jit(problem, pg, opts, labels, consts):
    """Labels, iterations, whether still changing, the pull kernel's tiles
    run on the dynamic schedule (None on the static one), and the pull
    iterations where push may take some (None: every iteration pulls)."""
    iteration = _make_iteration(problem, pg, opts, consts=consts, with_stats=True)
    if dynamic_skip_enabled(problem, pg, opts):
        # frontier-carried loop: the per-iteration label-change words both
        # schedule the next iteration's tiles AND are the convergence check
        # (empty frontier == no label changed == problem.not_converged False
        # for the monotone min problems dynamic skipping admits).
        fw0 = fwords.full_frontier_words(pg.l, pg.sub_size, lead=(pg.p,))
        if push_enabled(problem, pg, opts):
            # direction-optimizing: thread last iteration's direction through
            # the carry for the alpha/beta hysteresis (False on iteration 0 —
            # the full frontier always takes the pull branch under 'auto').

            def cond(carry):
                _, _, it, changed, _, _, _ = carry
                return jnp.logical_and(changed, it < opts.max_iters)

            def body(carry):
                labels, fw, it, _, dirp, tiles, pulls = carry
                new, nf, dirn, stats = iteration(labels, fw, dirp)
                # a push iteration runs the push kernel, no pull tile
                pulled = jnp.where(dirn, jnp.int32(0), stats["active_tiles"])
                return (new, nf, it + 1, jnp.any(nf != jnp.uint32(0)), dirn,
                        tiles + pulled, pulls + jnp.where(dirn, 0, 1))

            labels, _, iters, changed, _, tiles, pulls = jax.lax.while_loop(
                cond, body,
                (labels, fw0, jnp.int32(0), jnp.bool_(True), jnp.bool_(False),
                 jnp.int32(0), jnp.int32(0)),
            )
            return labels, iters, changed, tiles, pulls

        def cond(carry):
            _, _, it, changed, _ = carry
            return jnp.logical_and(changed, it < opts.max_iters)

        def body(carry):
            labels, fw, it, _, tiles = carry
            new, nf, stats = iteration(labels, fw)
            return (new, nf, it + 1, jnp.any(nf != jnp.uint32(0)),
                    tiles + stats["active_tiles"])

        labels, _, iters, changed, tiles = jax.lax.while_loop(
            cond, body,
            (labels, fw0, jnp.int32(0), jnp.bool_(True), jnp.int32(0)),
        )
        return labels, iters, changed, tiles, None

    def cond(carry):
        _, it, changed = carry
        return jnp.logical_and(changed, it < opts.max_iters)

    def body(carry):
        labels, it, _ = carry
        new = iteration(labels)
        changed = problem.not_converged(labels, new)
        return new, it + 1, changed

    labels, iters, changed = jax.lax.while_loop(
        cond, body, (labels, jnp.int32(0), jnp.bool_(True))
    )
    return labels, iters, changed, None, None


# LRU-bounded (was unbounded: a serving loop running many graphs pinned every
# Problem/PartitionedGraph ever run for the life of the process). Eviction is
# safe — the wrapper is only a jit cache key, so re-wrapping an evicted object
# costs one retrace, never wrong results. The `hit[0] is obj` guard also
# covers id() reuse after eviction frees the old object.
_WRAP_CACHE: OrderedDict = OrderedDict()
_WRAP_CACHE_MAX = 128


def _wrap(obj):
    """Identity-hashed static wrapper, cached so repeated runs share jit cache."""
    key = id(obj)
    hit = _WRAP_CACHE.get(key)
    if hit is not None and hit[0] is obj:
        _WRAP_CACHE.move_to_end(key)
        return hit[1]
    w = _Hashable(obj)
    _WRAP_CACHE[key] = (obj, w)  # keep obj alive so id stays valid
    while len(_WRAP_CACHE) > _WRAP_CACHE_MAX:
        _WRAP_CACHE.popitem(last=False)
    return w


def evict_from_cache(obj) -> bool:
    """Drop a retired object (typically the pre-flush ``PartitionedGraph``)
    from the static-wrapper cache.

    A delta flush (``partition.apply_edge_deltas``) returns a NEW partition
    object — the old wrapper's traces and uploaded edge arrays
    (``device_array``) belong to the old stream, so the old entry can never
    serve the updated graph and only pins dead device arrays (and the retired
    host arrays themselves) until 128 newer entries push it out. The serving loop calls
    this on every flush. Returns True if an entry was evicted."""
    return _WRAP_CACHE.pop(id(obj), None) is not None


def run(
    problem: Problem,
    g,
    pg: PartitionedGraph,
    opts: EngineOptions = EngineOptions(),
    labels: Dict[str, jnp.ndarray] | None = None,
) -> EngineResult:
    """Run ``problem`` to convergence. ``labels`` (a ``prepare_labels`` tree)
    overrides the problem's own init — the serving loop's warm-cache hook: a
    multi-query problem's traced computation depends only on its lane count,
    never on the root VALUES (those live in the label init), so admission
    batches reuse ONE template problem as the jit cache key and feed each
    batch's roots through ``labels`` without retracing (launch/serve.py).

    Spans (``repro.telemetry``): ``engine.run`` around the whole call;
    ``engine.execute`` from the dispatch until the iteration count is back
    on the host; ``engine.readback`` for the labels' copy back and
    un-permutation. Counters (Pallas backend only): ``engine.pull_tiles_run``,
    the pull kernel's tiles run over all iterations and phases;
    ``engine.pull_tiles_real``, the real tiles times iterations; and
    ``engine.fold_rows``, the rows the level-2 fold combined: the moved
    rows of every (core, phase) bucket times the pull iterations."""
    with telemetry.span("engine.run"):
        if labels is None:
            labels = prepare_labels(problem, g, pg)
        # opts is a frozen dataclass of primitives: hashable BY VALUE, so
        # fresh EngineOptions() instances hit the jit cache (id-wrapping it
        # caused a recompile per call — caught because benchmarks timed
        # compiles).
        pgw = _wrap(pg)
        # the packed stream enters as jit ARGUMENTS, each host array uploaded
        # once per partition and kept with its wrapper: baked in as
        # constants, a graph500-scale stream would be serialized into (and
        # slow) every executable.
        consts = _edge_constants(problem, pg, opts, put=pgw.device_array)
        with telemetry.span("engine.execute"):
            labels, iters, changed, tiles, pulls = _run_jit(
                _wrap(problem), pgw, opts, labels, consts
            )
            iters, changed, tiles, pulls = jax.device_get(
                (iters, changed, tiles, pulls)
            )
        iters = int(iters)
        tiles_run = None
        if opts.backend == "pallas":
            real = iters * int(pg.tile_counts.sum())
            # the static schedule runs every real tile each iteration
            tiles_run = real if tiles is None else int(tiles)
            telemetry.count("engine.pull_tiles_run", tiles_run)
            telemetry.count("engine.pull_tiles_real", real)
            pulls = iters if pulls is None else int(pulls)
            telemetry.count("engine.fold_rows", pulls * pg.split_rows)
        with telemetry.span("engine.readback"):
            out = unpad_labels(labels, pg)
    return EngineResult(
        labels=out, iterations=iters, converged=not bool(changed),
        pull_tiles_run=tiles_run,
    )


def run_frontier_trace(
    problem: Problem, g, pg: PartitionedGraph, opts: EngineOptions = EngineOptions()
) -> dict:
    """Host-stepped dynamic run that records the per-iteration schedule.

    Same numerics as ``run`` (one jitted ``iteration(labels, frontier)`` per
    step), but stepped from the host so each iteration's active-tile count
    can be read back. Returns a dict with the final ``labels`` /
    ``iterations`` / ``converged``, ``active_tiles`` (the tiles each
    iteration's kernel ran), ``dynamic_skipped_tile_fraction`` — a
    per-iteration list over the SAME denominator as the static
    ``pg.skipped_tile_fraction`` (all (core, phase, row-block) x T_max tile
    slots; a push iteration's fraction uses the push stream's own (core,
    phase, src-block) x Tp_max denominator, since that is the stream it
    scheduled against) — ``dense_iterations`` (how often the density switch
    took the wide-frontier fallback), ``direction`` (the per-iteration
    'push'/'pull' choice; all-'pull' when the push path is off), and
    ``push_iterations``."""
    if not dynamic_skip_enabled(problem, pg, opts):
        raise ValueError(
            "run_frontier_trace needs dynamic skipping: a min problem, the "
            "pallas backend, coverage bitmaps, and dynamic_tile_skip=True"
        )
    labels = prepare_labels(problem, g, pg)
    step = jax.jit(make_iteration(problem, pg, opts, with_stats=True))
    fw = fwords.full_frontier_words(pg.l, pg.sub_size, lead=(pg.p,))
    push_on = push_enabled(problem, pg, opts)
    total_tiles = pg.tile_counts.size * pg.tile_word.shape[3]
    total_push_tiles = (
        pg.push_counts.size * pg.push_word.shape[3] if push_on else 0
    )
    prev = jnp.bool_(False)
    active, fractions, directions = [], [], []
    dense_iters, it, converged = 0, 0, False
    while it < opts.max_iters:
        if push_on:
            labels, fw, prev, stats = step(labels, fw, prev)
            pushed = bool(stats["direction"])
        else:
            labels, fw, stats = step(labels, fw)
            pushed = False
        total = total_push_tiles if pushed else total_tiles
        active.append(int(stats["active_tiles"]))
        fractions.append(1.0 - active[-1] / max(total, 1))
        directions.append("push" if pushed else "pull")
        dense_iters += int(stats["use_dense"])
        it += 1
        if not bool(jnp.any(fw != jnp.uint32(0))):  # free convergence check
            converged = True
            break
    return {
        "labels": unpad_labels(labels, pg),
        "iterations": it,
        "converged": converged,
        "active_tiles": active,
        "dynamic_skipped_tile_fraction": fractions,
        "mean_dynamic_skipped_tile_fraction": (
            float(np.mean(fractions)) if fractions else 0.0
        ),
        "dense_iterations": dense_iters,
        "direction": directions,
        "push_iterations": directions.count("push"),
    }


class _Hashable:
    """Identity-hashed wrapper so dataclasses with arrays can be static args."""

    def __init__(self, obj):
        self._obj = obj
        self._device = {}  # id(host array) -> its device copy

    def device_array(self, host):
        """The device copy of one of the wrapped object's host arrays,
        uploaded on first use."""
        hit = self._device.get(id(host))
        if hit is None:
            # waits for the copy, so the span times the whole upload
            with telemetry.span("engine.upload", bytes=int(host.nbytes)):
                hit = self._device[id(host)] = jnp.asarray(host).block_until_ready()
        return hit

    def __getattr__(self, name):
        return getattr(self._obj, name)

    def __hash__(self):
        return id(self._obj)

    def __eq__(self, other):
        return isinstance(other, _Hashable) and self._obj is other._obj
