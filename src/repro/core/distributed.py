"""Multi-device GraphScale engine: shard_map + phased all-gather crossbar,
streaming the COMPRESSED per-channel edge layout.

Mapping (docs/distributed.md §1): one mesh device per graph core / memory
channel. Vertex labels are sharded over the ``graph`` mesh axis; at phase
``m`` every device contributes its active sub-interval to an ``all_gather``
(``crossbar_exchange``) — the bulk-ICI equivalent of the paper's two-level
vertex-label crossbar — and then serves all of its edge label reads from that
local gathered block (the scratch pad).

What each channel streams is the point (docs/distributed.md §3): the
per-device edge constants are this core's shard of the partition-time packed
stream — ``tile_word``/``tile_word_hi`` bit-packed index words, the
scalar-prefetched ``tile_counts`` that skip padding tiles, and the fold
arrays ``fold_rows``/``fold_pos`` — exactly the arrays ``PartitionedGraph.channel_arrays()``
hands the single-process engine. Phase processing is literally the same
function (``engine.channel_phase_reduce_pallas``, the one Pallas phase-reduce
implementation) invoked with a channel axis of 1, so every compression win
(4-8 index B/edge, tile skipping, two-level hub reduce) crosses each
channel's HBM unchanged; the flat (l, E_pad) src/dst/valid arrays are never
shipped to, or materialized on, any device (jaxpr-asserted in
tests/test_distributed_equiv.py).

The engine is numerically the single-process engine run with p remote
channels: min problems are bit-identical, sum problems (PageRank) agree to
float reassociation (tested in tests/test_distributed_equiv.py — the
equivalence suite that keeps this docstring honest).

Multi-query lane batching rides through unchanged (docs/tile_layout.md §8):
a lane-batched label shard is (1, Vl, L) — the squeeze/re-expand rules and
the axis-0 ``crossbar_exchange`` are lane-oblivious, so each phase
all-gathers (sub, L) payload rows and one ``channel_phase_reduce_pallas``
launch per channel updates all K queries. The dynamic-skip frontier words
are the UNION over lanes (built inside ``make_iteration``), and both the
density popcount and the convergence check are psum'd exactly as in the
laneless engine, so every channel takes the same branch while individual
lanes converge at different iterations (tests/test_multi_query.py).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import frontier_words as fwords
from repro.core.engine import (
    EngineOptions,
    EngineResult,
    channel_phase_reduce_pallas,
    channel_phase_scatter_pallas,
    dynamic_skip_enabled,
    make_iteration,
    phase_consts_at,
    prepare_labels,
    push_enabled,
    unpad_labels,
)
from repro.core.partition import PartitionedGraph
from repro.core.problems import Problem

__all__ = [
    "crossbar_exchange",
    "place_channel_shards",
    "shard_labels",
    "build_distributed_run",
    "run_distributed",
]

# fixed flattening order for the packed per-channel constants (shard_map takes
# positional args; None entries are elided per problem/partition). The push_*
# entries are the source-binned scatter stream for direction-optimizing
# traversal (docs/tile_layout.md §9) — dropped by channel_arrays for sum
# problems, exactly like coverage.
_CONST_KEYS = (
    "word", "word_hi", "counts", "w", "fold_rows", "fold_pos", "coverage",
    "push_word", "push_word_hi", "push_counts", "push_w", "push_coverage",
)


def crossbar_exchange(sub_payload: jnp.ndarray, axis: str) -> jnp.ndarray:
    """The two-level crossbar, TPU edition (docs/distributed.md §2): replicate
    the p active sub-intervals so every later label read is a local (VMEM)
    gather.

    ``sub_payload``: this device's active sub-interval, (sub, ...) floats/ints.
    Returns the gathered block (p * sub, ...).
    """
    return jax.lax.all_gather(sub_payload, axis, axis=0, tiled=True)


def place_channel_shards(
    problem: Problem, pg: PartitionedGraph, mesh: Mesh, axis: str = "graph"
) -> Dict[str, jnp.ndarray]:
    """NamedSharding-place the packed per-channel edge stream: the core axis
    of every ``channel_arrays()`` entry becomes the ``axis`` mesh axis, so
    device q holds exactly core q's compressed shard (ragged R/T already
    padded uniform at partition time; the weight-streaming rule lives in
    ``channel_arrays`` itself)."""
    arrs = pg.channel_arrays(problem)
    out = {}
    for k, v in arrs.items():
        if v is None:
            out[k] = None
        else:
            spec = P(axis, *([None] * (v.ndim - 1)))
            out[k] = jax.device_put(jnp.asarray(v), NamedSharding(mesh, spec))
    return out


def _label_specs(labels, axis):
    # read ndim off the value itself (works for np/jnp arrays AND tracers —
    # jax.make_jaxpr traces through run_fn.traceable; np.asarray would throw)
    return {
        k: (P(axis) if getattr(v, "ndim", 0) >= 1 else P())
        for k, v in labels.items()
    }


def build_distributed_run(
    problem: Problem,
    pg: PartitionedGraph,
    mesh: Mesh,
    axis: str = "graph",
    opts: EngineOptions = EngineOptions(),
):
    """Returns run_fn(labels) -> (labels, iters, changed); labels pre-sharded
    over ``axis``. ``run_fn.traceable`` is the un-jitted composite (constants
    bound) for jaxpr inspection by the equivalence suite."""
    if opts.backend != "pallas":
        raise ValueError(
            "run_distributed streams the compressed per-channel layout and "
            "has exactly one phase-reduce implementation — the Pallas one; "
            f"backend={opts.backend!r} has no distributed variant (run the "
            "XLA oracle through core.engine.run instead)"
        )
    consts = place_channel_shards(problem, pg, mesh, axis)  # raises if no tiles
    const_keys = tuple(k for k in _CONST_KEYS if consts[k] is not None)
    const_vals = tuple(consts[k] for k in const_keys)
    sub_size = pg.sub_size
    dyn = dynamic_skip_enabled(problem, pg, opts)

    def body(labels, *cvals):
        # shard_map blocks keep a leading core dim of size 1 -> squeeze labels
        # to this device's (Vl,) shard; the packed constants KEEP theirs (the
        # channel phase reduce runs the (n=1, R, T) kernel grid directly).
        labels = {
            k: (v[0] if getattr(v, "ndim", 0) >= 1 and v.shape[0] == 1 else v)
            for k, v in labels.items()
        }
        cm_all = dict(zip(const_keys, cvals))
        cm_all.update({k: None for k in _CONST_KEYS if k not in const_keys})
        # coverage feeds the active-tile schedule below, not the phase reduce
        coverage = cm_all.pop("coverage")
        # the push stream never enters the pull phase reduce: pop it and
        # re-key to the canonical stream names for the scatter primitive.
        push_cm = {
            "word": cm_all.pop("push_word"),
            "word_hi": cm_all.pop("push_word_hi"),
            "counts": cm_all.pop("push_counts"),
            "w": cm_all.pop("push_w"),
        }
        push_coverage = cm_all.pop("push_coverage")

        def reduce_at_phase(m, labels_local, active=None):
            payload = problem.src_transform(labels_local)  # (Vl,) elementwise
            sub = jax.lax.dynamic_slice_in_dim(
                payload, m * sub_size, sub_size, axis=0
            )
            gathered = crossbar_exchange(sub, axis)  # (G,) scratch pad
            reduced = channel_phase_reduce_pallas(
                problem, pg, gathered, phase_consts_at(cm_all, m), opts, active
            )  # (1, Vl)
            return reduced[0]

        phase_active = density_fn = None
        if dyn:
            counts = cm_all["counts"]  # (1, l, R) this channel's shard

            def phase_active(m, live_fw, use_dense):
                # the per-channel frontier words ride the SAME crossbar as
                # the labels: all-gathering the p phase-m (Ws,) slices in
                # core order yields exactly the gathered-block word layout
                # the coverage bitmaps index (docs/tile_layout.md §7).
                cov_m = jax.lax.dynamic_index_in_dim(
                    coverage, m, axis=1, keepdims=False
                )  # (1, R, T, Wc)
                cnt_m = jax.lax.dynamic_index_in_dim(
                    counts, m, axis=1, keepdims=False
                )  # (1, R)
                local = jax.lax.dynamic_index_in_dim(
                    live_fw, m, axis=-2, keepdims=False
                )  # (Ws,)
                gfw = crossbar_exchange(local, axis)  # (p * Ws,)
                return fwords.frontier_active_tiles(cov_m, gfw, cnt_m, use_dense)

            def density_fn(fw):
                # GLOBAL popcount: every channel sees the same density and
                # takes the same lax.cond branch (collectives inside the
                # dense/dynamic arms must line up across devices).
                return jax.lax.psum(fwords.frontier_popcount(fw), axis)

        push_on = push_enabled(problem, pg, opts)
        push_reduce_at_phase = push_phase_active = None
        if push_on:

            def push_reduce_at_phase(m, labels_local, active):
                payload = problem.src_transform(labels_local)
                sub = jax.lax.dynamic_slice_in_dim(
                    payload, m * sub_size, sub_size, axis=0
                )
                gathered = crossbar_exchange(sub, axis)
                reduced = channel_phase_scatter_pallas(
                    problem, pg, gathered, phase_consts_at(push_cm, m), opts,
                    active,
                )  # (1, Vl)
                return reduced[0]

            def push_phase_active(m, live_fw):
                cov_m = jax.lax.dynamic_index_in_dim(
                    push_coverage, m, axis=1, keepdims=False
                )  # (1, B, Tp, Wc)
                cnt_m = jax.lax.dynamic_index_in_dim(
                    push_cm["counts"], m, axis=1, keepdims=False
                )  # (1, B)
                local = jax.lax.dynamic_index_in_dim(
                    live_fw, m, axis=-2, keepdims=False
                )  # (Ws,)
                gfw = crossbar_exchange(local, axis)  # (p * Ws,)
                return fwords.frontier_active_tiles(cov_m, gfw, cnt_m, None)

            def push_phase_live(m, live_fw):
                # phase-level skip, collective edition: the GLOBAL any() via
                # psum so every channel takes the same lax.cond branch (the
                # skipped arm elides the crossbar all-gathers, which must
                # line up across devices).
                local = jnp.any(
                    jax.lax.dynamic_index_in_dim(
                        live_fw, m, axis=-2, keepdims=False
                    )
                    != 0
                )
                return jax.lax.psum(local.astype(jnp.int32), axis) > 0

        iteration = make_iteration(
            problem, pg, opts, reduce_at_phase, phase_active, density_fn,
            push_reduce_at_phase=push_reduce_at_phase,
            push_phase_active=push_phase_active,
            push_phase_live=push_phase_live if push_on else None,
        )

        if dyn and push_on:
            # direction-carried loop: the switch reads the PSUM'd popcount
            # (density_fn), so every channel chooses the same direction and
            # the crossbar collectives inside each arm line up.

            def cond(carry):
                _, _, it, changed, _ = carry
                return jnp.logical_and(changed, it < opts.max_iters)

            def step(carry):
                labels, fw, it, _, dirp = carry
                new, nf, dirn = iteration(labels, fw, dirp)
                changed = (
                    jax.lax.psum(
                        jnp.any(nf != jnp.uint32(0)).astype(jnp.int32), axis
                    )
                    > 0
                )
                return new, nf, it + 1, changed, dirn

            fw0 = fwords.full_frontier_words(pg.l, sub_size)  # (l, Ws) local
            labels, _, iters, changed, _ = jax.lax.while_loop(
                cond, step,
                (labels, fw0, jnp.int32(0), jnp.bool_(True), jnp.bool_(False)),
            )
        elif dyn:

            def cond(carry):
                _, _, it, changed = carry
                return jnp.logical_and(changed, it < opts.max_iters)

            def step(carry):
                labels, fw, it, _ = carry
                new, nf = iteration(labels, fw)
                changed = (
                    jax.lax.psum(
                        jnp.any(nf != jnp.uint32(0)).astype(jnp.int32), axis
                    )
                    > 0
                )  # free convergence check: stop when EVERY frontier is empty
                return new, nf, it + 1, changed

            fw0 = fwords.full_frontier_words(pg.l, sub_size)  # (l, Ws) local
            labels, _, iters, changed = jax.lax.while_loop(
                cond, step, (labels, fw0, jnp.int32(0), jnp.bool_(True))
            )
        else:

            def cond(carry):
                _, it, changed = carry
                return jnp.logical_and(changed, it < opts.max_iters)

            def step(carry):
                labels, it, _ = carry
                new = iteration(labels)
                local_changed = problem.not_converged(labels, new)
                changed = (
                    jax.lax.psum(local_changed.astype(jnp.int32), axis) > 0
                )  # cores agree to stop only when NO core changed
                return new, it + 1, changed

            labels, iters, changed = jax.lax.while_loop(
                cond, step, (labels, jnp.int32(0), jnp.bool_(True))
            )
        labels = {
            k: (
                v[None]
                if getattr(v, "ndim", 0) >= 1 and v.shape[0] == pg.vertices_per_core
                else v
            )
            for k, v in labels.items()
        }
        return labels, iters, changed

    def make_fn(labels):
        in_specs = (
            _label_specs(labels, axis),
            *(P(axis, *([None] * (v.ndim - 1))) for v in const_vals),
        )
        out_specs = (_label_specs(labels, axis), P(), P())
        return jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
        )

    # jit once per label-tree structure: a fresh jax.jit around a fresh
    # shard_map closure on every call would retrace + recompile every time
    # (caught because the channel-scaling bench timed compiles, not steps)
    jitted: dict = {}

    def run_fn(labels):
        key = tuple(sorted((k, getattr(v, "ndim", 0)) for k, v in labels.items()))
        fn = jitted.get(key)
        if fn is None:
            fn = jitted[key] = jax.jit(make_fn(labels))
        return fn(labels, *const_vals)

    run_fn.traceable = lambda labels: make_fn(labels)(labels, *const_vals)
    run_fn.const_keys = const_keys
    return run_fn


def shard_labels(labels: Dict, mesh: Mesh, axis: str = "graph") -> Dict:
    """device_put a prepare_labels() dict over the mesh: core axis of every
    (p, Vl) array -> the ``axis`` mesh axis, scalars replicated."""
    return {
        k: jax.device_put(
            v, NamedSharding(mesh, P(axis) if getattr(v, "ndim", 0) >= 1 else P())
        )
        for k, v in labels.items()
    }


def run_distributed(
    problem: Problem,
    g,
    pg: PartitionedGraph,
    mesh: Mesh,
    axis: str = "graph",
    opts: EngineOptions = EngineOptions(),
) -> EngineResult:
    """Convenience end-to-end: init labels, shard, run, unpad."""
    assert pg.p == mesh.shape[axis], (pg.p, dict(mesh.shape))
    sharded = shard_labels(prepare_labels(problem, g, pg), mesh, axis)
    run_fn = build_distributed_run(problem, pg, mesh, axis, opts)
    out, iters, changed = run_fn(sharded)
    return EngineResult(
        labels=unpad_labels({k: np.asarray(v) for k, v in out.items()}, pg),
        iterations=int(iters),
        converged=not bool(changed),
    )
