"""Subprocess child for honest partition-build RSS / wall / agreement records.

Peak RSS (``VmHWM``) is a process-wide HIGH-WATER mark, so a build measured
inside a long-lived bench process inherits every earlier allocation peak.
This child exists to measure one streaming build from a cold start: baseline
RSS is
snapshotted after imports, the build runs, and the peak is snapshotted BEFORE
any comparison/engine work (later allocations cannot retroactively raise the
captured number). The bounded-memory acceptance ratio is

    rss_over_footprint = (peak_rss - baseline_rss) / memory_report().total

i.e. build-attributable memory over the final resident partition footprint —
``partition_2d_streaming``'s O(chunk + largest bucket) transient claim means
this stays well under the 4x ceiling where the in-memory path's O(E) edge
materialization would blow through it.

Optional phases, run strictly AFTER the RSS snapshot:
  --compare   materialize the same stream in RAM, build ``partition_2d``, and
              check bit-identity of every packed/flat array (the streaming
              contract, docs/tile_layout.md §11).
  --engine    run BFS (K=1) and lane-batched BFS (each K in --k-lanes) on the
              XLA backend; with --compare the labels from the streaming-built
              and in-memory-built partitions must agree. Reports MTEPS per
              point — the mteps_vs_scale suite's engine numbers.

Prints one JSON object on the last stdout line (the parent parses it).
"""
from __future__ import annotations

import argparse
import json
import resource
import time

import numpy as np

from repro.core.partition import (
    PartitionConfig,
    partition_2d,
    partition_2d_streaming,
)
from repro.data.rmat import materialize, rmat_chunks

# fields whose bit-identity defines streaming == in-memory (None-ness must
# match too; config carries no arrays and is compared by value elsewhere)
_IDENTITY_FIELDS = (
    "src_gidx", "dst_lidx", "valid", "weights", "bucket_sizes",
    "tile_word", "tile_word_hi", "tile_counts", "tile_weights",
    "tile_coverage", "tile_fold_rows", "tile_fold_pos",
    "push_word", "push_word_hi", "push_counts", "push_weights",
    "push_coverage",
)


def _rss_mb() -> float:
    # VmHWM, not ru_maxrss: Linux carries ru_maxrss across fork+exec, so a
    # child spawned from a fat bench parent would inherit the PARENT'S peak
    # and report a zero build delta. VmHWM lives in the mm and resets on
    # exec — the cold-start number this child exists to measure.
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def bit_identical(a, b) -> bool:
    for name in _IDENTITY_FIELDS:
        va, vb = getattr(a, name), getattr(b, name)
        if (va is None) != (vb is None):
            return False
        if va is not None and not np.array_equal(np.asarray(va), np.asarray(vb)):
            return False
    return (
        a.p == b.p and a.l == b.l and a.sub_size == b.sub_size
        and a.num_edges == b.num_edges and a.src_bits == b.src_bits
        and a.split_rows == b.split_rows and a.push_block == b.push_block
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=int, required=True)
    ap.add_argument("--edge-factor", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--p", type=int, default=2)
    ap.add_argument("--l", type=int, default=2)
    ap.add_argument("--tile-vb", type=int, default=None)
    ap.add_argument("--chunk-edges", type=int, default=1 << 18)
    ap.add_argument("--no-push", action="store_true",
                    help="pull-only layout (halves packed bytes at scale)")
    ap.add_argument("--memmap-dir", default=None,
                    help="np.memmap the large outputs under this directory")
    ap.add_argument("--compare", action="store_true",
                    help="bit-identity check vs the in-memory partition_2d")
    ap.add_argument("--engine", action="store_true",
                    help="run XLA-backend BFS points (with --compare: "
                         "cross-partition label agreement)")
    ap.add_argument("--k-lanes", default="1",
                    help="comma list of lane widths for --engine (e.g. 1,16)")
    ap.add_argument("--assert-rss-ratio", type=float, default=None,
                    help="fail unless (peak - baseline) / footprint < R")
    ap.add_argument("--assert-rss-delta-mb", type=float, default=None,
                    help="fail unless peak - baseline < M MB")
    args = ap.parse_args()

    stream = rmat_chunks(
        args.scale, args.edge_factor, seed=args.seed,
        chunk_edges=args.chunk_edges,
    )
    cfg = PartitionConfig(
        p=args.p, l=args.l, tile_vb=args.tile_vb,
        build_push=not args.no_push,
    )

    # warm numpy's allocator on a toy build so first-touch pool growth is
    # charged to the baseline, not to the measured build (toy-sized config:
    # the real one may carry a tile_vb larger than the toy graph's vpc)
    warm_cfg = PartitionConfig(p=2, l=2, build_push=not args.no_push)
    partition_2d_streaming(rmat_chunks(6, 4, seed=0), 1 << 6, warm_cfg)
    rss0 = _rss_mb()
    t0 = time.perf_counter()
    pg = partition_2d_streaming(
        stream, stream.num_vertices, cfg, memmap_dir=args.memmap_dir
    )
    build_s = time.perf_counter() - t0
    rss1 = _rss_mb()  # peak up to HERE: later phases cannot raise it

    rep = pg.memory_report()
    footprint_mb = rep["total_bytes"] / 1e6
    delta_mb = max(rss1 - rss0, 0.0)
    ratio = delta_mb / max(footprint_mb, 1e-9)
    rec = {
        "scale": args.scale,
        "edge_factor": args.edge_factor,
        "V": stream.num_vertices,
        "E": stream.num_edges,
        "p": pg.p, "l": pg.l, "tile_vb": pg.tile_vb,
        "src_bits": pg.src_bits,
        "chunk_edges": args.chunk_edges,
        "memmap": args.memmap_dir is not None,
        "partition_build_s": build_s,
        "rss_baseline_mb": rss0,
        "peak_rss_mb": rss1,
        "rss_delta_mb": delta_mb,
        "footprint_mb": footprint_mb,
        "device_mb": rep["device_total_bytes"] / 1e6,
        "device_bytes_per_edge": rep["device_bytes_per_edge"],
        "bytes_per_edge": rep["bytes_per_edge"],
        "rss_over_footprint": ratio,
        "bit_identical": None,
        "points": None,
    }
    if args.assert_rss_ratio is not None:
        assert ratio < args.assert_rss_ratio, (
            f"streaming build used {delta_mb:.0f} MB over a "
            f"{footprint_mb:.0f} MB footprint ({ratio:.2f}x >= "
            f"{args.assert_rss_ratio}x ceiling)"
        )
    if args.assert_rss_delta_mb is not None:
        assert delta_mb < args.assert_rss_delta_mb, (
            f"streaming build RSS delta {delta_mb:.0f} MB exceeds the "
            f"{args.assert_rss_delta_mb:.0f} MB ceiling"
        )

    pg_mem = None
    if args.compare:
        g = materialize(stream)
        pg_mem = partition_2d(g, cfg)
        rec["bit_identical"] = bool(bit_identical(pg, pg_mem))
        assert rec["bit_identical"], (
            "streaming build diverged from partition_2d"
        )

    if args.engine:
        # deferred: jax import + engine runs happen after the RSS snapshot
        import types

        from benchmarks.common import mteps, time_call
        from repro.core.engine import EngineOptions, run
        from repro.core.problems import bfs, bfs_multi
        from repro.data.synthetic import query_workload

        # label init only reads num_vertices for BFS-family problems; the
        # full edge list never needs to exist in this process (that is the
        # point of the streaming build)
        gv = types.SimpleNamespace(num_vertices=stream.num_vertices)
        opts = EngineOptions(backend="xla")
        ks = [int(k) for k in args.k_lanes.split(",")]
        roots = query_workload(max(max(ks), 1), stream.num_vertices, seed=0)
        # K=1 traverses from the modal source of the first chunk — a random
        # root on an unsymmetrized RMAT is often isolated (1-iteration BFS
        # makes the MTEPS point degenerate); multi-lane keeps the random
        # workload (the union frontier is live as long as any lane is)
        s0 = np.asarray(next(iter(stream()))[0])
        hub = int(np.bincount(s0, minlength=stream.num_vertices).argmax())
        points = []
        for k in ks:
            prob = bfs(hub) if k == 1 else bfs_multi(
                [int(r) for r in roots[:k]]
            )
            res = run(prob, gv, pg, opts)
            agree = None
            if pg_mem is not None:
                res_m = run(prob, gv, pg_mem, opts)
                key = "label" if k == 1 else "dist"
                agree = bool(
                    np.array_equal(
                        np.asarray(res.labels[key]),
                        np.asarray(res_m.labels[key]),
                    )
                ) and res.iterations == res_m.iterations
                assert agree, f"K={k} labels diverged across build paths"
            t = time_call(lambda: run(prob, gv, pg, opts))
            points.append({
                "K": k,
                "iterations": int(res.iterations),
                "us": t * 1e6,
                "mteps": mteps(stream.num_edges * k, t),
                "agreement": agree,
            })
        rec["points"] = points

    print(json.dumps(rec))


if __name__ == "__main__":
    main()
