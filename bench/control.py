"""The controls of the check that decides ``correct``: the plain reference
with one guarantee broken (``reference.bfs_one_way``) or computed in the
precision below the configuration's (``reference.pagerank_lowp``), put in the
program's place and judged by the cell's own check. Each must come out not
correct. Prints one line per seed with the numbers compared.

    python3 bench/control.py --workload g500-s20-bfs --seeds 1,2,3

Runs at the cell's own size (on the chip, where the bfloat16 PageRank runs
on the device); ``bench/tests`` runs it at a small size.
"""
import argparse
import json
import sys
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

import numpy as np  # noqa: E402

from bench import graphs, harness, reference, tracing  # noqa: E402


def control_checks(bench, workload: str, seed: int, runs: int,
                   cache_dir=graphs.CACHE_DIR) -> list:
    """The cell's check, applied to the control's output for ``seed``."""
    cell = bench.cell(workload)
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    graph = graphs.load_or_build(bench.config_file(cell["config"]), config,
                                 cache_dir=cache_dir)
    ctx = harness.Context(cell=cell, config=config, traffic=traffic, seed=seed,
                          graph=graph, spans=tracing.Spans())
    drv = bench.driver(traffic["driver"]).Driver(ctx)
    g, V = graph, graph.num_vertices
    if traffic["driver"] == "engine_runs" and traffic["problem"] == "bfs":
        roots = drv.roots[:runs]
        levels = reference.bfs_one_way(g.src, g.dst, V, roots)
        drv.runs = [dict(root=r, labels=lv, converged=True) for r, lv in zip(roots, levels)]
    else:
        ranks = reference.pagerank_lowp(g.src, g.dst, V, traffic["damping"], traffic["tol"])
        drv.runs = [dict(labels=ranks.astype(np.float32), converged=True)]
    return drv.check()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--runs", type=int, default=8, help="BFS roots per seed")
    args = ap.parse_args()
    bench = harness.Bench()
    harness.use_checkout_compile_cache(bench.root)
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = control_checks(bench, args.workload, seed, args.runs)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": all(v <= lim for _, v, lim in checks),
                          "checks": {n: {"value": v, "limit": lim} for n, v, lim in checks}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
