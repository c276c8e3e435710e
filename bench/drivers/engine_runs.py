"""Back-to-back whole-graph runs through ``engine.run`` (Graph500 kernel 2,
PageRank).

Traffic parameters: ``problem`` (``bfs`` or ``pagerank``), for PageRank
``damping`` and ``tol``, and ``limits`` for the check. BFS roots are the
configuration's ``search_keys`` Graph500 search keys: drawn from its
``graph_seed`` among the vertices with an edge other than a self-loop, one
set per graph as the specification draws them. ``--seed`` orders them, and
the window runs whole rounds of them, so every seed does the same work in
another order. On one TPU v5e, roots drawn anew per seed spread a window's
time by 3.4% between seeds, and a last round cut short by the clock by
about 1%. PageRank takes nothing from the seed; a round is one run.

The window starts rounds while its time is not up and waits for the last
one to end; ``mteps`` credits a BFS run with the input edge tuples of its root's
component and a PageRank run with all of them (Graph500 TEPS), over the time
from the window's start to the end of its last run.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference, stats


class Driver:
    def __init__(self, ctx):
        from repro.core import engine, problems

        self.ctx = ctx
        self.engine = engine
        self.kind = ctx.traffic["problem"]
        g = ctx.graph
        self.coo, self.pg = g.coo(), g.pg
        self.opts = engine.EngineOptions()
        if self.kind == "bfs":
            deg = np.bincount(g.src[g.src != g.dst], minlength=g.num_vertices)
            keys = np.random.default_rng(
                np.random.SeedSequence([ctx.config["graph_seed"], 1])
            ).choice(np.flatnonzero(deg > 0), ctx.config["search_keys"], replace=False)
            rng = np.random.default_rng(np.random.SeedSequence([ctx.seed, 1]))
            self.roots = rng.permutation(keys)
            self.make = problems.bfs
            # one template: the jit key; each root enters through its labels
            self.template = problems.bfs(int(self.roots[0]))
        elif self.kind == "pagerank":
            t = ctx.traffic
            self.template = problems.pagerank(damping=t["damping"], tol=t["tol"])
        else:
            raise ValueError(f"engine_runs has no problem {self.kind!r}")
        self.runs: list[dict] = []

    def _labels(self, root):
        if self.kind == "bfs":
            return self.engine.prepare_labels(self.make(int(root)), self.coo, self.pg)
        return self.engine.prepare_labels(self.template, self.coo, self.pg)

    def setup(self):
        """Compile the one program the window runs, on inputs that converge
        at once: BFS labels with no root, PageRank with no out-edges."""
        import jax.numpy as jnp

        labels = self._labels(self.roots[0] if self.kind == "bfs" else None)
        if self.kind == "bfs":
            labels = {"label": jnp.full_like(labels["label"], reference.INF_U32)}
        else:
            labels = dict(labels, inv_deg=jnp.zeros_like(labels["inv_deg"]))
        self.engine.run(self.template, self.coo, self.pg, self.opts, labels=labels)

    def window(self, seconds: float) -> dict:
        spans, g = self.ctx.spans, self.ctx.graph
        t0 = time.perf_counter()
        per_round = len(self.roots) if self.kind == "bfs" else 1
        while time.perf_counter() - t0 < seconds or len(self.runs) % per_round:
            root = self.roots[len(self.runs) % len(self.roots)] if self.kind == "bfs" else None
            with spans.span("run") as rec:
                with spans.span("prepare_labels"):
                    labels = self._labels(root)
                with spans.span("engine_run"):
                    res = self.engine.run(self.template, self.coo, self.pg,
                                          self.opts, labels=labels)
            edges = (int(g.comp_edges[g.comp[root]]) if self.kind == "bfs"
                     else g.input_edges)
            self.runs.append(dict(root=root, edges=edges, t1=rec["t1"],
                                  iterations=res.iterations,
                                  converged=res.converged,
                                  labels=res.labels["label"]))
        end = self.runs[-1]["t1"]
        return {
            "metrics": {"mteps": stats.mteps(sum(r["edges"] for r in self.runs), end - t0)},
            "attempted": len(self.runs),
            "failed": sum(not r["converged"] for r in self.runs),
            "runs": self.runs,
        }

    def check(self) -> list:
        """Every run of the window against the reference."""
        g, limits = self.ctx.graph, self.ctx.traffic["limits"]
        unconverged = sum(not r["converged"] for r in self.runs)
        if self.kind == "bfs":
            want = reference.bfs_levels(g.src, g.dst, g.num_vertices,
                                        [r["root"] for r in self.runs])
            wrong = sum(int((r["labels"] != w).sum()) for r, w in zip(self.runs, want))
            return [("wrong_levels", wrong, limits["wrong_levels"]),
                    ("unconverged_runs", unconverged, 0)]
        t = self.ctx.traffic
        want = reference.pagerank(g.src, g.dst, g.num_vertices, t["damping"], t["tol"])
        err = max(reference.rank_rel_err(r["labels"], want) for r in self.runs)
        return [("rank_rel_err", err, limits["rank_rel_err"]),
                ("unconverged_runs", unconverged, 0)]
