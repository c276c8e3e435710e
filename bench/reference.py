"""Plain references the benchmark judges the program by, and their controls.

Copied from ``repro.core.reference`` (the ones the cells need) and reduced
to plain arrays, so they import nothing of the program and take nothing it
made: the edge list is the benchmark's own generator output. BFS and
connected components run scipy's graph traversals; PageRank is a float64
power iteration on a scipy sparse matrix.

The controls are the same references with one guarantee of the
configuration broken, the step a later change might be tempted to take:

* ``bfs_one_way``: each undirected edge traversed in one direction only
  (the stored stream halved), so hop distances stop being the
  undirected graph's;
* ``pagerank_lowp``: the PageRank iteration computed in bfloat16, the
  precision below the float32 the configuration states.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse import csgraph

INF_U32 = np.uint32(0xFFFFFFFF)


def _adjacency(src, dst, n) -> sp.csr_matrix:
    ones = np.ones(src.shape[0], dtype=np.float32)
    return sp.csr_matrix((ones, (src, dst)), shape=(n, n))


def bfs_levels(src, dst, n, roots, block: int = 16) -> np.ndarray:
    """Hop distance along ``src -> dst`` from each root: (len(roots), n)
    uint32, ``INF_U32`` where unreachable. Roots go in blocks so that the
    float64 distance matrix stays small."""
    adj = _adjacency(src, dst, n)
    roots = np.asarray(roots, dtype=np.int64)
    out = np.empty((roots.shape[0], n), dtype=np.uint32)
    for i in range(0, roots.shape[0], block):
        d = csgraph.shortest_path(
            adj, method="D", unweighted=True, indices=roots[i : i + block]
        )
        fin = np.isfinite(d)
        blk = np.full(d.shape, INF_U32, dtype=np.uint32)
        blk[fin] = d[fin].astype(np.uint32)
        out[i : i + block] = blk
    return out


def bfs_one_way(src, dst, n, roots) -> np.ndarray:
    """Control: BFS over one direction of each undirected edge."""
    keep = src <= dst
    return bfs_levels(src[keep], dst[keep], n, roots)


def component_labels(src, dst, n) -> np.ndarray:
    """Weakly connected component id of every vertex (int32)."""
    _, comp = csgraph.connected_components(
        _adjacency(src, dst, n), directed=True, connection="weak"
    )
    return comp.astype(np.int32)


def pagerank(src, dst, n, damping: float, tol: float, max_iters: int = 1000):
    """Power iteration in float64, no dangling redistribution: stops once
    the largest change is under ``tol``. Returns float64 ranks."""
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.zeros(n)
    inv[outdeg > 0] = 1.0 / outdeg[outdeg > 0]
    pull = sp.csr_matrix((np.ones(src.shape[0]), (dst, src)), shape=(n, n))
    rank = np.full(n, 1.0 / n)
    for _ in range(max_iters):
        new = (1.0 - damping) / n + damping * (pull @ (rank * inv))
        done = np.max(np.abs(new - rank)) < tol
        rank = new
        if done:
            break
    return rank


def pagerank_lowp(src, dst, n, damping: float, tol: float, max_iters: int = 100):
    """Control: the same iteration with every value in bfloat16 (on the
    default JAX device). Its ranks carry three significant digits, so it
    seldom reaches ``tol``; it stops at ``max_iters``, five times what the
    float64 reference takes at scale 20. Returns float64 ranks."""
    import jax
    import jax.numpy as jnp

    dt = jnp.bfloat16

    @jax.jit
    def iterate(s, d):
        deg = jnp.zeros(n, dt).at[s].add(jnp.ones((), dt))
        inv = jnp.where(deg > 0, 1 / jnp.maximum(deg, 1), 0).astype(dt)
        base = jnp.asarray((1.0 - damping) / n, dt)

        def cond(c):
            _, it, done = c
            return jnp.logical_and(~done, it < max_iters)

        def body(c):
            rank, it, _ = c
            acc = jax.ops.segment_sum((rank * inv)[s], d, num_segments=n)
            new = (base + jnp.asarray(damping, dt) * acc.astype(dt)).astype(dt)
            done = jnp.max(jnp.abs(new - rank).astype(jnp.float32)) < tol
            return new, it + 1, done

        rank0 = jnp.full(n, 1.0 / n, dt)
        return jax.lax.while_loop(cond, body, (rank0, jnp.int32(0), jnp.bool_(False)))[0]

    rank = iterate(jnp.asarray(src.astype(np.int32)), jnp.asarray(dst.astype(np.int32)))
    return np.asarray(rank.astype(jnp.float32)).astype(np.float64)


def rank_rel_err(got, want) -> float:
    """Largest relative gap of a rank against the reference's; every rank
    of the reference is at least (1 - d) / n > 0."""
    got = np.asarray(got, dtype=np.float64)
    return float(np.max(np.abs(got - want) / want))
