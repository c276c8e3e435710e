"""The benchmark's seeded graph generator.

A copy of the program's Kronecker stream (``repro.data.rmat``), kept here so
that a change to the program cannot move the yardstick, with the step of the
Graph500 generator that the program's lacks: a seeded relabelling of the
vertices. ``bench/tests`` checks that, before that step, it still gives the
program's output bit for bit.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np


@dataclasses.dataclass(frozen=True)
class RMATStream:
    """Replayable chunked Graph500 Kronecker (R-MAT) edge stream.

    Calling the stream yields ``(src, dst[, weights])`` chunks; chunk ``i``
    is a pure function of ``(seed, i)``, so every pass replays the same
    edges. ``symmetric`` appends each chunk's reversed edges, with the same
    weight both ways (an undirected graph).

    As the Graph500 reference generator does, every vertex ID goes through
    one random permutation drawn from ``seed`` (``vertex_perm``): the
    Kronecker bits alone put the hubs at the lowest IDs, with degree falling
    with the number of set bits, and a layout would be judged on that order.
    The reference also shuffles the edge list; the edges here are drawn
    independently, so their order carries nothing to shuffle away."""

    scale: int
    edge_factor: int = 16
    a: float = 0.57
    b: float = 0.19
    c: float = 0.19
    seed: int = 0
    chunk_edges: int = 1 << 18
    symmetric: bool = False
    weighted: bool = False

    @property
    def num_vertices(self) -> int:
        return 1 << self.scale

    @property
    def base_edges(self) -> int:
        """Input edge tuples (before symmetrization)."""
        return self.num_vertices * self.edge_factor

    @property
    def num_chunks(self) -> int:
        return -(-self.base_edges // self.chunk_edges)

    @functools.cached_property
    def vertex_perm(self) -> np.ndarray:
        """(V,) int64: the new ID of each Kronecker vertex."""
        rng = np.random.default_rng(np.random.SeedSequence(self.seed, spawn_key=(1,)))
        return rng.permutation(self.num_vertices)

    def _chunk(self, idx: int):
        start = idx * self.chunk_edges
        m = min(self.chunk_edges, self.base_edges - start)
        rng = np.random.default_rng([self.seed, idx])
        src = np.zeros(m, dtype=np.int64)
        dst = np.zeros(m, dtype=np.int64)
        ab, abc = self.a + self.b, self.a + self.b + self.c
        for _bit in range(self.scale):
            r = rng.random(m)
            src_bit = (r >= ab).astype(np.int64)
            dst_bit = (((r >= self.a) & (r < ab)) | (r >= abc)).astype(np.int64)
            src = (src << 1) | src_bit
            dst = (dst << 1) | dst_bit
        src, dst = self.vertex_perm[src], self.vertex_perm[dst]
        w = rng.random(m).astype(np.float32) if self.weighted else None
        if self.symmetric:
            src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
            w = np.concatenate([w, w]) if w is not None else None
        return (src, dst, w) if w is not None else (src, dst)

    def __call__(self):
        for idx in range(self.num_chunks):
            yield self._chunk(idx)


def materialize(stream: RMATStream):
    """The stream's edges in stream order: ``(src u32, dst u32, w f32|None)``."""
    chunks = list(stream())
    src = np.concatenate([ch[0] for ch in chunks]).astype(np.uint32)
    dst = np.concatenate([ch[1] for ch in chunks]).astype(np.uint32)
    w = (
        np.concatenate([ch[2] for ch in chunks]).astype(np.float32)
        if stream.weighted else None
    )
    return src, dst, w

