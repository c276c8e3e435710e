"""Arithmetic of the benchmark's metrics, kept apart so tests can check it
against hand counts."""
from __future__ import annotations

# the paper's packed pull word per directed edge slot, and one label read
# plus one label write per vertex per iteration
WORD_BYTES = 4
LABEL_BYTES = 8


def mteps(edges: int, seconds: float) -> float:
    """Graph500 TEPS in millions: input edge tuples over wall seconds."""
    return edges / seconds / 1e6


def pull_pass_bytes(directed_slots: int, num_vertices: int) -> int:
    """Least bytes one pull iteration moves: every directed edge slot's
    packed word once, and each vertex label read and written once."""
    return directed_slots * WORD_BYTES + num_vertices * LABEL_BYTES


def least_seconds(nbytes: float, peak_bytes_per_s: float) -> float:
    return nbytes / peak_bytes_per_s
