"""Plain coloring: what a speculative greedy coloring has to give.

Independent of the program: numpy only, on the host CSR.  A speculative
coloring's colors depend on which assigns shared a round, so the run is
held to the properties every greedy coloring has, and the tests compare
it exactly with a sequential greedy where no two assigns share a round:

* proper: no edge joins two vertices of one color;
* complete: every vertex is colored (``-1`` marks an uncolored one);
* first-fit: a vertex takes the smallest color its neighbours left free
  when it picked, so its color is never above its degree.
"""
from __future__ import annotations

import numpy as np


def conflict_edges(row_ptr: np.ndarray, col_idx: np.ndarray,
                   colors: np.ndarray) -> int:
    """CSR entries whose two ends are colored alike (each undirected edge
    counts from both ends)."""
    src = np.repeat(colors, np.diff(row_ptr))
    return int(np.count_nonzero((src >= 0) & (src == colors[col_idx])))


def uncolored(colors: np.ndarray) -> int:
    return int(np.count_nonzero(colors < 0))


def colors_above_degree(row_ptr: np.ndarray, colors: np.ndarray) -> int:
    """Vertices whose color is past their degree: no first-fit pick can
    be, since ``degree`` neighbours leave one of colors 0..degree free."""
    return int(np.count_nonzero(colors > np.diff(row_ptr)))


def first_fit(row_ptr: np.ndarray, col_idx: np.ndarray) -> np.ndarray:
    """int32 colors of the sequential greedy in vertex order: each vertex
    takes the smallest color none of its already colored neighbours has."""
    n = row_ptr.shape[0] - 1
    colors = np.full(n, -1, np.int32)
    for v in range(n):
        taken = colors[col_idx[row_ptr[v]:row_ptr[v + 1]]]
        free = np.ones(taken.shape[0] + 1, bool)
        free[taken[(taken >= 0) & (taken <= taken.shape[0])]] = False
        colors[v] = np.argmax(free)
    return colors
