"""Plain BFS: hop distances by level-synchronous frontier expansion.

Independent of the program: numpy only, on the host CSR.  ``UNREACHED``
marks vertices no path reaches (the program's ``INF``).
"""
from __future__ import annotations

import numpy as np

UNREACHED = 0x7FFFFFFF


def neighbours(row_ptr: np.ndarray, col_idx: np.ndarray,
               rows: np.ndarray) -> np.ndarray:
    """Concatenated adjacency lists of ``rows``."""
    starts = row_ptr[rows].astype(np.int64)
    lens = row_ptr[rows + 1].astype(np.int64) - starts
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, col_idx.dtype)
    offsets = np.repeat(starts - np.cumsum(lens) + lens, lens)
    return col_idx[offsets + np.arange(total)]


def bfs(row_ptr: np.ndarray, col_idx: np.ndarray, root: int) -> np.ndarray:
    """int32 hop distance of every vertex from ``root``."""
    n = row_ptr.shape[0] - 1
    dist = np.full(n, UNREACHED, np.int32)
    dist[root] = 0
    frontier = np.array([root], np.int64)
    level = 0
    while frontier.size:
        level += 1
        nbrs = neighbours(row_ptr, col_idx, frontier)
        nbrs = np.unique(nbrs[dist[nbrs] == UNREACHED])
        dist[nbrs] = level
        frontier = nbrs
    return dist


def label_once(row_ptr: np.ndarray, col_idx: np.ndarray, root: int,
               wavefront: int, seed: int) -> np.ndarray:
    """A control: a relaxed traversal that labels each vertex once.

    It pops up to ``wavefront`` queued vertices at a time in a seeded
    order and gives every unlabelled neighbour the popper's distance + 1,
    never lowering a label later.  That is what the program's speculative
    BFS would return without its re-relaxation: distances of some path,
    not of the shortest one.
    """
    n = row_ptr.shape[0] - 1
    rng = np.random.default_rng(seed)
    dist = np.full(n, UNREACHED, np.int32)
    dist[root] = 0
    queue = np.array([root], np.int64)
    while queue.size:
        take = rng.permutation(queue.size)
        pop, queue = queue[take[:wavefront]], queue[take[wavefront:]]
        lens = (row_ptr[pop + 1] - row_ptr[pop]).astype(np.int64)
        nbrs = neighbours(row_ptr, col_idx, pop)
        cand = np.repeat(dist[pop], lens) + 1
        fresh = dist[nbrs] == UNREACHED
        nbrs, cand = nbrs[fresh], cand[fresh]
        first = np.unique(nbrs, return_index=True)[1]
        nbrs, cand = nbrs[first], cand[first]
        dist[nbrs] = cand
        queue = np.concatenate([queue, nbrs])
    return dist


def narrow(row_ptr: np.ndarray, col_idx: np.ndarray, root: int,
           bits: int = 8) -> np.ndarray:
    """A control: the reference with distances held in ``bits`` unsigned
    bits, so a depth past ``2^bits - 1`` wraps; unreached stays marked."""
    dist = bfs(row_ptr, col_idx, root)
    reached = dist != UNREACHED
    out = dist.copy()
    out[reached] = dist[reached] & ((1 << bits) - 1)
    return out


#: the controls a configuration may name (``control`` in its file)
CONTROLS = {
    "label_once": lambda rp, ci, root, wavefront, seed: label_once(
        rp, ci, root, wavefront, seed),
    "dist_uint8": lambda rp, ci, root, wavefront, seed: narrow(rp, ci, root),
}
