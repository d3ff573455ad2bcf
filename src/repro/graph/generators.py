"""Synthetic graph generators matching the paper's two dataset classes.

The paper evaluates on *scale-free* graphs (soc-LiveJournal, hollywood,
indochina: low diameter, heavy-tailed degrees) and *mesh-like* graphs
(road_usa, roadNet-CA: high diameter, degree <= ~12).  Offline we synthesize
the same two regimes:

  * ``rmat``   — Kronecker/R-MAT scale-free generator (a=0.57 b=c=0.19),
                 heavy-tailed in/out degrees, diameter O(log n).
  * ``grid2d`` — 2D lattice with optional diagonal jitter: max degree 4-8,
                 diameter O(sqrt n) — the road-network stand-in.
  * ``erdos``  — uniform random for property tests.
"""
from __future__ import annotations

import numpy as np

from .csr import CSRGraph, from_edges


def rmat(scale: int, edge_factor: int = 16, seed: int = 0,
         a: float = 0.57, b: float = 0.19, c: float = 0.19) -> CSRGraph:
    """R-MAT scale-free graph with 2**scale vertices."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, dtype=np.int32)
    dst = np.zeros(m, dtype=np.int32)
    r = np.empty(m)
    quadrant = np.empty(m, dtype=np.uint8)
    for bit in range(scale):
        rng.random(out=r)
        # quadrant 0..3 with probabilities a, b, c, d: its high bit is the
        # source bit, its low bit the destination bit (in-place, so a
        # Graph500-size graph does not stream int64 temporaries)
        np.greater_equal(r, a, out=quadrant, casting="unsafe")
        quadrant += r >= a + b
        quadrant += r >= a + b + c
        src <<= 1
        src |= quadrant >> 1
        dst <<= 1
        dst |= quadrant & 1
    return from_edges(n, src, dst, symmetrize=True)


def grid2d(rows: int, cols: int, seed: int = 0, extra_frac: float = 0.0) -> CSRGraph:
    """2D lattice (road-like).  ``extra_frac`` adds random shortcut edges."""
    n = rows * cols
    ids = np.arange(n, dtype=np.int64).reshape(rows, cols)
    right = np.stack([ids[:, :-1].ravel(), ids[:, 1:].ravel()])
    down = np.stack([ids[:-1, :].ravel(), ids[1:, :].ravel()])
    edges = np.concatenate([right, down], axis=1)
    if extra_frac > 0:
        rng = np.random.default_rng(seed)
        k = int(extra_frac * edges.shape[1])
        extra = rng.integers(0, n, size=(2, k))
        edges = np.concatenate([edges, extra], axis=1)
    return from_edges(n, edges[0], edges[1], symmetrize=True)


def erdos(n: int, m: int, seed: int = 0) -> CSRGraph:
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, size=m)
    dst = rng.integers(0, n, size=m)
    return from_edges(n, src, dst, symmetrize=True)


def edge_delta_stream(graph: CSRGraph, num_batches: int, batch_size: int,
                      seed: int = 0, insert_frac: float = 0.5) -> list:
    """Deterministic seeded stream of mixed insert/delete delta batches.

    Walks the evolving *undirected* edge set starting from ``graph``: each
    batch deletes ``~(1 - insert_frac) * batch_size`` existing pairs
    (sampled without replacement) and inserts ``~insert_frac * batch_size``
    currently-absent pairs (rejection-sampled, no self-loops), then emits
    both directions of every pair as one canonical
    :class:`~repro.stream.deltas.EdgeDelta` — so replaying the stream keeps
    the graph symmetric, matching the generators above.  Same
    ``(graph, num_batches, batch_size, seed, insert_frac)`` -> the same
    batches, bit for bit (the CI benches and tests rely on this).
    """
    from ..stream.deltas import make_delta  # lazy: stream imports graph

    if not 0.0 <= insert_frac <= 1.0:
        raise ValueError(f"insert_frac must be in [0, 1], got {insert_frac}")
    n = graph.num_vertices
    rng = np.random.default_rng(seed)
    rp = np.asarray(graph.row_ptr, dtype=np.int64)
    ci = np.asarray(graph.col_idx, dtype=np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64), np.diff(rp))
    # undirected pair keys u*n+v with u < v (self-loops never in the CSR)
    u, v = np.minimum(src, ci), np.maximum(src, ci)
    present = set((u * n + v).tolist())

    n_ins = int(round(batch_size * insert_frac))
    n_del = batch_size - n_ins
    batches = []
    for _ in range(num_batches):
        dels = np.empty(0, dtype=np.int64)
        if n_del and present:
            pool = np.sort(np.fromiter(present, dtype=np.int64))
            dels = rng.choice(pool, size=min(n_del, pool.size),
                              replace=False)
            present.difference_update(dels.tolist())
        ins: list = []
        attempts = 0
        while len(ins) < n_ins and attempts < 64:
            a = rng.integers(0, n, size=2 * (n_ins - len(ins)))
            b = rng.integers(0, n, size=a.size)
            lo, hi = np.minimum(a, b), np.maximum(a, b)
            cand = (lo * n + hi)[lo != hi]
            for k in cand.tolist():
                if k not in present and len(ins) < n_ins:
                    present.add(k)
                    ins.append(k)
            attempts += 1
        keys = np.concatenate([dels, np.asarray(ins, dtype=np.int64)])
        flags = np.concatenate([np.zeros(dels.size, bool),
                                np.ones(len(ins), bool)])
        lo, hi = keys // n, keys % n
        batches.append(make_delta(
            n,
            np.concatenate([lo, hi]),
            np.concatenate([hi, lo]),
            np.concatenate([flags, flags]),
        ))
    return batches
