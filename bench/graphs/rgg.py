"""DIMACS10 random geometric graph ``rgg_n_2_<scale>_s0``.

``n = 2^scale`` points uniform in the unit square, and an edge between
every two points closer than ``r = radius_factor * sqrt(ln n / n)``
(DIMACS10 uses 0.55).  The published graphs are files; here the point set
is drawn from the seed, and vertex ``i`` is the ``i``-th point drawn, so
vertex ids carry no locality.

Points sit on a ``2^b x 2^b`` lattice, with ``b`` the finest (up to 24)
at which two squared sides of ``r`` fit in 32 bits (24 at scale 20), and
distances are compared in integers, so the device, the CPU and a brute-force check agree on every
pair.  Pairs are found through a grid of cells at least ``r`` wide: the
points of a cell and of its eight neighbours are three runs of the points
sorted by cell, each at most ``WINDOW`` long, and no point has more than
``ROW_WIDTH`` neighbours (either raises where it does not hold).

Everything runs on the device in one jitted call from the seed.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .csr import seed_key

MAX_LATTICE_BITS = 24
#: CSR capacity over the expected number of CSR entries
CAPACITY_SLACK = 1.06
#: points looked at per run of three grid cells (mean 12.6 at any scale)
WINDOW = 48
#: neighbours held per point while the CSR is built (mean 13.2, most ~40)
ROW_WIDTH = 64
#: points per step of the pair search, to bound its temporaries
BLOCK = 1 << 16


def radius(config: dict) -> float:
    n = 1 << config["scale"]
    return config["radius_factor"] * math.sqrt(math.log(n) / n)


def lattice_bits(config: dict) -> int:
    """The finest lattice whose radius, squared twice, fits in uint32."""
    bits = MAX_LATTICE_BITS
    while 2 * (math.ceil(radius(config) * (1 << bits)) + 1) ** 2 >= 1 << 32:
        bits -= 1
    return bits


def lattice_radius_sq(config: dict) -> int:
    """Pairs with squared lattice distance below this are edges."""
    return math.ceil((radius(config) * (1 << lattice_bits(config))) ** 2)


def cells_per_side(config: dict) -> int:
    return max(1, int(1.0 / radius(config)))


def capacity(config: dict) -> int:
    """CSR entries held: the expected count n (n - 1) pi r^2, with slack."""
    n = 1 << config["scale"]
    expected = n * (n - 1) * math.pi * radius(config) ** 2
    return int(math.ceil(CAPACITY_SLACK * expected / 1024)) * 1024


def points(key, n: int, bits: int):
    """``(x, y)`` uint32 lattice coordinates of ``n`` seeded points."""
    xy = jax.random.bits(key, (2, n), jnp.uint32) >> (32 - bits)
    return xy[0], xy[1]


@partial(jax.jit, static_argnames=("n", "bits", "r2", "cells", "window",
                                   "row_width", "cap", "block"))
def _build(key, *, n: int, bits: int, r2: int, cells: int, window: int,
           row_width: int, cap: int, block: int):
    x, y = points(key, n, bits)
    width = -(-(1 << bits) // cells)      # cell side, at least r
    cx = (x // width).astype(jnp.int32)
    cy = (y // width).astype(jnp.int32)
    ids = jnp.arange(n, dtype=jnp.int32)
    cell_s, ids_s = jax.lax.sort((cy * cells + cx, ids), num_keys=1)
    start = jnp.searchsorted(
        cell_s, jnp.arange(cells * cells + 1, dtype=jnp.int32), side="left")
    # x, y and id of the points in cell order, gathered together
    pts = jnp.stack([x.astype(jnp.int32), y.astype(jnp.int32), ids])[:, ids_s]
    pts = pts.T
    cxs, cys = cx[ids_s], cy[ids_s]
    lo_x = jnp.maximum(cxs - 1, 0)
    hi_x = jnp.minimum(cxs + 1, cells - 1)
    j = jnp.arange(window, dtype=jnp.int32)
    bound = jnp.uint32(r2)
    side = jnp.uint32(math.isqrt(r2 - 1) + 1)

    def rows(p):
        """Neighbour ids of sorted points ``p`` [block], the longest run of
        three cells, and the most neighbours of one point."""
        out, longest = [], jnp.int32(0)
        here = pts[p]                                    # [block, 3]
        for dy in (-1, 0, 1):
            row = cys[p] + dy
            ok = (row >= 0) & (row < cells)
            row = jnp.clip(row, 0, cells - 1)
            lo = start[row * cells + lo_x[p]]
            hi = jnp.where(ok, start[row * cells + hi_x[p] + 1], lo)
            longest = jnp.maximum(longest, jnp.max(hi - lo))
            q = lo[:, None] + j[None, :]
            live = (q < hi[:, None]) & (q != p[:, None])
            there = pts[jnp.clip(q, 0, n - 1)]           # [block, window, 3]
            d = jnp.abs(there[..., :2] - here[:, None, :2]).astype(jnp.uint32)
            # a side of r or more is no edge; clamping there keeps the
            # squares and their sum inside uint32
            d = jnp.minimum(d, side)
            edge = live & (d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]
                           < bound)
            out.append(jnp.where(edge, there[..., 2], n))
        nbrs = jnp.sort(jnp.concatenate(out, axis=1), axis=1)
        most = jnp.max(jnp.sum(nbrs < n, axis=1))
        return nbrs[:, :row_width], longest, most

    blocks = jnp.arange(n, dtype=jnp.int32).reshape(n // block, block)
    nbrs, longest, most = jax.lax.map(rows, blocks)
    nbrs = nbrs.reshape(n, row_width)
    # rows back in vertex order; live entries lead each row
    by_id = jnp.zeros((n, row_width), jnp.int32).at[ids_s].set(
        nbrs, unique_indices=True)
    deg = jnp.sum(by_id < n, axis=1, dtype=jnp.int32)
    row_ptr = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(deg)])
    # owner of CSR slot e: rows that start at or before e, less one
    owner = jnp.cumsum(jnp.zeros((cap,), jnp.int32).at[row_ptr[1:n]].add(
        1, mode="drop", indices_are_sorted=True))
    e = jnp.arange(cap, dtype=jnp.int32)
    flat = owner * row_width + jnp.minimum(e - row_ptr[owner], row_width - 1)
    col_idx = jnp.where(e < row_ptr[n], by_id.reshape(-1)[flat], 0)
    return (row_ptr, col_idx, row_ptr[n], jnp.max(longest), jnp.max(most),
            x, y)


def generate(config: dict, seed: int) -> dict:
    """The graph of ``seed`` on the device: ``row_ptr``, ``col_idx``, the
    edge count ``m``, the ``points`` as fractions of the unit square, and
    the ``facts`` a run prints."""
    n = 1 << config["scale"]
    cap = capacity(config)
    bits = lattice_bits(config)
    row_ptr, col_idx, m, longest, most, x, y = _build(
        seed_key(seed), n=n, bits=bits,
        r2=lattice_radius_sq(config),
        cells=cells_per_side(config), window=WINDOW,
        row_width=ROW_WIDTH, cap=cap, block=min(n, BLOCK))
    longest, most, m_host = int(longest), int(most), int(m)
    if longest > WINDOW:
        raise RuntimeError(f"rgg: {longest} points in three adjacent cells, "
                           f"over the window of {WINDOW}")
    if most > ROW_WIDTH:
        raise RuntimeError(f"rgg: a point with {most} neighbours, over the "
                           f"row width {ROW_WIDTH}")
    if m_host > cap:
        raise RuntimeError(f"rgg: {m_host} CSR entries over the capacity "
                           f"{cap}")
    scale = 1.0 / (1 << bits)
    return {"row_ptr": row_ptr, "col_idx": col_idx, "m": m_host,
            "points": (x * scale, y * scale),
            "facts": {"radius": radius(config), "lattice_bits": bits,
                      "cells_per_side": cells_per_side(config),
                      "longest_cell_run": longest}}
