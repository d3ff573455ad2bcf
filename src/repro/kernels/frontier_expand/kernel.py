"""Pallas TPU kernel: merge-path load-balancing search (LBS).

This is the compute hot spot of Atos's CTA-worker expansion (paper section
3.3, after Merrill/Baxter's load-balancing search): given the inclusive scan
of the popped rows' degrees, every flattened work unit k must find its owner
row  owner(k) = first j with scan[j] > k  and its rank within the row
rank(k) = k - scan[owner-1].

GPU implementations binary-search the scan per thread (branchy, divergent).
TPU adaptation: the VPU has no efficient per-lane gather but eats 8x128
broadcast compares — so we replace the binary search with a compare-count:

    owner(k) = sum_j [scan[j] <= k]          (count of rows fully before k)
    excl(k)  = max_j  scan[j] * [scan[j] <= k]  (monotone scan -> running max)

**Windowed search.**  Each grid step holds TILE units [lo, lo + TILE).
The scan is monotone, so the rows wholly before the tile (scan <= lo) add
one each to ``owner`` and their last value to the running max, and the rows
whose scan exceeds the tile's last live unit add nothing: only the rows
in between can change an answer.  Two [1, W] compare-counts find them
(j0 = #(scan <= lo), j1 = #(scan <= min(lo + TILE - 1, total - 1))), and
the step compares its units against the 128-row scan chunks that hold rows
[j0, j1] only.  Rows j0 and j1 own the tile's first and last live units,
and each row of nonzero degree between them owns one or more units in
between, so the window spans at most TILE such rows: one or
two chunks at the mean degrees the benchmark's graphs pop (13 to 48), out
of W / 128 = 32 for a 4096-task wavefront.  The worst case for one step
is a window that spans every row (a few units spread over zero-degree
rows through the whole wavefront): that step sweeps all W / 128 chunks,
the [TILE, W] compares of an unwindowed search, plus the loop.  A call
cannot pay that on every step: the windows of consecutive tiles follow
one another and share one chunk at most, so a call sweeps at most
(live tiles + W / 128 - 1) chunks in all.

Tiles at or past the round's total (``pop_upto`` fills a prefix of the
wavefront, so the budget's tail carries no edge) skip the search and write
what it would give there: every row lies before such a unit, so
owner = W and rank = k - scan[W - 1].  Every output lane is the
unwindowed search's, bit for bit.

Layout: units run along lanes and a chunk's 128 rows along sublanes (the
chunk is broadcast and transposed once per chunk), so both reductions are
elementwise vreg adds and maxes over sublanes, and the results are
lane-dense [1, TILE] rows with no relayout.

Block layout: the scan (padded to a lane multiple) is VMEM-resident and
shared by every grid step; each grid step produces one TILE of (owner, rank).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.backend import resolve_interpret

TILE = 1024  # work units per grid step (8 sublanes x 128 lanes)
CHUNK = 128  # scan rows per compare block (one lane width)


def _lbs_kernel(scan_ref, owner_ref, rank_ref, *, w: int):
    """One TILE of the load-balancing search.

    scan_ref:  [1, W]    inclusive degree scan (padded with last value)
    owner_ref: [1, TILE] int32 owner row per work unit
    rank_ref:  [1, TILE] int32 rank within the owner row
    """
    lo = pl.program_id(0) * TILE
    scan = scan_ref[...]                                       # [1, W]
    total = jnp.max(scan)                                      # scan[W-1]
    k = lo + jax.lax.broadcasted_iota(jnp.int32, (1, TILE), 1)

    @pl.when(lo >= total)
    def _past_total():
        owner_ref[...] = jnp.full((1, TILE), w, jnp.int32)
        rank_ref[...] = k - total

    @pl.when(lo < total)
    def _search():
        hi = jnp.minimum(lo + TILE - 1, total - 1)  # last live unit
        j0 = jnp.sum((scan <= lo).astype(jnp.int32))
        j1 = jnp.sum((scan <= hi).astype(jnp.int32))
        c0 = j0 // CHUNK
        c1 = j1 // CHUNK  # j1 < w: scan[w-1] = total > hi
        # rows before chunk c0 all lie before the tile
        col = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        owner0 = jnp.full((1, TILE), c0 * CHUNK, jnp.int32)
        excl0 = jnp.full((1, TILE), jnp.max(
            jnp.where(col < c0 * CHUNK, scan, 0)), jnp.int32)

        def chunk(c, carry):
            owner, excl = carry
            part = scan_ref[:, pl.ds(pl.multiple_of(c * CHUNK, CHUNK),
                                     CHUNK)]                   # [1, 128]
            # rows[j, :] = part[j]: the chunk's rows down the sublanes
            rows = jnp.transpose(jnp.broadcast_to(part, (CHUNK, CHUNK)))
            counts, maxes = [], []
            for g in range(TILE // CHUNK):
                le = rows <= k[:, g * CHUNK:(g + 1) * CHUNK]  # [128, 128]
                counts.append(jnp.sum(le.astype(jnp.int32), axis=0,
                                      keepdims=True))
                maxes.append(jnp.max(jnp.where(le, rows, 0), axis=0,
                                     keepdims=True))
            return (owner + jnp.concatenate(counts, axis=1),
                    jnp.maximum(excl, jnp.concatenate(maxes, axis=1)))

        owner, excl = jax.lax.fori_loop(c0, c1 + 1, chunk, (owner0, excl0))
        # units of this tile at or past the total: every row lies before
        # them, not only the window's, as in the past-total branch
        past = k >= total
        owner_ref[...] = jnp.where(past, w, owner)
        rank_ref[...] = k - jnp.where(past, total, excl)


@functools.partial(jax.jit, static_argnames=("budget", "interpret"))
def lbs_pallas(scan: jax.Array, budget: int, interpret: bool | None = None):
    """Run the LBS kernel. ``scan``: [W] int32 inclusive scan of degrees.

    Returns (owner[budget], rank[budget]) int32.  ``interpret=None`` defers
    to the backend layer: compiled on TPU, interpreter elsewhere.
    """
    interpret = resolve_interpret(interpret)
    w = scan.shape[0]
    w_pad = max(CHUNK, -(-w // CHUNK) * CHUNK)
    # pad with the last scan value so padded rows own zero work units
    last = scan[-1] if w > 0 else jnp.int32(0)
    scan_p = jnp.full((1, w_pad), last, jnp.int32).at[0, :w].set(scan)
    budget_pad = -(-budget // TILE) * TILE
    grid = (budget_pad // TILE,)
    owner, rank = pl.pallas_call(
        functools.partial(_lbs_kernel, w=w_pad),
        grid=grid,
        in_specs=[pl.BlockSpec((1, w_pad), lambda t: (0, 0))],
        out_specs=[
            pl.BlockSpec((1, TILE), lambda t: (0, t)),
            pl.BlockSpec((1, TILE), lambda t: (0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, budget_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, budget_pad), jnp.int32),
        ],
        interpret=interpret,
    )(scan_p)
    return owner[0, :budget], rank[0, :budget]
