"""Pallas TPU kernel: stream compaction (the queue's push-slot reservation).

Atos pushes with an atomic ticket counter; the TPU-native equivalent is a
two-phase stream compaction (DESIGN.md section 2):

  phase 1 (this kernel) — per-tile *local* compaction + a per-tile count.
    Within a tile, the scatter "item i -> slot pos(i)" is expressed as a
    one-hot [TILE, TILE] mask contraction — scatters become a dense
    compare + masked reduce that the VPU executes without any dynamic
    addressing (the TPU answer to CUDA's shared-memory scatter).
  phase 2 (ops.py, jnp) — a tiny exclusive scan over the per-tile counts
    stitches tiles into the final contiguous output.

The sequential TPU grid plays the role of the GPU's atomic ticket: tile t's
global offset is fully determined by tiles 0..t-1, no contention possible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.backend import resolve_interpret

TILE = 256
#: lanes of the per-tile count block: Mosaic tiles the last dim by 128
LANES = 128


def _compact_kernel(items_ref, mask_ref, out_ref, cnt_ref):
    """items/mask: [1, TILE] -> out: [1, TILE] locally compacted,
    cnt: [1, LANES] (the tile's count, repeated across the lanes).

    Mosaic lowers no cumsum and no lane-to-sublane reshape, so both become
    [TILE, TILE] masked lane reductions (row i = sublane, column j = lane):
    the diagonal pick moves item j onto sublane j, the strict lower
    triangle gives the exclusive scan, and a sublane reduction of the
    one-hot scatters each live item into its slot.
    """
    items = items_ref[...]                               # [1, TILE]
    mask = mask_ref[...]                                 # [1, TILE] 0/1
    row = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1)
    eye = row == col
    items_c = jnp.sum(jnp.where(eye, items, 0), axis=1, keepdims=True)
    mask_c = jnp.sum(jnp.where(eye, mask, 0), axis=1, keepdims=True)
    pos_c = jnp.sum(jnp.where(col < row, mask, 0), axis=1,
                    keepdims=True)                       # exclusive scan
    # onehot[i, j] = item i lands in slot j
    onehot = (pos_c == col) & (mask_c > 0)
    out_ref[...] = jnp.sum(jnp.where(onehot, items_c, 0), axis=0,
                           keepdims=True)
    cnt_ref[...] = jnp.broadcast_to(
        jnp.sum(mask, axis=1, keepdims=True), (1, LANES))


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact_tiles_pallas(items: jax.Array, mask: jax.Array,
                         interpret: bool | None = None):
    """[N] items + [N] mask -> ([n_tiles, TILE] local, [n_tiles] counts)."""
    interpret = resolve_interpret(interpret)
    n = items.shape[0]
    n_pad = -(-n // TILE) * TILE
    items_p = jnp.zeros((1, n_pad), jnp.int32).at[0, :n].set(items)
    mask_p = jnp.zeros((1, n_pad), jnp.int32).at[0, :n].set(
        mask.astype(jnp.int32))
    n_tiles = n_pad // TILE
    local, counts = pl.pallas_call(
        _compact_kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, TILE), lambda t: (0, t)),
            pl.BlockSpec((1, TILE), lambda t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((1, TILE), lambda t: (0, t)),
            pl.BlockSpec((1, LANES), lambda t: (0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, n_tiles * LANES), jnp.int32),
        ],
        interpret=interpret,
    )(items_p, mask_p)
    return local.reshape(-1, TILE), counts.reshape(n_tiles, LANES)[:, 0]
