"""Undirected CSR from a directed edge list, on the device, in one jit.

Shared by the generator families: symmetrize, drop self-loops, sort by
(source, target), drop duplicates, and lay the survivors out as CSR.  The
survivor count depends on the seed, but every output shape does not:
``col_idx`` has a fixed capacity (``cap``) and only its first
``row_ptr[n]`` entries are edges.  So the graph, and every program the
benchmark compiles for it, has the same shapes for every seed, and the
compile cache serves every run after the first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """The device PRNG key of a seed of up to 64 bits (two uint32 words)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    words = jnp.asarray([seed & 0xFFFFFFFF, seed >> 32], jnp.uint32)
    return _key_of(words)


@jax.jit
def _key_of(words):
    return jax.random.fold_in(jax.random.key(words[0]), words[1])


@partial(jax.jit, static_argnames=("n", "cap"))
def symmetric_csr(src: jax.Array, dst: jax.Array, *, n: int, cap: int):
    """``(row_ptr[n+1], col_idx[cap], m)`` of the undirected simple graph
    on the pairs ``src[i] - dst[i]``; rows are sorted, ``m = row_ptr[n]``."""
    if 2 * src.shape[0] > cap:
        raise ValueError(f"capacity {cap} below 2 x {src.shape[0]} pairs")
    s = jnp.concatenate([src, dst]).astype(jnp.int32)
    d = jnp.concatenate([dst, src]).astype(jnp.int32)
    s = jnp.where(s == d, n, s)           # self-loops sort last, dropped
    s, d = jax.lax.sort((s, d), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    keep = first & (s < n)
    kept_before = jnp.cumsum(keep.astype(jnp.int32)) - keep
    pos = jnp.where(keep, kept_before, cap)
    col_idx = jnp.zeros((cap,), jnp.int32).at[pos].set(
        d, mode="drop", unique_indices=True)
    # row v starts at the first sorted pair whose source is >= v
    starts = jnp.searchsorted(s, jnp.arange(n + 1, dtype=jnp.int32),
                              side="left")
    kept_upto = jnp.concatenate([kept_before, jnp.sum(keep, keepdims=True,
                                                      dtype=jnp.int32)])
    row_ptr = kept_upto[starts]
    return row_ptr, col_idx, row_ptr[n]
