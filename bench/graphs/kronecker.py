"""Graph500 Kronecker generator (graph500.org specification, section 3).

The specification's reference code, step for step: ``edgefactor * 2^scale``
edges, each drawn bit by bit with two uniforms per level (the ``ii_bit`` /
``jj_bit`` rule with A, B, C), then the vertex labels permuted at random.
The edge order permutation of the specification is left out: the CSR that
follows sorts the edges anyway.  The graph is then made undirected, with
self-loops and duplicate edges removed (``csr.symmetric_csr``).

Everything runs on the device in one jitted call from the seed.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .csr import seed_key, symmetric_csr


@partial(jax.jit, static_argnames=("scale", "edgefactor", "a", "b", "c"))
def edge_list(key, *, scale: int, edgefactor: int, a: float, b: float,
              c: float):
    """``(src, dst)``: the specification's directed edge list, permuted."""
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    bits_key, perm_key = jax.random.split(key)

    def level(ib, ij):
        i, j = ij
        k_i, k_j = jax.random.split(jax.random.fold_in(bits_key, ib))
        ii_bit = jax.random.uniform(k_i, (m,)) > ab
        jj_bit = jax.random.uniform(k_j, (m,)) > jnp.where(ii_bit, c_norm,
                                                           a_norm)
        return (i | (ii_bit.astype(jnp.int32) << ib),
                j | (jj_bit.astype(jnp.int32) << ib))

    zero = jnp.zeros((m,), jnp.int32)
    i, j = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(perm_key, n).astype(jnp.int32)
    return perm[i], perm[j]


def capacity(config: dict) -> int:
    """CSR entries held: both directions of every generated edge."""
    return 2 * config["edgefactor"] * (1 << config["scale"])


def generate(config: dict, seed: int) -> dict:
    """The graph of ``seed`` on the device: ``row_ptr``, ``col_idx`` and
    the edge count ``m``."""
    src, dst = edge_list(seed_key(seed), scale=config["scale"],
                         edgefactor=config["edgefactor"], a=config["A"],
                         b=config["B"], c=config["C"])
    row_ptr, col_idx, m = symmetric_csr(src, dst, n=1 << config["scale"],
                                        cap=capacity(config))
    return {"row_ptr": row_ptr, "col_idx": col_idx, "m": int(m)}

