"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

Completes the parallelism matrix (DP/FSDP x TP x EP x SP x **PP**).  The
production 2-axis mesh doesn't need PP (depth fits via FSDP+TP), so this
executor targets deeper future meshes: stages hold disjoint layer slices
(params sharded over 'stage'), activations flow stage->stage through
``jax.lax.ppermute`` inside ``shard_map``, microbatches fill the pipeline
GPipe-style (bubble fraction (S-1)/(M+S-1)).

The schedule is the Atos theme in one more costume: stage workers consume a
queue of microbatch tasks; the pipeline's fill/drain bubbles are exactly the
small-frontier problem, and raising M is the fetch-size knob.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS


def pipeline_apply(stage_fn: Callable, stage_params, x_micro, *, mesh: Mesh,
                   axis: str = "stage"):
    """Run ``stage_fn(params_s, act)`` over S stages for M microbatches.

    stage_params: pytree with leading dim S (sharded over ``axis``).
    x_micro:      [M, mb, ...] microbatched input (replicated).
    Returns       [M, mb, ...] outputs of the final stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_local, x_all):
        # params_local: leading dim 1 (this stage's slice); x_all replicated
        p = jax.tree.map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        # the carry is stage-varying (each stage holds a different
        # activation); mark the initial zeros accordingly.
        zero_act = jax.lax.pcast(jnp.zeros_like(x_all[0]), (axis,),
                                 to="varying")

        def tick(carry, t):
            act_in = carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(s == 0, x_all[mb_idx], act_in)
            out = stage_fn(p, inp)
            # forward the activation ring; stage S-1 -> 0 wraps harmlessly
            nxt = jax.lax.ppermute(out, axis, perm)
            return nxt, out

        _, outs = jax.lax.scan(tick, zero_act, jnp.arange(ticks))
        # stage s produced microbatch (t - s) at tick t; keep the last
        # stage's window [S-1, S-1+M) — every stage returns its window so
        # out_specs can stack them; the caller slices stage S-1.
        start = jnp.clip(s, 0, ticks - n_micro)
        window = jax.lax.dynamic_slice_in_dim(outs, start, n_micro, axis=0)
        return window[None]  # [1, M, mb, ...] per stage

    # ``jax.make_mesh`` builds explicit axes, whose arrays index only
    # inside a mesh context
    with jax.set_mesh(mesh):
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(PS(axis), PS()),
            out_specs=PS(axis),
        )(stage_params, x_micro)
        # last stage's microbatch outputs, replicated
        return out.at[-1].get(out_sharding=PS())


def split_microbatches(x, n_micro: int):
    """[B, ...] -> [M, B//M, ...]"""
    b = x.shape[0]
    assert b % n_micro == 0
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])
