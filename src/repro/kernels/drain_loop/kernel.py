"""``fused_drain_pallas`` — run a whole while-loop inside one pallas_call.

The megakernel problem is *generality*: the drain's step function is an
arbitrary program body (BFS relaxations, PageRank residue scatters,
coloring conflict checks) closing over arbitrary graph state, and Pallas
kernels may not capture traced constants.  ``jax.closure_convert`` does
not help — it hoists only inexact-dtype (differentiable) constants, and a
CSR graph is int32.  So we hoist by hand:

  1. flatten the carry pytree and trace ``while_loop(cond, step, ·)`` over
     the leaves with ``jax.make_jaxpr`` — every closed-over array
     (row_ptr, col_idx, budgets, chunk codecs) lands in ``jaxpr.consts``;
  2. pass ``consts + carry leaves`` as explicit kernel operands (0-d
     scalars lifted to shape ``(1,)`` — TPU refs are >= 1-d);
  3. the kernel body re-evaluates the jaxpr with ``jax.core.eval_jaxpr``
     on the loaded values and stores the loop's outputs.

Because the kernel evaluates the *identical jaxpr* the persistent driver
would hand to ``lax.while_loop``, the fused drain is bit-identical to the
persistent strategy by construction — the parity matrix in
tests/test_megakernel.py pins that, and the property battery drives the
claim/push protocol through this same entry point.

**TPU status: interpret-mode prototype.**  The fused body has no Mosaic
lowering today: the drain jaxpr contains a *nested* ``pallas_call`` (the
``backend.STREAM`` expansion, csr_stream.py), ``lax.while_loop``, and
arbitrary gather/scatter — none of which Mosaic can lower from inside a
kernel body — and the operands here get default whole-array BlockSpecs,
which contradicts HBM-resident CSR state on a real chip.  So this entry
point only ever runs through the Pallas interpreter: a demand to compile
(``interpret=None`` on a TPU, where the repo-wide rule compiles, or an
explicit ``interpret=False``) raises rather than hand Mosaic a program it
cannot lower or emulate the drain on the chip.  The launch-structure
collapse and every correctness claim hold in interpret mode; a compiled
lowering (explicit HBM memory spaces for the CSR operands, in-kernel DMA
instead of the nested expansion call) is future work — see DESIGN.md §14.

Tracing the drain is the expensive part, so it happens ONCE per
:func:`make_fused_drain` — the returned runner reuses the jaxpr, the
hoisted constants, and one jitted ``pallas_call`` across every invocation
with like-shaped carries (the streaming snapshot layer calls it once per
segment).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.backend import resolve_interpret

_NO_LOWERING = (
    "kernel='megakernel' is an interpret-mode prototype: the fused drain "
    "body evaluates the whole while-loop jaxpr in-kernel — including a "
    "nested pallas_call expansion (kernels/drain_loop/csr_stream) and "
    "whole-array operands — which Mosaic has no lowering for"
)


def _resolve_fused_interpret(interpret) -> bool:
    """The megakernel's own interpret rule: interpret, or raise (see module
    docstring).  Off-TPU ``None`` resolves to the interpreter; on a real
    TPU, where it resolves to a compile, and for an explicit
    ``interpret=False``, this raises."""
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            f"{_NO_LOWERING}, so it cannot be compiled for this device.  "
            "Use kernel='persistent' for a compiled device-resident "
            "drain; off-TPU the megakernel runs through the Pallas "
            "interpreter.")
    return True


def make_fused_drain(step, cond, example_carry, *, interpret=None):
    """Build the fused ``while cond(c): c = step(c)`` kernel ONCE; return a
    runner for it.

    ``example_carry`` supplies shapes/dtypes only — the returned
    ``run(carry)`` accepts any carry with the same pytree structure and
    avals, reusing the traced jaxpr, the hoisted constants, and a single
    jitted ``pallas_call`` (no per-call retrace — the streaming snapshot
    layer drives one runner through O(num_segments) calls).  ``step`` /
    ``cond`` may close over anything traceable — constants are hoisted
    into kernel operands.  ``interpret`` follows the megakernel gate
    (:func:`_resolve_fused_interpret`): interpret off-TPU, raise on a TPU
    or on an explicit compile request.
    """
    interpret = _resolve_fused_interpret(interpret)
    flat0, treedef = jax.tree.flatten(example_carry)
    flat0 = [jnp.asarray(x) for x in flat0]

    def flat_drain(*leaves):
        carry = jax.tree.unflatten(treedef, list(leaves))
        out = jax.lax.while_loop(cond, step, carry)
        return tuple(jax.tree.leaves(out))

    closed = jax.make_jaxpr(flat_drain)(*flat0)
    consts = [jnp.asarray(c) for c in closed.consts]
    # TPU refs are >= 1-d; lift 0-d scalars (round counters, cursors) and
    # reshape back on load so the jaxpr sees its original avals.
    shapes = [x.shape for x in consts + flat0]
    out_avals = closed.out_avals
    n_in, n_const = len(shapes), len(consts)

    def kernel(*refs):
        in_refs, out_refs = refs[:n_in], refs[n_in:]
        vals = [r[...].reshape(s) for r, s in zip(in_refs, shapes)]
        outs = jax.core.eval_jaxpr(closed.jaxpr, vals[:n_const],
                                   *vals[n_const:])
        for o_ref, o in zip(out_refs, outs):
            o_ref[...] = o.reshape(o_ref.shape)

    call = pl.pallas_call(
        kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct(a.shape if a.ndim else (1,), a.dtype)
            for a in out_avals),
        interpret=interpret,
    )
    lifted_consts = [c.reshape(1) if c.ndim == 0 else c for c in consts]

    @jax.jit
    def run(carry):
        leaves = [jnp.asarray(x) for x in jax.tree.leaves(carry)]
        lifted = lifted_consts + [x.reshape(1) if x.ndim == 0 else x
                                  for x in leaves]
        outs = call(*lifted)
        outs = [o.reshape(a.shape) for o, a in zip(outs, out_avals)]
        return jax.tree.unflatten(treedef, outs)

    return run


def fused_drain_pallas(step, cond, carry0, *, interpret=None):
    """Run ``while cond(c): c = step(c)`` to its fixed point in ONE kernel.

    One-shot wrapper over :func:`make_fused_drain` — builds the fused
    kernel for ``carry0``'s shapes and runs it once.  ``carry0`` may be
    any pytree of arrays (the drain carry is ``(queue, state, rounds,
    processed)``; the property tests thread scripted op tapes through
    here).  Returns the final carry with the input tree structure.
    Callers that drive many like-shaped drains (the segmented snapshot
    path) should hold a :func:`make_fused_drain` runner instead.
    """
    return make_fused_drain(step, cond, carry0, interpret=interpret)(carry0)
