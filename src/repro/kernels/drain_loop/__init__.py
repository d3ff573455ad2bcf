"""The persistent Pallas megakernel: one launch per drain (DESIGN.md §14).

The paper's persistent strategy keeps workers resident in a single kernel
that claims tasks until the queue is globally empty.  Our ``persistent``
kernel value approximates that with a jitted ``lax.while_loop`` — zero
host round-trips, but every round still re-enters the expand/push kernels.
This package fuses the *whole* drain loop — claim → expand → apply → push →
global-empty check — into one ``pallas_call``:

  * :func:`~repro.kernels.drain_loop.kernel.fused_drain_pallas` traces any
    ``(step, cond, carry)`` while-loop into a jaxpr, hoists its closed-over
    constants (the CSR arrays, budgets, codecs) into explicit kernel
    inputs, and evaluates it inside a single kernel body;
  * :mod:`~repro.kernels.drain_loop.csr_stream` feeds the in-kernel
    expansion: per-chunk CSR row slices are DMA'd HBM→VMEM through a
    double-buffered scratch so the copy of round ``i+1`` overlaps the
    gather of round ``i``;
  * :func:`~repro.kernels.drain_loop.ops.megakernel_drive` is the driver
    the scheduler dispatches to for ``ExecutionPolicy(kernel="megakernel")``
    — with an optional round ``limit`` so the streaming snapshot layer can
    segment a drain at the exact same boundaries as the other strategies.

Unlike the leaf kernels in this tree, the fused drain body is an
**interpret-mode prototype**: its jaxpr contains a nested ``pallas_call``
(the DMA stream) and whole-array operands that Mosaic has no in-kernel
lowering for, so ``fused_drain_pallas`` only runs through the Pallas
interpreter — on a real TPU (where ``core.backend.resolve_interpret``
would compile) and for an explicit ``interpret=False`` it raises
``NotImplementedError``, pointing to ``kernel='persistent'``.  The
parity/property/fault tests therefore exercise the real fused loop on any
host; a compiled Mosaic lowering (explicit HBM memory spaces for the CSR
operands, in-kernel DMA instead of the nested expansion call) is future
work (DESIGN.md §14).
"""
from .csr_stream import expand_stream, stream_row_slices
from .kernel import fused_drain_pallas, make_fused_drain
from .ops import make_megakernel_segment, megakernel_drive

__all__ = ["expand_stream", "fused_drain_pallas", "make_fused_drain",
           "make_megakernel_segment", "megakernel_drive",
           "stream_row_slices"]
