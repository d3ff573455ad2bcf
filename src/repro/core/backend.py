"""Kernel-backend selection: jnp reference vs Pallas TPU kernels.

Atos treats the expansion schedule as a swappable component (cf. Osama et
al., "A Programming Model for GPU Load Balancing": composable LB schedules
behind one API).  This module is the TPU port of that idea — one ``backend``
axis threaded through every layer that owns a hot loop:

    SchedulerConfig.backend
      -> core/frontier.expand_merge_path   (kernels/frontier_expand LBS)
      -> core/queue.TaskQueue.push         (kernels/queue_compact reservation)
      -> algorithms/{bfs,pagerank,coloring} wavefront bodies
      -> server/jobs kernel bundles + server/autotune candidate grid

Values:

  * ``"jnp"``    — the pure-jnp reference implementations.  Portable,
    bit-exact oracle; the fastest choice on CPU.
  * ``"pallas"`` — the Pallas TPU kernels (``repro/kernels``).  On a real
    TPU they compile to Mosaic; anywhere else they run in ``interpret=True``
    mode so correctness tests double as backend-parity oracles on CPU.
  * ``"auto"``   — ``"pallas"`` when a TPU is attached, else ``"jnp"``.

Backend choice is a *performance* axis only: every dispatch site is required
(and tested) to produce bit-identical results across backends, so the
autotuner may measure both and pick freely (server/autotune.py).

One additional value exists *internally*: ``STREAM`` (``"stream"``), the
expansion backend the runtime substitutes for megakernel bodies
(``kernel="megakernel"``, DESIGN.md §14).  It is not user-facing — inside
the fused drain kernel the CSR lives in HBM and neighbor slices are
DMA-streamed through a double-buffered VMEM scratch
(``kernels/drain_loop/csr_stream``) instead of flat-gathered, still
bit-identical to the jnp reference.  ``resolve_backend`` rejects it like
any other unknown value; ``core.frontier.expand_merge_path`` dispatches it
before resolution, and the same interpret-mode fallback applies off-TPU.
"""
from __future__ import annotations

import jax

#: the public axis values, in the order they appear in CLIs and docs.
BACKENDS = ("jnp", "pallas", "auto")

#: internal expansion-backend value for megakernel bodies (see module doc);
#: never a valid ``SchedulerConfig.backend`` — the runtime injects it into
#: the :class:`~repro.runtime.program.ProgramContext` it builds for
#: ``kernel="megakernel"`` drains.
STREAM = "stream"


def has_tpu() -> bool:
    """True when JAX's default backend is the TPU.  A backend that fails
    to initialize raises here instead of passing for a CPU."""
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> str:
    """Collapse the user-facing axis to an executable one: jnp | pallas.

    ``"auto"`` picks the Pallas kernels only when real TPU hardware is
    attached — off-TPU the jnp reference is both faster and what interpret
    mode would emulate anyway.
    """
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if has_tpu() else "jnp"
    return backend


def default_interpret() -> bool:
    """Should ``pallas_call`` run in interpret mode?  Only off-TPU.

    This is the fallback that keeps tier-1 green on CPU: the kernels execute
    (slowly, via the Pallas interpreter) with exactly the compiled schedule,
    so parity tests exercise the real kernel code everywhere.
    """
    return not has_tpu()


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve an explicit/inherited interpret flag; ``None`` = auto-detect.

    Kernel wrappers (``kernels/*/ops.py``) default ``interpret=None`` so a
    real-TPU run never silently interprets, while CPU callers need no flag.
    """
    return default_interpret() if interpret is None else bool(interpret)
