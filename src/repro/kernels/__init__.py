"""Pallas TPU kernels for the recurrent hot spots (see ops.py).

Two decisions every ``pallas_call`` in this package shares live here:

* ``resolve_interpret`` — whether a kernel runs compiled or through the
  Pallas interpreter.  Entry points take ``interpret: bool | None = None``;
  ``None`` means interpret only on the CPU backend (where Mosaic cannot
  compile), compiled everywhere else.  ``interpret=True`` on a TPU is an
  error: the interpreter there would hide the device behind a Python loop.
* ``compiler_params`` — the explicit scoped-VMEM limit every kernel is
  compiled with (``factorization.VMEM_LIMIT_BYTES``).  The tiling choosers
  size working sets against ``factorization.DEFAULT_VMEM_BUDGET``, which is
  keyed to the same limit, so a tile they accept is one Mosaic accepts.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None,
                      backend: str | None = None) -> bool:
    """Pallas execution mode for ``backend`` (default: JAX's default).

    None -> interpret on "cpu", compiled elsewhere.  An explicit False is
    honoured on any backend (compiling for a described TPU from a CPU
    process is how tests/test_tpu_compile.py rehearses real-width kernels).
    An explicit True on a TPU raises.
    """
    backend = backend or jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError(
            "interpret=True on a TPU backend: the Pallas interpreter would "
            "run the kernel as host-driven jnp ops instead of on the chip; "
            "pass interpret=None (compiled) or False")
    return bool(interpret)


def compiler_params():
    """Mosaic compiler parameters shared by every kernel: the scoped-VMEM
    limit the tiling budget is keyed to."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.core import factorization
    return pltpu.CompilerParams(
        vmem_limit_bytes=factorization.VMEM_LIMIT_BYTES)
