"""Which backend the kernels run on, read when asked.

Nothing here runs at import: importing ``repro.core`` or
``repro.kernels`` must not initialize a JAX backend (a process that
touches the backend holds the chip).  Every caller asks at trace time
instead, so a program traced on the TPU compiles its Pallas kernels for
the chip, and one traced on the CPU interprets them.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Whether a Pallas kernel runs in interpret mode: on the CPU only.

    On a TPU the kernel compiles for the chip.  Any other backend has
    neither the chip's compiler nor the interpreter's excuse, so asking
    for a kernel there raises instead of quietly interpreting it.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend; "
        "compile for 'jit' instead of 'pallas' there")
