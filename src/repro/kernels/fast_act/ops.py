"""Public jit'd wrappers for the fast activations.

``fast_act(x, fn)`` reshapes any-rank input to 2D, dispatches to the
Pallas kernel (interpreted on the CPU, compiled on a TPU), and restores the
shape.  ``use_pallas=False`` falls back to the pure-jnp reference (same
math — used by the CPU-side CompiledNN back end where interpret-mode
Pallas would be needlessly slow).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from . import ref
from .kernel import fast_act_2d
from ..platform import interpret_mode


def fast_act(x: jnp.ndarray, fn: str, use_pallas: bool = False,
             block: Optional[Tuple[int, int]] = None) -> jnp.ndarray:
    """fn in {'exp','tanh','sigmoid'} (softmax handled at a higher level
    because it needs the two-pass reduction).

    ``block`` overrides the default (rows, cols) tile of the Pallas
    kernel — the autotuner passes the measured winner here.
    """
    # bf16 rides the kernel as bf16 tiles (half the bytes, double the
    # row block); the math is f32 internally on both paths so the two
    # agree to one output rounding.
    narrow = x.dtype == jnp.bfloat16
    if not use_pallas:
        if narrow:
            return ref.FAST[fn](x.astype(jnp.float32)).astype(jnp.bfloat16)
        return ref.FAST[fn](x)
    shape = x.shape
    if x.ndim == 0:
        x2 = x.reshape(1, 1)
    elif x.ndim == 1:
        x2 = x.reshape(1, -1)
    else:
        x2 = x.reshape(-1, shape[-1])
    if not narrow:
        x2 = x2.astype(jnp.float32)
    y = fast_act_2d(x2, fn, interpret=interpret_mode(), block=block)
    return y.reshape(shape)


def fast_softmax(x: jnp.ndarray, axis: int = -1, use_pallas: bool = False) -> jnp.ndarray:
    """Max-subtracted softmax built on the fast exp (paper §3.4)."""
    if not use_pallas:
        return ref.fast_softmax(x, axis=axis)
    m = jnp.max(x, axis=axis, keepdims=True)
    e = fast_act(x - m, "exp", use_pallas=True)
    return e / jnp.sum(e, axis=axis, keepdims=True)
