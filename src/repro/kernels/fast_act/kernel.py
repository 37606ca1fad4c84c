"""Pallas TPU kernels for the fast activation approximations.

Elementwise maps over VMEM tiles.  The TPU adaptation of the paper's
register-batching (§3.3): instead of sizing batches to ``4·(n_xmm−k)``
XMM registers, the tile is sized so a (block_rows × 128-lane) slab and
its intermediates fit VMEM; the VPU then executes the polynomial with
full lane parallelism.  The Schraudolph trick survives intact because
TPUs are IEEE-754: ``bitcast_convert_type`` compiles to a vector
reinterpret, exactly like x86's ``movd``-free punning.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Tile shape: sublane × lane aligned for f32.  bf16 inputs double the
# row cap (same VMEM bytes — the register file packs narrow elements
# deeper, exactly the tiles.py granule story).
BLOCK_ROWS = 256
BLOCK_COLS = 128

_EXP_A = 12102203.161561485
_EXP_B = 127.0 * (2.0 ** 23)
_EXP_C = 60801.0 * 8.0


def _exp_body(x):
    x = jnp.clip(x, -87.0, 88.0)
    i = (_EXP_A * x + (_EXP_B - _EXP_C)).astype(jnp.int32)
    return jax.lax.bitcast_convert_type(i, jnp.float32)


def _tanh_body(x):
    x = jnp.clip(x, -4.97, 4.97)
    x2 = x * x
    num = (((36.0 * x2 + 6930.0) * x2 + 270270.0) * x2 + 2027025.0) * x
    den = (((x2 + 630.0) * x2 + 51975.0) * x2 + 945945.0) * x2 + 2027025.0
    return num / den


def _sigmoid_body(x):
    return 0.5 * (_tanh_body(0.5 * x) + 1.0)


_BODIES = {"exp": _exp_body, "tanh": _tanh_body, "sigmoid": _sigmoid_body}


def _elementwise_kernel(x_ref, o_ref, *, fn: str):
    # Compute in f32 regardless of the tile dtype: the Schraudolph exp
    # puns f32 bit patterns, and the polynomial coefficients are tuned
    # for f32 — bf16 tiles cast on entry and round once on exit.
    v = x_ref[...].astype(jnp.float32)
    o_ref[...] = _BODIES[fn](v).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fn", "interpret", "block"))
def fast_act_2d(x: jnp.ndarray, fn: str, *, interpret: bool,
                block=None) -> jnp.ndarray:
    """Apply a fast activation to a 2D f32/bf16 array via Pallas (the
    output dtype matches the input; internals are f32 either way).

    The wrapper pads to tile multiples (compile-time shapes, so the pad
    is free to fuse) and slices back.  ``block=(rows, cols)`` overrides
    the default tile caps (the autotuner's measured geometry).  bf16
    tiles default to double the row cap: half the bytes per row means
    the same VMEM working set covers twice the rows.
    """
    m, n = x.shape
    narrow = x.dtype == jnp.bfloat16
    rows_cap, cols_cap = block if block is not None else (
        BLOCK_ROWS * (2 if narrow else 1), BLOCK_COLS)
    bm = min(rows_cap, max(16 if narrow else 8, m))
    bn = min(cols_cap, max(128, n)) if n >= 128 else n
    pm = -(-m // bm) * bm
    pn = -(-n // bn) * bn
    xp = jnp.pad(x, ((0, pm - m), (0, pn - n)))
    out = pl.pallas_call(
        functools.partial(_elementwise_kernel, fn=fn),
        grid=(pm // bm, pn // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((pm, pn), x.dtype),
        interpret=interpret,
    )(xp)
    return out[:m, :n]
