"""Pallas TPU kernel: tiled matmul with a fused epilogue.

This is the paper's P3+P5 combination rendered for the MXU:

* the matmul accumulates (bm × bn) tiles in a VMEM f32 scratch across
  the K grid dimension;
* on the *last* K step the epilogue — bias add, activation, optional
  folded-BN affine — is applied to the accumulator tile **while it is
  still in VMEM**, and only then stored to HBM.  That is exactly the
  paper's "the activation function is applied before writing the result
  of the operation into memory.  This avoids an additional loop with
  load and store operations" — with VMEM playing the role of the XMM
  register file.

Weights arrive in whatever layout the compile-time layout pass chose
(P5): (K, N) "io" or transposed (N, K) "oi"; the kernel body contracts
accordingly, so no runtime transpose ever appears in the lowered HLO.

Block sizes are MXU-aligned (multiples of (8,128) for f32); the wrapper
in ops.py pads operands at trace time (shapes are static — the pads are
compile-time constants, the paper's "statically known properties").
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fast_act.kernel import _BODIES as _FAST_BODIES

DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256

#: Grid (M, N, K): output tiles are independent, K accumulates in order.
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _apply_epilogue(acc, bias_ref, fn: Optional[str], fast: bool,
                    affine_refs, attrs):
    y = acc
    if bias_ref is not None:
        y = y + bias_ref[...]
    if fn and fn != "linear":
        if fn == "relu":
            y = jnp.maximum(y, 0.0)
        elif fn == "relu6":
            y = jnp.clip(y, 0.0, 6.0)
        elif fn == "leaky_relu":
            alpha = attrs.get("alpha", 0.01)
            y = jnp.where(y >= 0, y, alpha * y)
        elif fn == "hard_sigmoid":
            y = jnp.clip(y * 0.2 + 0.5, 0.0, 1.0)
        elif fn == "elu":
            y = jnp.where(y >= 0, y, jnp.expm1(y))
        elif fn == "tanh":
            y = _FAST_BODIES["tanh"](y) if fast else jnp.tanh(y)
        elif fn == "sigmoid":
            y = _FAST_BODIES["sigmoid"](y) if fast else jax.nn.sigmoid(y)
        else:  # pragma: no cover
            raise NotImplementedError(fn)
    if affine_refs is not None:
        s_ref, o_ref = affine_refs
        y = y * s_ref[...] + o_ref[...]
    return y


def _matmul_kernel(*refs, nk: int, fn: Optional[str], fast: bool,
                   has_bias: bool, has_affine: bool, w_layout: str, attrs):
    if has_bias and has_affine:
        x_ref, w_ref, b_ref, s_ref, off_ref, o_ref, acc_ref = refs
        affine = (s_ref, off_ref)
    elif has_bias:
        x_ref, w_ref, b_ref, o_ref, acc_ref = refs
        affine = None
    elif has_affine:
        x_ref, w_ref, s_ref, off_ref, o_ref, acc_ref = refs
        b_ref = None
        affine = (s_ref, off_ref)
    else:
        x_ref, w_ref, o_ref, acc_ref = refs
        b_ref = None
        affine = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if w_layout == "io":  # (K, N)
        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.float32
        )
    else:  # "oi": (N, K) — contract K on both, no transpose materialized
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...],
            w_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        o_ref[...] = _apply_epilogue(
            acc_ref[...], b_ref, fn, fast, affine, attrs
        ).astype(o_ref.dtype)


def fused_matmul_p(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
    *,
    fn: Optional[str] = None,
    fast: bool = False,
    w_layout: str = "io",
    block: Tuple[int, int, int] = (DEFAULT_BM, DEFAULT_BK, DEFAULT_BN),
    interpret: bool,
    attrs: Optional[dict] = None,
) -> jnp.ndarray:
    """Raw pallas_call: operands must already be tile-aligned.

    x: (M, K) f32 or bf16;  w: (K, N) or (N, K) per w_layout, same
    dtype as x; bias/scale/offset: (1, N) or None.  Accumulation is
    always f32 (``preferred_element_type``); returns (M, N) f32.
    """
    m, k = x.shape
    n = w.shape[1] if w_layout == "io" else w.shape[0]
    bm, bk, bn = block
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, block)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))]
    if w_layout == "io":
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
    else:
        in_specs.append(pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)))
    operands = [x, w]
    has_bias = bias is not None
    has_affine = scale is not None
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    if has_affine:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.extend([scale.reshape(1, n), offset.reshape(1, n)])

    kernel = functools.partial(
        _matmul_kernel,
        nk=nk,
        fn=fn,
        fast=fast,
        has_bias=has_bias,
        has_affine=has_affine,
        w_layout=w_layout,
        attrs=attrs or {},
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)


def _q8_matmul_kernel(*refs, nk: int, fn: Optional[str], fast: bool,
                      has_bias: bool, has_affine: bool, w_layout: str,
                      attrs):
    """Int8 body: i32 VMEM accumulation of int8 tiles, then — on the
    last K step, while the tile is still in VMEM — one f32 dequant
    multiply by the fused ``s_x * s_w`` vector followed by the standard
    epilogue.  The i32 sum is exact, so blocking order cannot perturb
    the result and the lax reference is bit-identical."""
    if has_bias and has_affine:
        x_ref, w_ref, deq_ref, b_ref, s_ref, off_ref, o_ref, acc_ref = refs
        affine = (s_ref, off_ref)
    elif has_bias:
        x_ref, w_ref, deq_ref, b_ref, o_ref, acc_ref = refs
        affine = None
    elif has_affine:
        x_ref, w_ref, deq_ref, s_ref, off_ref, o_ref, acc_ref = refs
        b_ref = None
        affine = (s_ref, off_ref)
    else:
        x_ref, w_ref, deq_ref, o_ref, acc_ref = refs
        b_ref = None
        affine = None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if w_layout == "io":  # (K, N)
        acc_ref[...] += jnp.dot(
            x_ref[...], w_ref[...], preferred_element_type=jnp.int32
        )
    else:  # "oi": (N, K)
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...],
            w_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(pl.program_id(2) == nk - 1)
    def _epilogue():
        y = acc_ref[...].astype(jnp.float32) * deq_ref[...]
        o_ref[...] = _apply_epilogue(
            y, b_ref, fn, fast, affine, attrs
        ).astype(o_ref.dtype)


def fused_matmul_q8_p(
    xq: jnp.ndarray,
    wq: jnp.ndarray,
    deq: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
    *,
    fn: Optional[str] = None,
    fast: bool = False,
    w_layout: str = "io",
    block: Tuple[int, int, int] = (DEFAULT_BM, DEFAULT_BK, DEFAULT_BN),
    interpret: bool,
    attrs: Optional[dict] = None,
) -> jnp.ndarray:
    """Raw int8 pallas_call: operands must already be quantized and
    tile-aligned to the itemsize-1 granule (sublane 32).

    xq: (M, K) int8;  wq: (K, N) or (N, K) int8 per w_layout;
    deq: (N,) f32 fused dequant scales (``s_x * s_w``); bias/scale/
    offset: (N,) f32 or None.  Accumulates in an i32 VMEM scratch and
    returns (M, N) f32.
    """
    m, k = xq.shape
    n = wq.shape[1] if w_layout == "io" else wq.shape[0]
    bm, bk, bn = block
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, block)
    nk = k // bk
    grid = (m // bm, n // bn, nk)

    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk))]
    if w_layout == "io":
        in_specs.append(pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)))
    else:
        in_specs.append(pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)))
    in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
    operands = [xq, wq, deq.reshape(1, n)]
    has_bias = bias is not None
    has_affine = scale is not None
    if has_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.reshape(1, n))
    if has_affine:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.extend([scale.reshape(1, n), offset.reshape(1, n)])

    kernel = functools.partial(
        _q8_matmul_kernel,
        nk=nk,
        fn=fn,
        fast=fast,
        has_bias=has_bias,
        has_affine=has_affine,
        w_layout=w_layout,
        attrs=attrs or {},
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*operands)

