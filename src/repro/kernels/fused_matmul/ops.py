"""Jit'd public wrapper around the fused matmul kernel.

Handles padding to tile multiples (compile-time, from static shapes) and
falls back to the jnp reference when Pallas is not requested (the CPU
CompiledNN back end) — the *semantics* are identical by construction and
by test (tests/test_kernels.py sweeps shapes × epilogues).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from . import ref
from .kernel import fused_matmul_p, fused_matmul_q8_p
from ..platform import interpret_mode
from ..tiles import pick_block
from ..qmath import dequant_scales, quantize_q8


def _pad_to(a: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = -(-a.shape[0] // m0) * m0 - a.shape[0]
    p1 = -(-a.shape[1] // m1) * m1 - a.shape[1]
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))
    return a


def fused_matmul(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
    *,
    fn: Optional[str] = None,
    fast: bool = False,
    w_layout: str = "io",
    use_pallas: bool = False,
    block: Optional[Tuple[int, int, int]] = None,
    attrs: Optional[dict] = None,
) -> jnp.ndarray:
    """y = epilogue(x @ W (+ bias)) with W in 'io' (K,N) or 'oi' (N,K).

    x may be any rank; the contraction is over the last axis.

    ``block`` overrides the heuristic ``pick_block`` geometry — the
    autotuner passes the measured winner here so the kernel tiles
    exactly the way the micro-benchmark did.
    """
    shape = x.shape
    k = shape[-1]
    # bf16 operands stay bf16 on the Pallas path (the MXU multiplies
    # narrow inputs exactly into the f32 accumulator, so numerics match
    # an upcast) — this is what makes the dtype-parametrized VMEM model
    # in kernels/tiles.py true: a bf16 tile really is half the bytes.
    # Everything else computes in f32, as before.
    compute = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    x2 = x.reshape(-1, k).astype(compute)
    n = w.shape[1] if w_layout == "io" else w.shape[0]
    if not use_pallas:
        y = ref.fused_matmul_ref(
            x2.astype(jnp.float32), w, bias, scale, offset, fn=fn, fast=fast,
            w_layout=w_layout, attrs=attrs,
        )
        return y.reshape(shape[:-1] + (n,))

    m = x2.shape[0]
    itemsize = jnp.dtype(compute).itemsize
    bm, bk, bn = block if block is not None else pick_block(m, k, n, itemsize)
    xp = _pad_to(x2, bm, bk)
    wp = _pad_to(w, bk if w_layout == "io" else bn, bn if w_layout == "io" else bk)
    pn = wp.shape[1] if w_layout == "io" else wp.shape[0]

    def pad_vec(v):
        if v is None:
            return None
        return jnp.pad(v.astype(jnp.float32), (0, pn - v.shape[0]))

    y = fused_matmul_p(
        xp,
        wp.astype(compute),
        pad_vec(bias),
        pad_vec(scale),
        pad_vec(offset),
        fn=fn,
        fast=fast,
        w_layout=w_layout,
        block=(bm, bk, bn),
        interpret=interpret_mode(),
        attrs=attrs,
    )
    return y[:m, :n].reshape(shape[:-1] + (n,))


def fused_matmul_q8(
    x: jnp.ndarray,
    w: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    scale: Optional[jnp.ndarray] = None,
    offset: Optional[jnp.ndarray] = None,
    *,
    x_scale: float,
    w_scales: jnp.ndarray,
    fn: Optional[str] = None,
    fast: bool = False,
    w_layout: str = "io",
    use_pallas: bool = False,
    block: Optional[Tuple[int, int, int]] = None,
    attrs: Optional[dict] = None,
) -> jnp.ndarray:
    """Int8 fused matmul: quantize both f32 operands with the
    calibrated scales (``x_scale`` per-tensor, ``w_scales`` per output
    channel), contract int8×int8 into an exact i32 accumulator, dequant
    with one fused f32 multiply, then the standard epilogue.

    With static weights the weight quantization constant-folds at trace
    time (``embed_weights``) — the compiled program holds int8 weights,
    the paper's specialize-to-static-properties thesis applied to dtype.
    The non-pallas path is the reference ``lax.dot_general`` int8
    lowering — bit-identical to the Pallas kernel because the i32 sum
    is exact under any blocking.
    """
    shape = x.shape
    k = shape[-1]
    x2 = x.reshape(-1, k).astype(jnp.float32)
    n = w.shape[1] if w_layout == "io" else w.shape[0]
    w_scales = jnp.asarray(w_scales, dtype=jnp.float32)
    xq = quantize_q8(x2, jnp.float32(x_scale))
    wq = quantize_q8(
        w.astype(jnp.float32),
        w_scales[None, :] if w_layout == "io" else w_scales[:, None])
    deq = dequant_scales(x_scale, w_scales)
    if not use_pallas:
        y = ref.fused_matmul_q8_ref(
            xq, wq, deq, bias, scale, offset, fn=fn, fast=fast,
            w_layout=w_layout, attrs=attrs,
        )
        return y.reshape(shape[:-1] + (n,))

    m = x2.shape[0]
    bm, bk, bn = block if block is not None else pick_block(m, k, n, 1)
    xp = _pad_to(xq, bm, bk)
    wp = _pad_to(wq, bk if w_layout == "io" else bn,
                 bn if w_layout == "io" else bk)
    pn = wp.shape[1] if w_layout == "io" else wp.shape[0]

    def pad_vec(v):
        if v is None:
            return None
        return jnp.pad(v.astype(jnp.float32), (0, pn - v.shape[0]))

    y = fused_matmul_q8_p(
        xp,
        wp,
        pad_vec(deq),
        pad_vec(bias),
        pad_vec(scale),
        pad_vec(offset),
        fn=fn,
        fast=fast,
        w_layout=w_layout,
        block=(bm, bk, bn),
        interpret=interpret_mode(),
        attrs=attrs,
    )
    return y[:m, :n].reshape(shape[:-1] + (n,))
