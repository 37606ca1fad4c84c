"""Pallas TPU kernel: single-token GQA decode attention.

The paper identifies the matrix-*vector* product as "the most important
operation" (§3.3) and engineers its register schedule.  The LLM-decode
analogue is attention against a long KV cache with a single new query
token: a chain of GEMV-shaped contractions that is memory-bound on the
KV stream.  The TPU rendition:

* grid (batch, kv_head, S/bs): each instance owns the G = H/Hkv query
  heads of one KV head — the GQA group is the register-batch (§3.3);
* K/V stream through VMEM in (bs × D) tiles.  The cache is viewed as
  (B, S, Hkv·D) — a free reshape — so a tile is one KV head's D lanes
  of bs rows: its minor dims (bs, D) meet the chip's (8, 128) tiling,
  which a (bs, 1, D) block of the (B, S, Hkv, D) array cannot;
* the online-softmax state
  (m, l, acc) lives in VMEM scratch across the S dimension — the
  accumulator never round-trips to HBM (the paper's "results are
  written to the destination addresses" only once per batch);
* optional Schraudolph exp epilogue (`fast=True`) ties in §3.4.

Padding rows of the KV cache (beyond `length`) are masked with -inf
before the online max/sum.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fast_act.kernel import _exp_body

DEFAULT_BS = 512  # KV rows per tile


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, ns: int, bs: int,
                   scale: float, fast: bool):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]         # (G, D)
    k = k_ref[0]            # (bs, D)
    v = v_ref[0]            # (bs, D)

    scores = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale               # (G, bs)

    # Mask rows beyond the valid context length of this batch element.
    length = len_ref[pl.program_id(0)]
    pos = s_idx * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(pos < length, scores, -jnp.inf)

    m_prev = m_ref[...]                      # (G, 1)
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    exp = _exp_body if fast else jnp.exp
    # exp(-inf) under the Schraudolph body is exp(clip(-inf,-87,88)) ≈ 0.
    p = exp(scores - m_new)                  # (G, bs)
    p = jnp.where(pos < length, p, 0.0)
    alpha = exp(m_prev - m_new)              # (G, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(s_idx == ns - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def decode_attention_p(
    q: jnp.ndarray,          # (B, H, D) f32
    k_cache: jnp.ndarray,    # (B, S, Hkv, D) f32
    v_cache: jnp.ndarray,    # (B, S, Hkv, D) f32
    lengths: jnp.ndarray,    # (B,) int32
    *,
    scale: Optional[float] = None,
    fast: bool = False,
    bs: int = DEFAULT_BS,
    interpret: bool,
) -> jnp.ndarray:
    """Pallas single-token decode attention over a padded KV cache:
    one grid step per (batch, kv-head), KV streamed in ``bs``-row
    tiles, rows past each sequence's length masked in-kernel."""
    b, h, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = h // hkv
    assert g * hkv == h, (h, hkv)
    bs = min(bs, s)
    if s % bs:
        # Pad the KV stream to a tile multiple; padded rows sit beyond
        # every valid length and are masked inside the kernel.
        pad = bs - s % bs
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        s += pad
    ns = s // bs
    scale = scale if scale is not None else d ** -0.5

    qg = q.reshape(b, hkv, g, d)
    kv_shape = (b, s, hkv * d)
    kernel = functools.partial(
        _decode_kernel, ns=ns, bs=bs, scale=scale, fast=fast
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, ns),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, si, lens: (bi, hi, 0, 0)),
            pl.BlockSpec((1, bs, d), lambda bi, hi, si, lens: (bi, si, hi)),
            pl.BlockSpec((1, bs, d), lambda bi, hi, si, lens: (bi, si, hi)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, si, lens: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        interpret=interpret,
        name="decode_attention",
    )(lengths, qg, k_cache.reshape(kv_shape), v_cache.reshape(kv_shape))
    return out.reshape(b, h, d)
