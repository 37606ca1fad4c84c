"""Jit'd public wrapper for decode attention."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from . import ref
from .kernel import decode_attention_p
from ..platform import interpret_mode


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    lengths: Optional[jnp.ndarray] = None,
    *,
    scale: Optional[float] = None,
    fast: bool = False,
    use_pallas: bool = False,
    bs: Optional[int] = None,
) -> jnp.ndarray:
    """``bs`` overrides the default KV-tile depth of the Pallas kernel
    (the autotuner's measured geometry)."""
    b, _, _ = q.shape
    s = k_cache.shape[1]
    if lengths is None:
        lengths = jnp.full((b,), s, jnp.int32)
    if not use_pallas:
        return ref.decode_attention_ref(
            q, k_cache, v_cache, lengths, scale=scale, fast=fast
        )
    kw = {} if bs is None else {"bs": int(bs)}
    return decode_attention_p(
        q.astype(jnp.float32),
        k_cache.astype(jnp.float32),
        v_cache.astype(jnp.float32),
        lengths.astype(jnp.int32),
        scale=scale,
        fast=fast,
        interpret=interpret_mode(),
        **kw,
    )
