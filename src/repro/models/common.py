"""Shared building blocks for the LM-family model zoo.

Everything here is pure JAX over pytree params.  Sharding is expressed
with *logical axis* annotations (``repro.distributed.sharding.logical``)
so the same code runs on 1 CPU device (tests) and on the production
(pod, data, model) mesh (dry-run) — the paper's compile-time-
specialization philosophy extended to distribution.

Conventions
-----------
* params are dicts of jnp arrays; per-layer params are stacked on a
  leading ``L`` dim and consumed by ``jax.lax.scan`` (HLO size O(1) in
  depth — required to lower 61-layer 671B models in finite time).
* every ``init_*`` has a twin ``*_axes`` returning the same pytree
  structure with tuples of logical axis names per dim; the launcher
  turns those into NamedShardings.
* compute dtype is ``cfg.dtype`` (bf16 by default), params are kept in
  ``cfg.param_dtype``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import current_mesh, logical


# ---------------------------------------------------------------------------
# Parameter initialization helpers
# ---------------------------------------------------------------------------
def dense_init(key, shape, fan_in, dtype):
    scale = 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------
def rms_norm(x, gamma, eps):
    """RMSNorm in f32 accumulation (standard practice for bf16 nets)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + gamma.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, gamma, beta, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                      # (d/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., S, d/2)
    cos = jnp.cos(ang)[..., None, :]                # (..., S, 1, d/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # cast each half before the concat so every tensor that survives the
    # op (and any GSPMD reshard of it) is already in the compute dtype —
    # measured: f32 rope intermediates were what the (kv_heads < model)
    # padding gathers moved, at 2× the necessary bytes.
    out = jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype),
                           (x1 * sin + x2 * cos).astype(x.dtype)], axis=-1)
    return out


# ---------------------------------------------------------------------------
# Attention — pure-JAX flash-style chunked attention
# ---------------------------------------------------------------------------
# The (B,H,S,S) score matrix is never materialized: the KV sequence is
# processed in chunks with an online softmax (m, l, acc carried through a
# scan).  This is the jnp twin of the Pallas decode kernel, shaped so XLA
# keeps the working set bounded by the chunk size — on TPU the analogous
# fused kernel is kernels/decode_attention.

def chunked_attention(
    q: jnp.ndarray,            # (B, Sq, H, D)
    k: jnp.ndarray,            # (B, Sk, Hkv, D)
    v: jnp.ndarray,            # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: int = 0,           # 0 = global; >0 = sliding window width
    window_arr=None,           # traced int32 window (0 = global); wins over `window`
    q_offset: int = 0,         # absolute position of q[0] (prefill chunks)
    kv_chunk: int = 1024,
    q_chunk: int = 512,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    compute_dtype: str = "float32",  # §Perf: bf16 operands, f32 accum
    causal_skip: bool = False,       # §Perf: lax.cond skips masked chunks
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]                # may differ from d (MLA)
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    kv_chunk = min(kv_chunk, sk)
    q_chunk = min(q_chunk, sq)

    # Pad sequence dims to chunk multiples (masked off below).
    pq = (-sq) % q_chunk
    pk = (-sk) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = (sq + pq) // q_chunk, (sk + pk) // kv_chunk

    # Consistent shardings on every chunked view: without these, GSPMD
    # resolves the (kv_heads < model-axis) padding mismatch by fully
    # all-gathering the score tensors on EVERY kv step (measured: 7.5
    # TiB/device of loop collectives on qwen train_4k).
    _c = lambda t, *ax: logical(t, *ax)
    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    qg = q.reshape(b, nq, q_chunk, hkv, g, d).astype(cdt) \
        * jnp.asarray(scale, cdt)
    kc = k.reshape(b, nk, kv_chunk, hkv, d).astype(cdt)
    vc = v.reshape(b, nk, kv_chunk, hkv, dv).astype(cdt)
    qg = _c(qg, "batch", None, None, "kv_heads", None, None)
    kc = _c(kc, "batch", None, None, "kv_heads", None)
    vc = _c(vc, "batch", None, None, "kv_heads", None)

    q_pos = q_offset + jnp.arange(sq + pq).reshape(nq, q_chunk)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def per_q_chunk(qi, q_blk):
        # q_blk: (B, q_chunk, Hkv, G, D).  Checkpointed: the backward
        # pass recomputes scores per chunk instead of saving the inner
        # scan's per-step residuals (flash-attention backward structure;
        # without this the scan-of-scan residuals are O(S^2/chunk)).
        qpos = q_pos[qi]                              # (q_chunk,)

        def compute_chunk(m, l, acc, kj, k_blk, v_blk):
            kpos = kj * kv_chunk + jnp.arange(kv_chunk)
            k_blk = _c(k_blk, "batch", None, "kv_heads", None)
            v_blk = _c(v_blk, "batch", None, "kv_heads", None)
            # scores accumulate in f32 regardless of operand dtype
            s = jnp.einsum("bqhgd,bkhd->bqhgk", q_blk, k_blk,
                           preferred_element_type=jnp.float32)
            s = _c(s, "batch", None, "kv_heads", None, None)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window_arr is not None:
                band = qpos[:, None] - kpos[None, :] < window_arr
                mask &= (window_arr == 0) | band
            elif window:
                mask &= qpos[:, None] - kpos[None, :] < window
            mask &= (kpos < sk)[None, :]
            s = jnp.where(mask[None, :, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # rows with no valid key yet keep m = -inf; guard the exp
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(mask[None, :, None, None, :], p, 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p.astype(cdt), v_blk,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def kv_step(carry, inputs):
            m, l, acc = carry
            kj, k_blk, v_blk = inputs
            if causal_skip and causal:
                # Chunks entirely above the diagonal (or entirely below
                # the window) contribute nothing: branch them away so
                # neither the FLOPs nor the buffers exist at run time.
                visible = kj * kv_chunk <= qpos[-1]
                if window_arr is not None:
                    below = (window_arr > 0) & (
                        kj * kv_chunk + kv_chunk - 1 < qpos[0]
                        - window_arr + 1)
                    visible &= ~below
                m, l, acc = jax.lax.cond(
                    visible,
                    lambda op: compute_chunk(*op),
                    lambda op: (op[0], op[1], op[2]),
                    (m, l, acc, kj, k_blk, v_blk))
            else:
                m, l, acc = compute_chunk(m, l, acc, kj, k_blk, v_blk)
            return (m, l, acc), None

        m0 = jnp.full((b, q_chunk, hkv, g), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, hkv, g), jnp.float32)
        a0 = jnp.zeros((b, q_chunk, hkv, g, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
        l = jnp.where(l == 0.0, 1.0, l)
        return acc / l[..., None]

    out = jax.lax.map(
        lambda args: per_q_chunk(*args),
        (jnp.arange(nq), jnp.moveaxis(qg, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, sq + pq, h, dv)[:, :sq]
    return out.astype(q.dtype)


def decode_attention_jnp(
    q: jnp.ndarray,          # (B, H, D) — one new token per sequence
    k_cache: jnp.ndarray,    # (B, S, Hkv, D)
    v_cache: jnp.ndarray,    # (B, S, Hkv, D)
    lengths: jnp.ndarray,    # (B,) valid context length per sequence
    *,
    window: int = 0,
    window_arr=None,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    compute_dtype: str = "float32",
) -> jnp.ndarray:
    """Single-token attention against a KV cache (GEMV-shaped — the
    paper's "most important operation" in its LLM-decode incarnation)."""
    b, h, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    # flash-decoding layout: KV stream sharded over the sequence dim;
    # the softmax stats and the (B,H,dv) output reduce over it.
    k_cache = logical(k_cache, "batch", "kv_seq", None, None)
    v_cache = logical(v_cache, "batch", "kv_seq", None, None)
    qg = q.reshape(b, hkv, g, d).astype(cdt) * jnp.asarray(scale, cdt)
    # bf16 mode streams the cache WITHOUT materializing an f32 copy —
    # at 32k context the f32 cast alone is 2× the cache in HBM traffic.
    kf = k_cache.astype(cdt)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, kf,
                        preferred_element_type=jnp.float32)
    scores = logical(scores, "batch", None, None, "kv_seq")
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    pos = jnp.arange(s)[None, :]                    # (1, S)
    valid = pos < lengths[:, None]
    if window_arr is not None:
        band = pos >= (lengths[:, None] - window_arr)
        valid &= (window_arr == 0) | band
    elif window:
        valid &= pos >= (lengths[:, None] - window)
    scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(cdt),
                     v_cache.astype(cdt),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, v_cache.shape[-1]).astype(q.dtype)


def maybe_remat(cfg, fn):
    """Activation-checkpoint policy for a scanned layer body (training).
    "full" recomputes everything in backward (min memory), "dots" saves
    matmul outputs (the usual TPU sweet spot), "none" saves all."""
    if cfg.remat == "full":
        return jax.checkpoint(fn, prevent_cse=False)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return fn


def ring_insert(cache: jnp.ndarray, new: jnp.ndarray,
                pos: jnp.ndarray, mode: str = "where") -> jnp.ndarray:
    """Write ``new`` (B, ...) into ``cache`` (B, S, ...) at slot
    ``pos % S`` per batch element.  When S covers the whole context the
    slot equals the absolute position; when S is a sliding window the
    ring overwrite implements the window eviction.

    mode="where" rewrites the whole cache through a select (baseline);
    mode="scatter" lowers to a scatter that touches only the written
    row — §Perf: the where form costs a cache-sized read+write per
    layer per token."""
    b, s = cache.shape[:2]
    slot = pos % s
    if mode == "scatter":
        return cache.at[jnp.arange(b), slot].set(new.astype(cache.dtype))
    oh = jnp.arange(s)[None, :] == slot[:, None]          # (B, S)
    oh = oh.reshape(b, s, *([1] * (cache.ndim - 2)))
    return jnp.where(oh, new[:, None].astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_tokens(table: jnp.ndarray, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    x = jnp.take(table, tokens, axis=0).astype(dtype)
    return logical(x, "batch", "seq", "embed")


def lm_logits(x: jnp.ndarray, head: jnp.ndarray) -> jnp.ndarray:
    """x: (B,S,D) @ head: (D,V) -> (B,S,V); f32 logits for a stable loss."""
    y = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                   head.astype(jnp.float32))
    return logical(y, "batch", "seq", "vocab")


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  ignore_id: int = -1) -> jnp.ndarray:
    """Mean token cross-entropy; logits (B,S,V) f32, labels (B,S) int."""
    m = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(m, labels[..., None].clip(0), axis=-1)[..., 0]
    mask = (labels != ignore_id).astype(jnp.float32)
    return -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Row-parallel output projection (TP reduce pinned to the compute dtype)
# ---------------------------------------------------------------------------
def row_parallel_out(h, w, tp_psum: bool = False):
    """y = h @ w where h's last dim is model-sharded (row-parallel).

    With ``tp_psum`` and an active mesh, the contraction runs inside a
    ``shard_map`` with an explicit ``psum("model")`` — pinning the TP
    all-reduce (forward) and the dW reduce-scatter (backward) to ``h``'s
    dtype.  Left to GSPMD, XLA sinks the reduce past the rms-norm f32
    convert and moves 2× the bytes (measured on qwen train_4k: the f32
    dx/dy all-reduces were >60% of all collective traffic).
    """
    mesh = current_mesh()
    f = h.shape[-1]
    usable = (tp_psum and mesh is not None and "model" in mesh.axis_names
              and mesh.shape["model"] > 1 and f % mesh.shape["model"] == 0)
    if usable:
        batch_axes = tuple(a for a in ("pod", "data")
                           if a in mesh.axis_names and mesh.shape[a] > 1)
        nb = 1
        for a in batch_axes:
            nb *= mesh.shape[a]
        bspec = batch_axes if (batch_axes and h.shape[0] % nb == 0) \
            else None
        from jax.sharding import PartitionSpec as P
        wc = w.astype(h.dtype)

        def fn(hl, wl):
            y = jnp.einsum("bsf,fd->bsd", hl, wl,
                           preferred_element_type=hl.dtype)
            return jax.lax.psum(y, "model")

        return jax.shard_map(fn, mesh=mesh,
                             in_specs=(P(bspec, None, "model"),
                                       P("model", None)),
                             out_specs=P(bspec, None, None),
                             check_vma=False)(h, wc)
    return jnp.einsum("bsf,fd->bsd", h, w.astype(h.dtype),
                      preferred_element_type=h.dtype)


def col_parallel_in(x, weights, tp_psum: bool = False):
    """[x @ w for w in weights] with outputs model-sharded
    (column-parallel).  Under ``tp_psum`` all projections sharing ``x``
    run in ONE shard_map, so the backward emits a single fused
    ``psum("model")`` for dx — in x's dtype.  Left to GSPMD, the dx
    all-reduces sink past the rms-norm f32 convert and each projection
    reduces separately (measured: the f32 dx reduces were the largest
    single collective on qwen train_4k)."""
    mesh = current_mesh()
    usable = (tp_psum and mesh is not None and "model" in mesh.axis_names
              and mesh.shape["model"] > 1
              and all(w.shape[-1] % mesh.shape["model"] == 0
                      for w in weights))
    if not usable:
        return [jnp.einsum("bsd,dn->bsn", x, w.astype(x.dtype))
                for w in weights]
    batch_axes = tuple(a for a in ("pod", "data")
                       if a in mesh.axis_names and mesh.shape[a] > 1)
    nb = 1
    for a in batch_axes:
        nb *= mesh.shape[a]
    bspec = batch_axes if (batch_axes and x.shape[0] % nb == 0) else None
    from jax.sharding import PartitionSpec as P

    def fn(xl, *wl):
        return tuple(jnp.einsum("bsd,dn->bsn", xl, w,
                                preferred_element_type=xl.dtype)
                     for w in wl)

    outs = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(bspec, None, None),) + (P(None, "model"),) * len(weights),
        out_specs=(P(bspec, None, "model"),) * len(weights),
        check_vma=False)(x, *[w.astype(x.dtype) for w in weights])
    return list(outs)


# ---------------------------------------------------------------------------
# Gated MLP (SwiGLU / GeGLU family)
# ---------------------------------------------------------------------------
def gated_mlp(x, wi_gate, wi_up, wo, act: str = "silu",
              tp_psum: bool = False):
    """x: (B,S,D); wi_*: (D,F); wo: (F,D)."""
    h_gate, h_up = col_parallel_in(x, (wi_gate, wi_up), tp_psum)
    h_gate = logical(h_gate, "batch", "seq", "mlp")
    if act == "silu":
        h = jax.nn.silu(h_gate) * h_up
    elif act == "gelu":
        h = jax.nn.gelu(h_gate, approximate=True) * h_up
    else:
        raise NotImplementedError(act)
    y = row_parallel_out(h, wo, tp_psum)
    return logical(y, "batch", "seq", "embed")


def mlp_init(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(k1, (d_model, d_ff), d_model, dtype),
        "wi_up": dense_init(k2, (d_model, d_ff), d_model, dtype),
        "wo": dense_init(k3, (d_ff, d_model), d_ff, dtype),
    }


def mlp_axes():
    return {
        "wi_gate": ("fsdp", "mlp"),
        "wi_up": ("fsdp", "mlp"),
        "wo": ("mlp", "fsdp"),
    }
