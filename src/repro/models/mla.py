"""Multi-head Latent Attention (DeepSeek-V3).

MLA compresses K/V into a small latent ``c_kv`` (rank 512 + a 64-dim
shared RoPE key) and re-expands per head.  Two execution forms:

* **train/prefill** — naive expansion: k/v materialized per head and fed
  to the shared chunked-attention (matmul-heavy, MXU-friendly); a
  prefill *chunk* writes its latents into the cache and expands the
  whole cache (``mla_chunk``);
* **decode** — the *absorbed* form: ``W_UK`` is folded into the query
  projection and ``W_UV`` into the output projection at compile time, so
  attention runs entirely in the 576-dim latent space and the KV cache
  stores only the latent.  This is precisely the paper's Eq. 3 move —
  "the elements of the matrix are parameters known at compile time, so
  the memory layout can be chosen arbitrarily" — promoted from a
  register-shuffle trick to an attention-algebra rewrite.

Rotary positions use YaRN where the config stretches the context
(``common.rope_table``), and the softmax scale takes YaRN's mscale
squared (``softmax_scale``).

Cache slices (4-D to match the generic transformer cache):
    c_kv   (B, S, 1, kv_rank)
    k_rope (B, S, 1, rope_dim)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.sharding import logical
from . import common as C


def mla_init(key, cfg):
    d, h = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = C.split_keys(key, 8)
    dt = cfg.param_dtype
    return {
        "q_down": C.dense_init(ks[0], (d, qr), d, dt),
        "q_norm": jnp.zeros((qr,), dt),
        "q_up": C.dense_init(ks[1], (qr, h * (dn + dr)), qr, dt),
        "kv_down": C.dense_init(ks[2], (d, kvr + dr), d, dt),
        "kv_norm": jnp.zeros((kvr,), dt),
        "k_up": C.dense_init(ks[3], (kvr, h * dn), kvr, dt),
        "v_up": C.dense_init(ks[4], (kvr, h * dv), kvr, dt),
        "wo": C.dense_init(ks[5], (h * dv, d), h * dv, dt),
    }


def mla_axes(cfg):
    return {
        "q_down": ("fsdp", None),
        "q_norm": (None,),
        "q_up": (None, "heads"),
        "kv_down": ("fsdp", None),
        "kv_norm": (None,),
        "k_up": (None, "heads"),
        "v_up": (None, "heads"),
        "wo": ("heads", "fsdp"),
    }


def _latent(p, cfg, x, positions):
    """Shared front: queries (nope+rope) and the compressed KV latent."""
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = jnp.einsum("bsd,dr->bsr", x, p["q_down"].astype(x.dtype))
    q = C.rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rn->bsn", q, p["q_up"].astype(x.dtype))
    q = q.reshape(b, s, h, dn + dr)
    q = logical(q, "batch", "seq", "heads", None)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    table = C.rope_table(cfg, dr)
    q_rope = C.apply_rope(q_rope, positions, cfg.rope_theta, table)

    ckr = jnp.einsum("bsd,dr->bsr", x, p["kv_down"].astype(x.dtype))
    c_kv = C.rms_norm(ckr[..., : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = C.apply_rope(ckr[..., None, cfg.kv_lora_rank:], positions,
                          cfg.rope_theta, table)   # (B,S,1,dr), shared
    return q_nope, q_rope, c_kv, k_rope


def softmax_scale(cfg) -> float:
    """(qk_nope + qk_rope)^-½, times YaRN's mscale squared when the
    rotary embedding is stretched (``mscale_all_dim``)."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= C.yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _expand(p, cfg, q_nope, q_rope, c_kv, k_rope, dtype):
    """Per-head queries, keys and values of the expanded form from the
    latent ``c_kv`` (B,S,kvr) and the shared rotary key (B,S,1,dr)."""
    b, s = c_kv.shape[:2]
    h, dn, dr, dv = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.v_head_dim
    k_nope = jnp.einsum("bsr,rn->bsn", c_kv,
                        p["k_up"].astype(dtype)).reshape(b, s, h, dn)
    v = jnp.einsum("bsr,rn->bsn", c_kv,
                   p["v_up"].astype(dtype)).reshape(b, s, h, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))],
                        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = logical(k, "batch", "seq", "heads", None)
    v = logical(v, "batch", "seq", "heads", None)
    return q, k, v


def mla_apply(p, cfg, x, positions, window):
    """Full-sequence form: expand K/V per head, run chunked attention.
    Returns (out, (c_kv_4d, k_rope_4d)) cache slices."""
    b, s, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _latent(p, cfg, x, positions)
    q, k, v = _expand(p, cfg, q_nope, q_rope, c_kv, k_rope, x.dtype)
    out = C.chunked_attention(
        q, k, v, causal=True, window_arr=window,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        scale=softmax_scale(cfg),
        compute_dtype=cfg.attn_compute_dtype,
        causal_skip=cfg.causal_skip)
    out = out.reshape(b, s, cfg.n_heads * cfg.v_head_dim)
    y = C.row_parallel_out(out, p["wo"], cfg.tp_psum)
    return (logical(y, "batch", "seq", "embed"),
            (c_kv[:, :, None, :], k_rope))


def mla_chunk(p, cfg, x, c_cache, r_cache, positions, window, start):
    """Prefill one chunk against the latent cache: write the chunk's
    latents and rotary keys into rows ``[start, start+C)``, expand the
    whole cache per head and attend the chunk's queries over it
    (``q_offset`` keeps the causal mask absolute, so rows past the
    chunk are invisible)."""
    b, c, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _latent(p, cfg, x, positions)
    c_cache = jax.lax.dynamic_update_slice_in_dim(
        c_cache, c_kv[:, :, None, :].astype(c_cache.dtype), start, axis=1)
    r_cache = jax.lax.dynamic_update_slice_in_dim(
        r_cache, k_rope.astype(r_cache.dtype), start, axis=1)
    q, k, v = _expand(p, cfg, q_nope, q_rope, c_cache[:, :, 0],
                      r_cache, x.dtype)
    out = C.chunked_attention(
        q, k, v, causal=True, window_arr=window, q_offset=start,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        scale=softmax_scale(cfg),
        compute_dtype=cfg.attn_compute_dtype,
        causal_skip=cfg.causal_skip)
    out = out.reshape(b, c, cfg.n_heads * cfg.v_head_dim)
    y = C.row_parallel_out(out, p["wo"], cfg.tp_psum)
    return logical(y, "batch", "seq", "embed"), (c_cache, r_cache)


def mla_decode(p, cfg, x, c_cache, r_cache, lengths, window):
    """Absorbed decode: x (B,1,D); c_cache (B,S,1,kvr); r_cache
    (B,S,1,dr); lengths (B,) tokens already cached."""
    b = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    positions = lengths[:, None]
    q_nope, q_rope, c_kv, k_rope = _latent(p, cfg, x, positions)

    # Insert the new latent at each sequence's slot.
    c_cache = C.ring_insert(c_cache, c_kv[:, 0, None, :], lengths,
                            cfg.cache_update)
    r_cache = C.ring_insert(r_cache, k_rope[:, 0], lengths,
                            cfg.cache_update)

    # Absorb W_UK into q: q_abs = q_nope @ W_UK^T  -> latent space.  The
    # scores are the latent part plus the rotary part, each against its
    # own cache, so the two caches are never joined into a copy.
    f32 = jnp.float32
    cdt = jnp.bfloat16 if cfg.attn_compute_dtype == "bfloat16" else f32
    scale = softmax_scale(cfg)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0],
                       p["k_up"].reshape(kvr, h, dn).astype(x.dtype),
                       preferred_element_type=f32)          # (B,H,kvr)
    c, r = c_cache[:, :, 0].astype(cdt), r_cache[:, :, 0].astype(cdt)
    scores = (jnp.einsum("bhr,bsr->bhs", (q_abs * scale).astype(cdt), c,
                         preferred_element_type=f32)
              + jnp.einsum("bhd,bsd->bhs",
                           (q_rope[:, 0].astype(f32) * scale).astype(cdt),
                           r, preferred_element_type=f32))
    pos = jnp.arange(c.shape[1])[None, :]
    valid = pos < (lengths + 1)[:, None]
    valid &= (window == 0) | (pos >= (lengths + 1)[:, None] - window)
    scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
    out_lat = jnp.einsum("bhs,bsr->bhr",
                         jax.nn.softmax(scores, axis=-1).astype(cdt), c,
                         preferred_element_type=f32)        # (B,H,kvr)

    # Absorb W_UV into the output projection.
    out = jnp.einsum("bhr,rhd->bhd", out_lat.astype(x.dtype),
                     p["v_up"].reshape(kvr, h, dv).astype(x.dtype),
                     preferred_element_type=f32)
    out = out.reshape(b, 1, h * dv).astype(x.dtype)
    y = C.row_parallel_out(out, p["wo"], cfg.tp_psum)
    return logical(y, "batch", "seq", "embed"), (c_cache, r_cache)
