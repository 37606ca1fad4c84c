"""Decoder-only transformer assembly.

Covers the dense archs (qwen2.5, deepseek-7b, gemma3, minicpm, the
internvl2 LM backbone) and — via pluggable FFN/attention modules — the
MoE archs (mixtral, deepseek-v3/MLA).  The recurrentgemma hybrid lives
in ``rglru.py`` and reuses the attention/MLP pieces here.

Heterogeneous layer patterns (gemma3's 5:1 local:global, mixtral's SWA)
are expressed as a *stacked per-layer window array* consumed inside one
``lax.scan`` body: local vs global attention differ only in the band
mask, so a single scanned body serves every pattern with zero duplicated
compute — the compile-time-constant pattern baked into the program, the
way the paper bakes layer structure into its instruction stream.

KV caches are ring buffers: when every layer is sliding-window
(mixtral), the cache allocates only the window and the ring overwrite
implements eviction; otherwise the cache covers the full context and
local layers mask by window.  ``cache["pos"]`` counts absolute tokens.

A model whose first ``cfg.first_k_dense`` layers have a dense MLP and
the rest experts (deepseek-v3) holds two layer stacks, each scanned:
``params["dense_layers"]`` (cache ``d1``/``d2``) before
``params["layers"]`` (cache ``c1``/``c2``).  A model without them has
the one stack and is traced as it always was.

The serving entry points (``prefill``, ``prefill_chunk``,
``decode_step``) run expert layers on the dropless path
(``moe.moe_serve``); with ``stats=True`` the last two also return the
expert counters, summed over the expert layers (``moe_serve``).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import logical
from . import common as C
from . import mla as mla_mod
from . import moe as moe_mod


# ---------------------------------------------------------------------------
# Per-layer static window pattern
# ---------------------------------------------------------------------------
def layer_windows(cfg) -> np.ndarray:
    """Per-layer sliding-window width; 0 = global attention."""
    L = cfg.num_layers
    if cfg.pattern == "gemma3":            # 5 local : 1 global
        w = [0 if (i + 1) % 6 == 0 else cfg.window for i in range(L)]
    elif cfg.pattern == "swa":             # all layers sliding-window
        w = [cfg.window] * L
    else:                                   # all global
        w = [0] * L
    return np.asarray(w, np.int32)


def cache_len(cfg, max_len: int) -> int:
    """Ring caches allocate only the window when no layer is global."""
    w = layer_windows(cfg)
    if (w == 0).any():
        return max_len
    return min(max_len, int(w.max()))


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def attn_init(key, cfg):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = C.split_keys(key, 4)
    dt = cfg.param_dtype
    p = {
        "wq": C.dense_init(ks[0], (d, h * hd), d, dt),
        "wk": C.dense_init(ks[1], (d, hkv * hd), d, dt),
        "wv": C.dense_init(ks[2], (d, hkv * hd), d, dt),
        "wo": C.dense_init(ks[3], (h * hd, d), h * hd, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def attn_axes(cfg):
    p = {"wq": ("fsdp", "heads"), "wk": ("fsdp", "kv_heads"),
         "wv": ("fsdp", "kv_heads"), "wo": ("heads", "fsdp")}
    if cfg.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)})
    if cfg.qk_norm:
        p.update({"q_norm": (None,), "k_norm": (None,)})
    return p


def _qkv(p, cfg, x, positions):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = C.col_parallel_in(x, (p["wq"], p["wk"], p["wv"]),
                                cfg.tp_psum)
    if cfg.qkv_bias:
        q, k, v = (q + p["bq"].astype(x.dtype), k + p["bk"].astype(x.dtype),
                   v + p["bv"].astype(x.dtype))
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = C.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = C.rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = logical(q, "batch", "seq", "heads", "head_dim")
    k = logical(k, "batch", "seq", "kv_heads", "head_dim")
    v = logical(v, "batch", "seq", "kv_heads", "head_dim")
    if cfg.rope_theta:
        q = C.apply_rope(q, positions, cfg.rope_theta)
        k = C.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(p, cfg, x, positions, window):
    """Full-sequence attention; window is a traced int32 (0 = global).
    Returns (out, (k, v)) — the cache slices for this layer."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    out = C.chunked_attention(
        q, k, v, causal=True, window_arr=window,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        softcap=cfg.attn_softcap, scale=cfg.attn_scale,
        compute_dtype=cfg.attn_compute_dtype,
        causal_skip=cfg.causal_skip)
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    y = C.row_parallel_out(out, p["wo"], cfg.tp_psum)
    return logical(y, "batch", "seq", "embed"), (k, v)


def attn_decode(p, cfg, x, k_cache, v_cache, pos, window):
    """One-token decode; x (B,1,D), caches (B,S,Hkv,D), pos (B,)."""
    b = x.shape[0]
    s_cache = k_cache.shape[1]
    q, k, v = _qkv(p, cfg, x, pos[:, None])
    k_cache = C.ring_insert(k_cache, k[:, 0], pos, cfg.cache_update)
    v_cache = C.ring_insert(v_cache, v[:, 0], pos, cfg.cache_update)
    eff_len = jnp.minimum(pos + 1, s_cache)
    # All-local models get a ring cache: eviction is the overwrite, so no
    # window mask is needed (static property of the config).
    ring = bool((layer_windows(cfg) > 0).all())
    out = C.decode_attention_jnp(
        q[:, 0], k_cache, v_cache, eff_len,
        window_arr=None if ring else window,
        softcap=cfg.attn_softcap, scale=cfg.attn_scale,
        compute_dtype=cfg.attn_compute_dtype)
    out = out.reshape(b, 1, cfg.n_heads * cfg.head_dim)
    y = C.row_parallel_out(out, p["wo"], cfg.tp_psum)
    return logical(y, "batch", "seq", "embed"), (k_cache, v_cache)


# ---------------------------------------------------------------------------
# One decoder layer
# ---------------------------------------------------------------------------
def layer_init(key, cfg, dense: bool = False):
    """One layer's params; ``dense`` gives a layer of the dense prefix
    (a ``d_ff`` MLP where the others have experts)."""
    k_attn, k_ffn = jax.random.split(key)
    p: Dict[str, Any] = {
        "ln1": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        "ln2": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        "attn": (mla_mod.mla_init if cfg.mla else attn_init)(k_attn, cfg),
        "ffn": (moe_mod.moe_init(k_ffn, cfg) if cfg.n_experts and not dense
                else C.mlp_init(k_ffn, cfg.d_model, cfg.d_ff,
                                cfg.param_dtype)),
    }
    return p


def layer_axes(cfg, dense: bool = False):
    return {
        "ln1": (None,), "ln2": (None,),
        "attn": mla_mod.mla_axes(cfg) if cfg.mla else attn_axes(cfg),
        "ffn": (moe_mod.moe_axes(cfg) if cfg.n_experts and not dense
                else C.mlp_axes()),
    }


def _ffn(p, cfg, x, serve: bool = False):
    """The layer's FFN: experts where its params have a router, else the
    dense MLP.  Returns (out, aux loss), or with ``serve`` (out, expert
    counters of ``moe.moe_serve``, None for a dense MLP)."""
    if "router" in p:
        return (moe_mod.moe_serve if serve else moe_mod.moe_apply)(p, cfg, x)
    return C.gated_mlp(x, p["wi_gate"], p["wi_up"], p["wo"],
                       act=cfg.mlp_act, tp_psum=cfg.tp_psum), (
        None if serve else jnp.float32(0.0))


def layer_apply(p, cfg, x, positions, window, serve: bool = False):
    flavor = mla_mod.mla_apply if cfg.mla else attn_apply
    h, slices = flavor(p["attn"], cfg, C.rms_norm(x, p["ln1"], cfg.norm_eps),
                       positions, window)
    x = x + h
    h, aux = _ffn(p["ffn"], cfg, C.rms_norm(x, p["ln2"], cfg.norm_eps),
                  serve)
    return x + h, slices, aux


def layer_decode(p, cfg, x, c1, c2, pos, window):
    """One layer of a decode step; returns (x, c1, c2, expert counters
    or None)."""
    flavor = mla_mod.mla_decode if cfg.mla else attn_decode
    h, (c1, c2) = flavor(p["attn"], cfg,
                         C.rms_norm(x, p["ln1"], cfg.norm_eps),
                         c1, c2, pos, window)
    x = x + h
    h, counts = _ffn(p["ffn"], cfg, C.rms_norm(x, p["ln2"], cfg.norm_eps),
                     serve=True)
    return x + h, c1, c2, counts


def _stacks(cfg, params):
    """(layer params, windows, cache keys) of each layer stack in order:
    the dense prefix where the model has one, then the other layers."""
    w = layer_windows(cfg)
    k = cfg.first_k_dense
    out = []
    if k:
        out.append((params["dense_layers"], jnp.asarray(w[:k]),
                    ("d1", "d2")))
    out.append((params["layers"], jnp.asarray(w[k:]), ("c1", "c2")))
    return out


# ---------------------------------------------------------------------------
# Whole model: params
# ---------------------------------------------------------------------------
def init_params(cfg, key) -> Dict[str, Any]:
    k_emb, k_layers, k_head, k_mtp, k_img = jax.random.split(key, 5)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    k = cfg.first_k_dense
    layers = jax.vmap(lambda kk: layer_init(kk, cfg))(layer_keys[k:])
    p = {
        "embed": C.dense_init(k_emb, (cfg.vocab, cfg.d_model),
                              cfg.d_model, cfg.param_dtype),
        "layers": layers,
        "ln_f": jnp.zeros((cfg.d_model,), cfg.param_dtype),
    }
    if k:
        p["dense_layers"] = jax.vmap(
            lambda kk: layer_init(kk, cfg, dense=True))(layer_keys[:k])
    if not cfg.tie_embeddings:
        p["head"] = C.dense_init(k_head, (cfg.d_model, cfg.vocab),
                                 cfg.d_model, cfg.param_dtype)
    if cfg.mtp:
        p["mtp"] = {
            "proj": C.dense_init(k_mtp, (2 * cfg.d_model, cfg.d_model),
                                 2 * cfg.d_model, cfg.param_dtype),
            "block": layer_init(k_mtp, cfg),
            "ln": jnp.zeros((cfg.d_model,), cfg.param_dtype),
        }
    if cfg.num_image_tokens:
        p["img_proj"] = C.dense_init(k_img, (cfg.vit_dim, cfg.d_model),
                                     cfg.vit_dim, cfg.param_dtype)
    return p


def param_axes(cfg) -> Dict[str, Any]:
    is_ax = lambda x: isinstance(x, tuple)
    stack = lambda t: jax.tree.map(lambda ax: ("layers",) + ax, t,
                                   is_leaf=is_ax)
    p = {
        "embed": ("vocab", "fsdp"),
        "layers": stack(layer_axes(cfg)),
        "ln_f": (None,),
    }
    if cfg.first_k_dense:
        p["dense_layers"] = stack(layer_axes(cfg, dense=True))
    if not cfg.tie_embeddings:
        p["head"] = ("fsdp", "vocab")
    if cfg.mtp:
        p["mtp"] = {"proj": ("fsdp", None), "block": layer_axes(cfg),
                    "ln": (None,)}
    if cfg.num_image_tokens:
        p["img_proj"] = (None, "fsdp")
    return p


# ---------------------------------------------------------------------------
# Forward (training)
# ---------------------------------------------------------------------------
def _embed_in(cfg, params, tokens, patches=None):
    x = C.embed_tokens(params["embed"], tokens, cfg.dtype)
    if cfg.scale_embed:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.dtype)
    if cfg.num_image_tokens and patches is not None:
        img = jnp.einsum("bnd,de->bne", patches.astype(cfg.dtype),
                         params["img_proj"].astype(cfg.dtype))
        x = jnp.concatenate([img, x[:, cfg.num_image_tokens:]], axis=1)
    return x


def _head(cfg, params, x):
    x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = C.lm_logits(x, head)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def forward(cfg, params, tokens, patches=None):
    """Training forward: tokens (B,S) -> (logits (B,S,V), extras)."""
    b, s = tokens.shape
    x = _embed_in(cfg, params, tokens, patches)
    positions = jnp.arange(s)[None, :]

    def body(carry, xs):
        x, aux = carry
        lp, w = xs
        x, _, a = layer_apply(lp, cfg, x, positions, w)
        return (x, aux + a), None

    aux = jnp.float32(0.0)
    for layers, windows, _ in _stacks(cfg, params):
        (x, aux), _ = jax.lax.scan(C.maybe_remat(cfg, body), (x, aux),
                                   (layers, windows))
    logits = _head(cfg, params, x)
    extras = {"aux_loss": aux * cfg.moe_aux_alpha}
    if cfg.mtp:
        extras["mtp_hidden"] = x
    return logits, extras


def mtp_logits(cfg, params, hidden, tokens):
    """DeepSeek-V3 multi-token-prediction head (depth 1): combine the
    final hidden at t with the embedding of token t+1 to predict t+2
    through one extra block sharing the unembedding."""
    p = params["mtp"]
    emb_next = _embed_in(cfg, params, jnp.roll(tokens, -1, axis=1))
    h = jnp.concatenate(
        [C.rms_norm(hidden, p["ln"], cfg.norm_eps), emb_next], axis=-1)
    h = jnp.einsum("bsd,de->bse", h, p["proj"].astype(h.dtype))
    positions = jnp.arange(tokens.shape[1])[None, :]
    h, _, _ = layer_apply(p["block"], cfg, h, positions, jnp.int32(0))
    return _head(cfg, params, h)


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------
def init_cache(cfg, batch: int, max_len: int):
    s = cache_len(cfg, max_len)
    k = cfg.first_k_dense
    L = cfg.num_layers - k
    if cfg.mla:
        shapes = ((batch, s, 1, cfg.kv_lora_rank),
                  (batch, s, 1, cfg.qk_rope_dim))
    else:
        shapes = ((batch, s, cfg.n_kv_heads, cfg.head_dim),) * 2
    cache = {
        "c1": jnp.zeros((L,) + shapes[0], cfg.dtype),
        "c2": jnp.zeros((L,) + shapes[1], cfg.dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if k:
        cache["d1"] = jnp.zeros((k,) + shapes[0], cfg.dtype)
        cache["d2"] = jnp.zeros((k,) + shapes[1], cfg.dtype)
    return cache


def cache_axes(cfg):
    kv = (None, None) if cfg.mla else ("kv_heads", "head_dim")
    axes = {
        "c1": ("layers", "batch", "kv_seq") + kv,
        "c2": ("layers", "batch", "kv_seq") + kv,
        "pos": ("batch",),
    }
    if cfg.first_k_dense:
        axes["d1"], axes["d2"] = axes["c1"], axes["c2"]
    return axes


def prefill(cfg, params, tokens, cache, patches=None):
    """Run the prompt, fill the cache, return last-position logits."""
    b, s = tokens.shape
    x = _embed_in(cfg, params, tokens, patches)
    positions = jnp.arange(s)[None, :]
    slen = cache["c1"].shape[2]

    def fit(t):
        """Store the last `slen` positions, ring-aligned."""
        if s > slen:
            t = t[:, -slen:]
            return jnp.roll(t, shift=s % slen, axis=1)
        if s < slen:
            pad = [(0, 0)] * t.ndim
            pad[1] = (0, slen - s)
            return jnp.pad(t, pad)
        return t

    cache = {}
    for layers, windows, (k1, k2) in _stacks(cfg, params):
        sliced, index, complete = _serving_layers(layers)

        def body(x, xs):
            lp, w, *extra = xs
            x, (c1, c2), _ = layer_apply(complete(lp, extra), cfg, x,
                                         positions, w, serve=True)
            return x, (fit(c1.astype(cfg.dtype)), fit(c2.astype(cfg.dtype)))

        x, (cache[k1], cache[k2]) = jax.lax.scan(body, x,
                                                 (sliced, windows) + index)
    cache["pos"] = jnp.full((b,), s, jnp.int32)
    return _head(cfg, params, x[:, -1:]), cache


def attn_chunk(p, cfg, x, k_cache, v_cache, positions, window, start):
    """Prefill one chunk against an existing cache: write the chunk's
    K/V into rows ``[start, start+C)`` and attend the chunk's queries
    over the cache prefix plus the chunk itself (``q_offset`` keeps the
    causal/window masks absolute).  Rows the chunk's pad positions
    write are causally invisible to every real query and are
    overwritten (or masked by ``pos``) before decode can see them."""
    b, c, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k.astype(k_cache.dtype), start, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v.astype(v_cache.dtype), start, axis=1)
    out = C.chunked_attention(
        q, k_cache, v_cache, causal=True, window_arr=window,
        q_offset=start,
        q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
        softcap=cfg.attn_softcap, scale=cfg.attn_scale,
        compute_dtype=cfg.attn_compute_dtype,
        causal_skip=cfg.causal_skip)
    out = out.reshape(b, c, cfg.n_heads * cfg.head_dim)
    y = C.row_parallel_out(out, p["wo"], cfg.tp_psum)
    return logical(y, "batch", "seq", "embed"), (k_cache, v_cache)


def layer_chunk(p, cfg, x, c1, c2, positions, window, start):
    """One decoder layer over a prefill chunk (the chunk twin of
    ``layer_apply``/``layer_decode``); returns (x, c1, c2, expert
    counters or None)."""
    flavor = mla_mod.mla_chunk if cfg.mla else attn_chunk
    h, (c1, c2) = flavor(p["attn"], cfg,
                         C.rms_norm(x, p["ln1"], cfg.norm_eps),
                         c1, c2, positions, window, start)
    x = x + h
    h, counts = _ffn(p["ffn"], cfg, C.rms_norm(x, p["ln2"], cfg.norm_eps),
                     serve=True)
    return x + h, c1, c2, counts


def _serving_layers(layers):
    """What a serving scan slices per layer, and ``complete(lp, xs)``
    that finishes a layer's params in the body.  An expert stack keeps
    its routed experts' weights whole, beside the layer's index (its
    last scanned input), so the grouped matmul reads them in place and
    the scan copies no layer's experts out (``moe.moe_serve``)."""
    if "router" not in layers["ffn"]:
        return layers, (), lambda lp, extra: lp
    ffn = layers["ffn"]
    whole = {k: ffn[k] for k in moe_mod.EXPERT_WEIGHTS}
    rest = dict(layers, ffn={k: v for k, v in ffn.items()
                             if k not in whole})
    index = (jnp.arange(ffn["router"].shape[0]),)
    return rest, index, lambda lp, extra: dict(
        lp, ffn=dict(lp["ffn"], **whole, layer=extra[0]))


def _scan_cache(cfg, params, cache, x, layer_fn):
    """Run ``x`` through every layer stack, each layer updating its rows
    of the cache: ``layer_fn(lp, x, c1, c2, window)`` returns (x, c1,
    c2, counters or None).  Returns (x, the cache's layer leaves,
    counters summed over the expert layers)."""
    leaves, counts = {}, None
    for layers, windows, (k1, k2) in _stacks(cfg, params):
        experts = "router" in layers["ffn"]
        sliced, index, complete = _serving_layers(layers)

        def body(x, xs):
            lp, w, c1, c2, *extra = xs
            x, c1, c2, n = layer_fn(complete(lp, extra), x, c1, c2, w)
            return x, ((c1, c2, n) if experts else (c1, c2))

        x, ys = jax.lax.scan(body, x, (sliced, windows, cache[k1],
                                       cache[k2]) + index)
        leaves[k1], leaves[k2] = ys[0], ys[1]
        if experts:
            n = jnp.sum(ys[2], axis=0)
            counts = n if counts is None else counts + n
    return x, leaves, counts


def _with_counts(out, counts, stats: bool):
    """``out`` with the expert counters appended when ``stats`` (zeros
    for a model without experts)."""
    if not stats:
        return out
    return out + (jnp.zeros((2,), jnp.int32) if counts is None else counts,)


def prefill_chunk(cfg, params, tokens, cache, start, length,
                  stats: bool = False):
    """Incremental prefill: process ``length`` (≤ C) prompt tokens at
    absolute positions ``[start, start+length)`` against an existing
    cache.

    ``tokens`` is a fixed-size (B, C) chunk (pad beyond ``length``);
    ``start``/``length`` are traced, so ONE compiled program serves
    every chunk of every prompt.  Returns the logits of the last *real*
    token and the updated cache (``pos = start+length``) — both
    bit-identical to the corresponding positions of one full-sequence
    ``prefill`` (tested), because the chunk queries see exactly the
    same keys in the same order: the cache prefix holds the earlier
    chunks' K/V at their absolute rows and ``q_offset`` keeps the
    causal/window masks absolute.  An MLA cache holds latents: each
    chunk writes its latents and expands the whole cache per head.
    With ``stats`` the expert counters come third (every position of
    the chunk is routed, its pad too).

    Requires a full-context cache (``cache_len == max_len``): with a
    ring cache the chunk's absolute row indices would alias, so callers
    gate chunked prefill off for all-sliding-window models (the
    scheduler does).
    """
    b, c = tokens.shape
    x = _embed_in(cfg, params, tokens)
    positions = start + jnp.arange(c)[None, :]
    x, leaves, counts = _scan_cache(
        cfg, params, cache, x,
        lambda lp, x, c1, c2, w: layer_chunk(lp, cfg, x, c1, c2, positions,
                                             w, start))
    new_cache = dict(leaves, pos=jnp.full_like(cache["pos"], start + length))
    last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    return _with_counts((_head(cfg, params, last), new_cache), counts, stats)


def decode_step(cfg, params, cache, tokens, stats: bool = False):
    """One decode step: tokens (B,1) -> (logits (B,1,V), updated cache),
    with ``stats`` the expert counters third (every row of the batch is
    routed, empty slots too)."""
    x = _embed_in(cfg, params, tokens)
    pos = cache["pos"]
    x, leaves, counts = _scan_cache(
        cfg, params, cache, x,
        lambda lp, x, c1, c2, w: layer_decode(lp, cfg, x, c1, c2, pos, w))
    new_cache = dict(leaves, pos=pos + 1)
    return _with_counts((_head(cfg, params, x), new_cache), counts, stats)
