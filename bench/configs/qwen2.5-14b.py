"""Qwen2.5-14B: its weights from the seed, the system's model
configuration for it, and its plain reference.

The reference is the Qwen2 decoder as published (RMSNorm, rotary
positions on half-split heads, grouped-query attention with Q/K/V
biases, a SiLU-gated MLP, an untied head) in straightforward
``jax.numpy`` at float32 and ``highest`` precision, one sequence at a
time, layer by layer.  It imports nothing of the system under test and
takes only the weights this file makes.  Its ``int8`` mode is the
control: every linear layer with int8 weights (per output channel) and
int8 inputs (per token), the precision step below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def shape(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h),
                ff=cfg["intermediate_size"], v=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def make_weights(cfg: dict, key) -> Dict:
    """Every weight, in the configuration's dtype, made on the device in
    one call: matrices normal / sqrt(fan_in), Q/K/V biases 0.02 *
    normal, RMSNorm gains stored as offsets from one (``g`` scales by
    ``1 + g``), 0.1 * normal.  The layout (names, stacking over layers,
    ``x @ W`` orientation) is the one the served model takes."""
    s = shape(cfg)
    d, q, kv, ff, v, L = (s["d"], s["h"] * s["hd"], s["hkv"] * s["hd"],
                          s["ff"], s["v"], s["L"])
    dt = jnp.dtype(cfg["torch_dtype"])
    spec = {
        "embed": ((v, d), d), "head": ((d, v), d), "ln_f": ((d,), "gain"),
        "layers/ln1": ((L, d), "gain"), "layers/ln2": ((L, d), "gain"),
        "layers/attn/wq": ((L, d, q), d), "layers/attn/wk": ((L, d, kv), d),
        "layers/attn/wv": ((L, d, kv), d), "layers/attn/wo": ((L, q, d), q),
        "layers/attn/bq": ((L, q), "bias"), "layers/attn/bk": ((L, kv), "bias"),
        "layers/attn/bv": ((L, kv), "bias"),
        "layers/ffn/wi_gate": ((L, d, ff), d),
        "layers/ffn/wi_up": ((L, d, ff), d),
        "layers/ffn/wo": ((L, ff, d), ff),
    }

    def build(key):
        keys = jax.random.split(key, len(spec))
        flat = {}
        for k, (name, (shp, kind)) in zip(keys, sorted(spec.items())):
            scale = {"gain": 0.1, "bias": 0.02}.get(kind) if isinstance(
                kind, str) else 1.0 / np.sqrt(kind)
            flat[name] = (jax.random.normal(k, shp, jnp.float32)
                          * scale).astype(dt)
        return flat

    flat = jax.jit(build)(key)
    out: Dict = {}
    for name, a in flat.items():
        node = out
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = a
    return out


def arch(cfg: dict):
    """The system's model configuration for this file: the registered
    ``qwen2.5-14b`` with every width, the depth, the norm epsilon, the
    rotary base and the dtypes taken from the file."""
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("qwen2.5-14b"),
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=shape(cfg)["hd"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], qkv_bias=cfg["attention_bias"],
        tie_embeddings=cfg["tie_word_embeddings"],
        mlp_act=cfg["hidden_act"], rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"], param_dtype=cfg["torch_dtype"],
        dtype=cfg["activation_dtype"])


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------
def _quant(x, axis):
    """Symmetric int8 rounding along ``axis`` (values kept in float32)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, mode):
    """``x @ w`` in float32; in ``int8`` mode both rounded to int8
    first, ``w`` per output column and ``x`` per row."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = _quant(x, -1), _quant(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def _rope(x, theta):
    """Rotary embedding on (S, H, D): the first and second halves of
    each head rotate together, as ``rotate_half`` does."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(cfg: dict, w: Dict, tokens, mode: str = "highest"):
    """Logits (S, V), float32, of one sequence ``tokens`` (S,)."""
    s = shape(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n = tokens.shape[0]
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((n, n), bool))
    g = s["h"] // s["hkv"]

    def layer(x, p):
        a, f = p["attn"], p["ffn"]
        h = _rms(x, p["ln1"], eps)
        q = (_linear(h, a["wq"], mode) + a["bq"].astype(jnp.float32))
        k = (_linear(h, a["wk"], mode) + a["bk"].astype(jnp.float32))
        v = (_linear(h, a["wv"], mode) + a["bv"].astype(jnp.float32))
        q = _rope(q.reshape(n, s["h"], s["hd"]), theta)
        k = _rope(k.reshape(n, s["hkv"], s["hd"]), theta)
        v = v.reshape(n, s["hkv"], s["hd"])
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST)
        sc = jnp.where(causal, sc / np.sqrt(s["hd"]), -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                       precision=HIGHEST).reshape(n, -1)
        x = x + _linear(o, a["wo"], mode)
        h = _rms(x, p["ln2"], eps)
        up = jax.nn.silu(_linear(h, f["wi_gate"], mode)) * _linear(
            h, f["wi_up"], mode)
        return x + _linear(up, f["wo"], mode), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _linear(_rms(x, w["ln_f"], eps), w["head"], mode)


def gaps(cfg: dict, w: Dict, seq, nxt):
    """At each position of ``seq``, how far the logit of ``nxt`` lies
    below the reference's best."""
    logits = forward(cfg, w, seq)
    got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got


def control_gaps(cfg: dict, w: Dict, seq):
    """At each position of ``seq``, how far the logit of the token that
    the int8 control puts first lies below the reference's best."""
    ref = forward(cfg, w, seq)
    pick = jnp.argmax(forward(cfg, w, seq, mode="int8"), axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got
