"""DeepSeek-V3: its weights from the seed, the system's model
configuration for it, and its plain reference.

The reference is DeepSeek-V3's decoder as published (HF
``modeling_deepseek``): RMSNorm; multi-head latent attention in its
expanded form, the query through the q_a/q_b projections, keys and
values through the kv_a projection, its norm and kv_b; YaRN rotary
positions on interleaved pairs; a SiLU-gated dense MLP in the leading
layers; in the others the ``noaux_tc`` router over all the published
experts (sigmoid scores, a selection-only bias, the best groups, the
top-8 inside them, the unbiased scores normalized and scaled) and the
shared expert; an untied head.  It runs in straightforward
``jax.numpy`` at float32 and ``highest`` precision, one sequence at a
time, layer by layer, attention in blocks of heads and the head in
blocks of the vocabulary so that it fits beside the weights.  Of the
routed experts it adds the part of those held here, as the program
does: the chip's share of an expert-parallel deployment.  It imports
nothing of the system under test and takes only the weights this file
makes, mapping them back to HF's layout (the configuration's
``assumed.layout``).  Its ``int8`` mode is the control: every linear
layer, the router among them, with int8 weights (per output channel)
and int8 inputs (per token), the precision step below the bfloat16 the
configuration states.

The program publishes its expert counters as ``jax.monitoring``
scalars; loading this file starts recording them
(``bench/expert_counts.py``), for the per-layer readers of this cell.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import expert_counts

HIGHEST = jax.lax.Precision.HIGHEST
#: Heads per block of the reference's attention, vocabulary blocks of
#: its head: the largest temporaries stay a few hundred MB at 2048
#: positions.
HEAD_BLOCK = 16
VOCAB_BLOCKS = 10

expert_counts.listen()


def shape(cfg: dict) -> dict:
    rs = cfg["rope_scaling"]
    first, end = cfg["held_expert_ids"]
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        qr=cfg["q_lora_rank"], kvr=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], ff=cfg["intermediate_size"],
        mff=cfg["moe_intermediate_size"], v=cfg["vocab_size"],
        L=cfg["num_hidden_layers"], k=cfg["first_k_dense_replace"],
        e=cfg["published"]["n_routed_experts"],
        held=cfg["n_routed_experts"], first=first, end=end,
        topk=cfg["num_experts_per_tok"], groups=cfg["n_group"],
        topk_groups=cfg["topk_group"], scale=cfg["routed_scaling_factor"],
        shared=cfg["n_shared_experts"], rope=rs,
        theta=float(cfg["rope_theta"]))


def _spec(cfg: dict) -> dict:
    """Every weight by its path in the served layout: (shape, init),
    init a fan-in (normal / sqrt(fan_in)), "gain" or "bias"."""
    s = shape(cfg)
    d, h, qr, kvr = s["d"], s["h"], s["qr"], s["kvr"]
    dn, dr, dv = s["dn"], s["dr"], s["dv"]

    def mla(n, pre):
        return {
            f"{pre}/ln1": ((n, d), "gain"), f"{pre}/ln2": ((n, d), "gain"),
            f"{pre}/attn/q_down": ((n, d, qr), d),
            f"{pre}/attn/q_norm": ((n, qr), "gain"),
            f"{pre}/attn/q_up": ((n, qr, h * (dn + dr)), qr),
            f"{pre}/attn/kv_down": ((n, d, kvr + dr), d),
            f"{pre}/attn/kv_norm": ((n, kvr), "gain"),
            f"{pre}/attn/k_up": ((n, kvr, h * dn), kvr),
            f"{pre}/attn/v_up": ((n, kvr, h * dv), kvr),
            f"{pre}/attn/wo": ((n, h * dv, d), h * dv),
        }

    k, n, ff, mff = s["k"], s["L"] - s["k"], s["ff"], s["mff"]
    sff = mff * s["shared"]
    spec = {"embed": ((s["v"], d), d), "head": ((d, s["v"]), d),
            "ln_f": ((d,), "gain")}
    spec.update(mla(n, "layers"))
    spec.update({
        "layers/ffn/router": ((n, d, s["e"]), d),
        "layers/ffn/router_bias": ((n, s["e"]), "bias"),
        "layers/ffn/wi_gate": ((n, s["held"], d, mff), d),
        "layers/ffn/wi_up": ((n, s["held"], d, mff), d),
        "layers/ffn/wo": ((n, s["held"], mff, d), mff),
        "layers/ffn/shared/wi_gate": ((n, d, sff), d),
        "layers/ffn/shared/wi_up": ((n, d, sff), d),
        "layers/ffn/shared/wo": ((n, sff, d), sff),
    })
    if k:
        spec.update(mla(k, "dense_layers"))
        spec.update({
            "dense_layers/ffn/wi_gate": ((k, d, ff), d),
            "dense_layers/ffn/wi_up": ((k, d, ff), d),
            "dense_layers/ffn/wo": ((k, ff, d), ff),
        })
    return spec


def make_weights(cfg: dict, key) -> Dict:
    """Every weight, made on the device one array at a time (so the
    float32 draw of only one is ever alive): matrices normal /
    sqrt(fan_in) in the configuration's dtype, RMSNorm gains stored as
    offsets from one (``g`` scales by ``1 + g``), 0.1 * normal, the
    router's selection bias 0.1 * normal in float32.  The layout
    (names, stacking over layers, ``x @ W`` orientation) is the one the
    served model takes."""
    dt = jnp.dtype(cfg["torch_dtype"])
    spec = _spec(cfg)
    keys = jax.random.split(key, len(spec))
    out: Dict = {}
    for k, (name, (shp, kind)) in zip(keys, sorted(spec.items())):
        scale = {"gain": 0.1, "bias": 0.1}.get(kind) if isinstance(
            kind, str) else 1.0 / np.sqrt(kind)
        a = _draw(k, shp, float(scale),
                  jnp.float32 if kind == "bias" else dt)
        node = out
        *path, leaf = name.split("/")
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = a
    return out


@functools.partial(jax.jit, static_argnums=(1, 3))
def _draw(key, shp, scale, dtype):
    return (jax.random.normal(key, shp, jnp.float32) * scale).astype(dtype)


def arch(cfg: dict):
    """The system's model configuration for this file: the registered
    ``deepseek-v3-671b`` with every width, the depth, the experts held
    here, the routing, the rotary scaling and the dtypes taken from the
    file.  The multi-token-prediction module is not served."""
    from repro.configs import get_config
    s = shape(cfg)
    rs = s["rope"]
    if s["end"] - s["first"] != s["held"]:
        raise ValueError("held_expert_ids and n_routed_experts disagree")
    if not (cfg["norm_topk_prob"] and cfg["scoring_func"] == "sigmoid"
            and cfg["topk_method"] == "noaux_tc" and rs["type"] == "yarn"):
        raise ValueError("the served model routes as noaux_tc with "
                         "normalized sigmoid weights, under YaRN")
    return dataclasses.replace(
        get_config("deepseek-v3-671b"),
        num_layers=s["L"], first_k_dense=s["k"], d_model=s["d"],
        n_heads=s["h"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=s["dv"], d_ff=s["ff"], vocab=s["v"],
        q_lora_rank=s["qr"], kv_lora_rank=s["kvr"], qk_nope_dim=s["dn"],
        qk_rope_dim=s["dr"], v_head_dim=s["dv"],
        n_experts=s["e"], n_held_experts=s["held"], held_offset=s["first"],
        top_k=s["topk"], n_shared=s["shared"], moe_d_ff=s["mff"],
        n_group=s["groups"], topk_group=s["topk_groups"],
        routed_scale=s["scale"],
        yarn_factor=float(rs["factor"]),
        yarn_original_len=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        mlp_act=cfg["hidden_act"], tie_embeddings=cfg["tie_word_embeddings"],
        mtp=False, param_dtype=cfg["torch_dtype"],
        dtype=cfg["activation_dtype"],
        attn_compute_dtype=cfg["activation_dtype"], cache_update="scatter")


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
    """HF's DeepseekV3RMSNorm; its weight is ``1 + g``."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g.astype(jnp.float32))


def _hf_rotary_columns(w, heads, dn, dr):
    """The served layout's rotary columns back in HF's: per head, served
    column j < dr/2 is HF's 2j and dr/2 + j is HF's 2j + 1 (``heads``
    groups of ``dn`` plain columns then ``dr`` rotary ones)."""
    lead = w.shape[:-1]
    w = w.reshape(lead + (heads, dn + dr))
    rot = w[..., dn:]
    half = dr // 2
    inter = jnp.stack([rot[..., :half], rot[..., half:]], axis=-1)
    w = jnp.concatenate([w[..., :dn], inter.reshape(rot.shape)], axis=-1)
    return w.reshape(lead + (heads * (dn + dr),))


def _yarn(s, n):
    """HF's DeepseekV3YarnRotaryEmbedding at positions [0, n): cos, sin
    (n, dr) and the softmax scale's mscale."""
    rs, dim, base = s["rope"], s["dr"], s["theta"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr(turns):
        return (dim * math.log(orig / (turns * 2 * math.pi))) / (
            2 * math.log(base))

    def mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    idx = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** idx
    inter = 1.0 / (factor * base ** idx)
    hi = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (hi - low), 0.0, 1.0)
    mask = 1.0 - ramp
    inv = inter * (1.0 - mask) + extra * mask
    freqs = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    amp = mscale(factor, rs["mscale"]) / mscale(factor, rs["mscale_all_dim"])
    return (jnp.cos(emb) * amp, jnp.sin(emb) * amp,
            mscale(factor, rs["mscale_all_dim"]))


def _rotate(x, cos, sin):
    """HF's apply_rotary_pos_emb on (S, H, dr): the interleaved pairs
    de-interleaved, then rotate_half."""
    s_, h, d = x.shape
    x = x.reshape(s_, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(s_, h, d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos[:, None, :] + half * sin[:, None, :]


def _attention(s, a, h, eps, rope, mode):
    """Multi-head latent attention, expanded, over one sequence h
    (S, D) already normed."""
    n = h.shape[0]
    H, dn, dr, dv, kvr = s["h"], s["dn"], s["dr"], s["dv"], s["kvr"]
    cos, sin, msc = rope
    q_up = _hf_rotary_columns(a["q_up"], H, dn, dr)
    kv_down = _hf_rotary_columns(a["kv_down"], 1, kvr, dr)
    q = _linear(_rms(_linear(h, a["q_down"], mode), a["q_norm"], eps),
                q_up, mode).reshape(n, H, dn + dr)
    ckv = _linear(h, kv_down, mode)
    c = _rms(ckv[:, :kvr], a["kv_norm"], eps)
    k_pe = _rotate(ckv[:, None, kvr:], cos, sin)             # (S, 1, dr)
    q_pe = _rotate(q[..., dn:], cos, sin)
    k_nope = _linear(c, a["k_up"], mode).reshape(n, H, dn)
    v = _linear(c, a["v_up"], mode).reshape(n, H, dv)
    qs = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    ks = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (n, H, dr))], -1)
    scale = (dn + dr) ** -0.5 * msc * msc
    causal = jnp.tril(jnp.ones((n, n), bool))
    hb = math.gcd(H, HEAD_BLOCK)
    nb = H // hb

    def block(args):
        qb, kb, vb = args                        # (S, HB, .) each
        sc = jnp.einsum("qhd,khd->hqk", qb, kb, precision=HIGHEST) * scale
        sc = jnp.where(causal, sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), vb,
                          precision=HIGHEST)

    split = lambda t: jnp.moveaxis(
        t.reshape(n, nb, hb, t.shape[-1]), 1, 0)
    o = jax.lax.map(block, (split(qs), split(ks), split(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(n, H * dv)
    return _linear(o, a["wo"], mode)


def _mlp(h, wg, wu, wo, mode):
    return _linear(jax.nn.silu(_linear(h, wg, mode)) * _linear(h, wu, mode),
                   wo, mode)


def _route(s, f, h, mode):
    """noaux_tc over all the published experts: the combine weight of
    each held expert for each position (S, held), and each position's
    held experts chosen (S, held) bool."""
    n = h.shape[0]
    scores = jax.nn.sigmoid(_linear(h, f["router"], mode))  # (S, E)
    choice = scores + f["router_bias"].astype(jnp.float32)
    g = choice.reshape(n, s["groups"], -1)
    gscore = jnp.sum(jax.lax.top_k(g, 2)[0], axis=-1)       # (S, groups)
    _, gidx = jax.lax.top_k(gscore, s["topk_groups"])
    keep = jnp.zeros((n, s["groups"]), bool).at[
        jnp.arange(n)[:, None], gidx].set(True)
    choice = jnp.where(keep[..., None], g, -jnp.inf).reshape(n, -1)
    _, idx = jax.lax.top_k(choice, s["topk"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * s["scale"]
    held = jnp.arange(s["first"], s["end"])
    hit = idx[:, :, None] == held[None, None, :]            # (S, k, held)
    return jnp.sum(jnp.where(hit, w[..., None], 0.0), axis=1), jnp.any(
        hit, axis=1)


def _experts(s, f, h, mode):
    """The held experts' part of the routed result plus the shared
    expert."""
    comb, _ = _route(s, f, h, mode)

    def one(y, xs):
        c, wg, wu, wo = xs
        return y + c[:, None] * _mlp(h, wg, wu, wo, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (comb.T, f["wi_gate"], f["wi_up"], f["wo"]))
    sh = f["shared"]
    return y + _mlp(h, sh["wi_gate"], sh["wi_up"], sh["wo"], mode)


def hidden(cfg: dict, w: Dict, tokens, mode: str = "highest"):
    """The final normed hidden states (S, D), float32, of one sequence
    ``tokens`` (S,), and the input of every expert layer's router
    (L_moe, S, D)."""
    s = shape(cfg)
    eps = cfg["rms_norm_eps"]
    rope = _yarn(s, tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)

    def dense(x, p):
        x = x + _attention(s, p["attn"], _rms(x, p["ln1"], eps), eps, rope,
                           mode)
        f = p["ffn"]
        return x + _mlp(_rms(x, p["ln2"], eps), f["wi_gate"], f["wi_up"],
                        f["wo"], mode), None

    def moe(x, p):
        x = x + _attention(s, p["attn"], _rms(x, p["ln1"], eps), eps, rope,
                           mode)
        h = _rms(x, p["ln2"], eps)
        return x + _experts(s, p["ffn"], h, mode), h

    if s["k"]:
        x, _ = jax.lax.scan(dense, x, w["dense_layers"])
    x, routed = jax.lax.scan(moe, x, w["layers"])
    return _rms(x, w["ln_f"], eps), routed


def _head_blocks(h, head, mode):
    """Best logit and its id at each position (S,), the head taken in
    vocabulary blocks."""
    v = head.shape[1]
    nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
    blocks = jnp.moveaxis(head.reshape(head.shape[0], nb, v // nb), 1, 0)

    def one(wb):
        lg = _linear(h, wb, mode)
        return jnp.max(lg, -1), jnp.argmax(lg, -1)

    best, arg = jax.lax.map(one, blocks)                     # (nb, S)
    b = jnp.argmax(best, axis=0)
    n = jnp.arange(h.shape[0])
    return best[b, n], arg[b, n] + b * (v // nb)


def _logit_at(h, head, ids):
    """The reference's logit of token ``ids`` (S,) at each position."""
    cols = head[:, ids].astype(jnp.float32)                  # (D, S)
    return jnp.einsum("sd,ds->s", h, cols, precision=HIGHEST)


def _report_routing(differs, held):
    print(f"[check] routing: {int(differs)} of {int(held)} (position, "
          "layer) pairs with a held expert chosen choose other held "
          "experts when the router's input is rounded to bfloat16",
          file=sys.stderr, flush=True)


def _routing_diagnostic(cfg, w, routed):
    """How many positions choose a different set of held experts when
    the router's input is rounded to bfloat16, as the program's is: the
    near-ties that can part the program's routing from the reference's.
    A diagnostic only; it changes no compared number."""
    s = shape(cfg)

    def held_sets(h, f):
        _, a = _route(s, f, h, "highest")
        _, b = _route(s, f, h.astype(jnp.bfloat16).astype(jnp.float32),
                      "highest")
        return jnp.sum(jnp.any(a != b, -1)), jnp.sum(jnp.any(a | b, -1))

    f = w["layers"]["ffn"]
    differs, held = jax.vmap(held_sets)(
        routed, {"router": f["router"], "router_bias": f["router_bias"]})
    jax.debug.callback(_report_routing, jnp.sum(differs), jnp.sum(held))


def served_gaps(cfg: dict, w: Dict, seq, nxt):
    """At each position of ``seq``, how far the reference's logit of
    ``nxt`` (the token served there) lies below its best."""
    h, routed = hidden(cfg, w, seq)
    _routing_diagnostic(cfg, w, routed)
    best, _ = _head_blocks(h, w["head"], "highest")
    return best - _logit_at(h, w["head"], nxt)


def control_pick_gaps(cfg: dict, w: Dict, seq):
    """At each position of ``seq``, how far the reference's logit of the
    token that the int8 control puts first lies below its best."""
    h, _ = hidden(cfg, w, seq)
    best, _ = _head_blocks(h, w["head"], "highest")
    hq, _ = hidden(cfg, w, seq, mode="int8")
    _, pick = _head_blocks(hq, w["head"], "int8")
    return best - _logit_at(h, w["head"], pick)


def window_means(cfg: dict, raw, seq):
    """The compared number's per-position form of the gaps ``raw`` (S,)
    along ``seq``: at each position p up to the last token ``seq`` gives
    (its last non-zero), the gaps of the ``check.window`` positions from
    p on, summed up to that last position and divided by the window
    (a window cut short by the end counts as whole); past it, p's own
    gap.  The widest over a request's served positions is then the
    widest mean gap over any ``window`` consecutive served tokens.  A
    routing near-tie that bfloat16 tips flips one token, which a window
    dilutes; a precision loss moves every token.  The windows look
    forward only, so they never reach a prompt position before the
    served ones, for the program and the control alike."""
    win = cfg["check"]["window"]
    n = seq.shape[0]
    pos = jnp.arange(n)
    end = jnp.max(jnp.where(seq != 0, pos, 0))
    inside = pos <= end
    csum = jnp.concatenate([jnp.zeros((1,), raw.dtype),
                            jnp.cumsum(jnp.where(inside, raw, 0.0))])
    upto = jnp.minimum(pos + win, end + 1)
    summed = csum[jnp.maximum(upto, pos)] - csum[pos]
    return jnp.where(inside, summed / win, raw)


def gaps(cfg: dict, w: Dict, seq, nxt):
    """``served_gaps`` in the compared number's form (``window_means``)."""
    return window_means(cfg, served_gaps(cfg, w, seq, nxt), seq)


def control_gaps(cfg: dict, w: Dict, seq):
    """``control_pick_gaps`` in the compared number's form: the int8
    control's tokens in the served ones' place."""
    return window_means(cfg, control_pick_gaps(cfg, w, seq), seq)
