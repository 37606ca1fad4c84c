"""Mixture-of-Experts FFN with expert-parallel dispatch.

Covers mixtral (8e top-2, softmax router) and deepseek-v3 (1 shared +
256 routed top-8, sigmoid router with in-group normalization).

Distribution design (the compile-time-layout idea applied to EP):
experts live on the "model" mesh axis; activations entering the FFN are
replicated over "model" (they were just all-reduced by the attention
output projection).  Dispatch therefore needs **no collective at all**:
each model shard scatters the tokens routed to *its own* experts into a
local (E_local, C, D) buffer, runs the expert FFNs as dense matmuls,
gathers back, and one ``psum`` over "model" — the same all-reduce a
dense TP FFN would need — combines expert outputs.  Expressed with
``shard_map``; on a single device (tests) the same local function runs
without a mesh.

Two sharding modes, chosen at compile time from (E, n_model):
* **EP**  (E % n_model == 0): experts split across shards (deepseek-v3:
  256/16 = 16 experts per shard).
* **TP**  (n_model % E == 0): every shard holds all experts but only a
  1/r slice of each expert's hidden width (mixtral: 8 experts on a
  16-way axis -> r = 2).  Dispatch is replicated, the expert matmuls are
  split, the same trailing psum combines partial outputs.

Capacity-based token dropping (capacity factor ``cfg.moe_cf``) keeps
every shape static — the paper's "statically known properties"
requirement in MoE form.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import current_mesh, logical


def moe_init(key, cfg):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ks = jax.random.split(key, 7)
    init = lambda k, shape, fan: (
        jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan)
    ).astype(cfg.param_dtype)
    p = {
        "router": init(ks[0], (d, e), d),
        "wi_gate": init(ks[1], (e, d, f), d),
        "wi_up": init(ks[2], (e, d, f), d),
        "wo": init(ks[3], (e, f, d), f),
    }
    if cfg.n_shared:
        fs = f * cfg.n_shared
        p["shared"] = {
            "wi_gate": init(ks[4], (d, fs), d),
            "wi_up": init(ks[5], (d, fs), d),
            "wo": init(ks[6], (fs, d), fs),
        }
    return p


def moe_axes(cfg):
    # EP mode shards the expert dim; TP mode shards the hidden width.
    ep = True
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        ep = cfg.n_experts % mesh.shape["model"] == 0
    if ep:
        p = {"router": (None, None),
             "wi_gate": ("experts", "fsdp", None),
             "wi_up": ("experts", "fsdp", None),
             "wo": ("experts", None, "fsdp")}
    else:
        p = {"router": (None, None),
             "wi_gate": (None, "fsdp", "mlp"),
             "wi_up": (None, "fsdp", "mlp"),
             "wo": (None, "mlp", "fsdp")}
    if cfg.n_shared:
        p["shared"] = {"wi_gate": ("fsdp", "mlp"), "wi_up": ("fsdp", "mlp"),
                       "wo": ("mlp", "fsdp")}
    return p


# ---------------------------------------------------------------------------
def _route(cfg, x, router):
    """Top-k routing.  x: (T, D) -> idx (T,k), weights (T,k), aux loss."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    if cfg.router_fn == "softmax":            # mixtral
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:                                      # deepseek-v3 sigmoid router
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, cfg.top_k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        probs = scores / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-20)
    e = cfg.n_experts
    sel = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(jnp.mean(sel, axis=0) * jnp.mean(probs, axis=0))
    return idx, w.astype(jnp.float32), aux


def _positions(cfg, idx):
    """Capacity slot of each (token, choice) within its expert — exact
    counting, computed one choice column at a time so the transient is
    (T, E) instead of (T*k, E)."""
    t, k = idx.shape
    e = cfg.n_experts
    base = jnp.zeros((e,), jnp.int32)
    cols = []
    for j in range(k):
        oh = jax.nn.one_hot(idx[:, j], e, dtype=jnp.int32)
        pos_j = jnp.cumsum(oh, axis=0) - 1 + base[None, :]
        cols.append(jnp.take_along_axis(pos_j, idx[:, j:j + 1], axis=1)[:, 0])
        base = base + jnp.sum(oh, axis=0)
    return jnp.stack(cols, axis=1)             # (T, k)


def _expert_ffn(cfg, buf, wi_gate, wi_up, wo):
    """buf: (E_l, C, D) -> (E_l, C, D) through per-expert gated MLPs."""
    h_g = jnp.einsum("ecd,edf->ecf", buf, wi_gate.astype(buf.dtype))
    h_u = jnp.einsum("ecd,edf->ecf", buf, wi_up.astype(buf.dtype))
    act = jax.nn.gelu if cfg.mlp_act == "gelu" else jax.nn.silu
    h = act(h_g) * h_u
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))


def _moe_local(cfg, x, router, wi_gate, wi_up, wo, *, e_offset, axis):
    """Per-shard MoE.  x: (T_local, D); expert weights: the local slice
    (owning global experts [e_offset, e_offset + E_local)); psum over
    `axis` (None on a single device)."""
    t, d = x.shape
    e_local = wi_gate.shape[0]
    cap = max(1, int(t * cfg.top_k * cfg.moe_cf / cfg.n_experts))

    idx, w, aux = _route(cfg, x, router)                 # global expert ids
    pos = _positions(cfg, idx)                           # (T, k)

    flat_e = idx.reshape(-1)
    flat_p = pos.reshape(-1)
    mine = ((flat_e >= e_offset) & (flat_e < e_offset + e_local)
            & (flat_p < cap))
    local_e = jnp.clip(flat_e - e_offset, 0, e_local - 1)
    slot = jnp.where(mine, local_e * cap + jnp.clip(flat_p, 0, cap - 1),
                     e_local * cap)                      # overflow row

    xk = jnp.repeat(x, cfg.top_k, axis=0)                # (T*k, D)
    buf = jnp.zeros((e_local * cap + 1, d), x.dtype).at[slot].add(
        jnp.where(mine[:, None], xk, jnp.zeros_like(xk)))
    buf = buf[:-1].reshape(e_local, cap, d)

    out_buf = _expert_ffn(cfg, buf, wi_gate, wi_up, wo)

    gathered = jnp.concatenate(
        [out_buf.reshape(e_local * cap, d), jnp.zeros((1, d), x.dtype)])
    yk = gathered[slot] * (w.reshape(-1, 1) * mine[:, None]).astype(x.dtype)
    y = jnp.sum(yk.reshape(t, cfg.top_k, d), axis=1)

    if axis is not None:
        y = jax.lax.psum(y, axis)
        aux = jax.lax.pmean(aux, axis)
    return y, aux


# ---------------------------------------------------------------------------
def moe_apply(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out (B,S,D), aux-loss scalar)."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    mesh = current_mesh()

    if mesh is None or "model" not in mesh.axis_names:
        y, aux = _moe_local(cfg, flat, p["router"], p["wi_gate"],
                            p["wi_up"], p["wo"], e_offset=0, axis=None)
    else:
        n_model = mesh.shape["model"]
        batch = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        bspec = batch if batch else None
        ep = cfg.n_experts % n_model == 0
        if not ep and n_model % cfg.n_experts != 0:
            raise ValueError(
                f"n_experts={cfg.n_experts} incompatible with model axis "
                f"{n_model}")
        e_per = cfg.n_experts // n_model if ep else cfg.n_experts

        def shard_fn(flat_l, router, wig, wiu, wo):
            e_off = jax.lax.axis_index("model") * e_per if ep else 0
            return _moe_local(cfg, flat_l, router, wig, wiu, wo,
                              e_offset=e_off, axis="model")

        wspec = (P("model", None, None) if ep else P(None, None, "model"))
        wospec = (P("model", None, None) if ep else P(None, "model", None))
        y, aux = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(bspec, None), P(None, None), wspec, wspec, wospec),
            out_specs=(P(bspec, None), P()),
            check_vma=False,
        )(flat, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
        aux = aux.reshape(())

    y = y.reshape(b, s, d)
    if cfg.n_shared:
        sp = p["shared"]
        h_g = jnp.einsum("bsd,df->bsf", x, sp["wi_gate"].astype(x.dtype))
        h_u = jnp.einsum("bsd,df->bsf", x, sp["wi_up"].astype(x.dtype))
        h = jax.nn.silu(h_g) * h_u
        from .common import row_parallel_out
        y = y + row_parallel_out(h, sp["wo"], cfg.tp_psum)
    return logical(y, "batch", "seq", "embed"), aux
