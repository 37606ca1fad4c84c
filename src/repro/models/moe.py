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
requirement in MoE form.  Training takes that path.

Serving on one device takes the dropless path instead (``moe_serve``):
the token-choices that fall on the experts held here
(``cfg.held_offset`` onward, ``cfg.held_experts`` of them) are sorted
by expert into the grouped matmul's tiled layout and run through
``kernels/grouped_matmul`` — every choice computed, no capacity, and
no weight bytes read for an expert no token chose.  What goes on is
the held experts' part of the routed result plus the shared expert:
the share one device of an expert-parallel deployment computes.
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
    eh = cfg.held_experts
    p = {
        "router": init(ks[0], (d, e), d),
        "wi_gate": init(ks[1], (eh, d, f), d),
        "wi_up": init(ks[2], (eh, d, f), d),
        "wo": init(ks[3], (eh, f, d), f),
    }
    if cfg.router_fn == "sigmoid":        # noaux_tc's selection bias
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
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
    if cfg.router_fn == "sigmoid":
        p["router_bias"] = (None,)
    if cfg.n_shared:
        p["shared"] = {"wi_gate": ("fsdp", "mlp"), "wi_up": ("fsdp", "mlp"),
                       "wo": ("mlp", "fsdp")}
    return p


# ---------------------------------------------------------------------------
def _route(cfg, x, router, bias=None):
    """Top-k routing.  x: (T, D) -> idx (T,k), weights (T,k), aux loss.

    The sigmoid router is DeepSeek-V3's ``noaux_tc``: ``bias`` (zeros
    when None) is added to the scores for the choice only; with
    ``n_group`` groups each group scores the sum of its two best, the
    ``topk_group`` best groups are kept and the top-k experts are chosen
    inside them; the chosen unbiased scores are normalized and scaled by
    ``routed_scale``."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    if cfg.router_fn == "softmax":            # mixtral
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    else:                                      # deepseek-v3 sigmoid router
        scores = jax.nn.sigmoid(logits)
        choice = scores if bias is None else scores + bias.astype(
            jnp.float32)
        if cfg.n_group > 1:
            t, e = choice.shape
            grouped = choice.reshape(t, cfg.n_group, e // cfg.n_group)
            gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
            _, gidx = jax.lax.top_k(gscore, cfg.topk_group)
            keep = jnp.any(gidx[..., None] == jnp.arange(cfg.n_group),
                           axis=-2)                          # (T, G)
            choice = jnp.where(keep[..., None], grouped,
                               -jnp.inf).reshape(t, e)
        _, idx = jax.lax.top_k(choice, cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * cfg.routed_scale
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


def _moe_local(cfg, x, router, wi_gate, wi_up, wo, *, e_offset, axis,
               bias=None):
    """Per-shard MoE.  x: (T_local, D); expert weights: the local slice
    (owning global experts [e_offset, e_offset + E_local)); psum over
    `axis` (None on a single device)."""
    t, d = x.shape
    e_local = wi_gate.shape[0]
    cap = max(1, int(t * cfg.top_k * cfg.moe_cf / cfg.n_experts))

    idx, w, aux = _route(cfg, x, router, bias)           # global expert ids
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

    bias = p.get("router_bias")
    if mesh is None or "model" not in mesh.axis_names:
        y, aux = _moe_local(cfg, flat, p["router"], p["wi_gate"],
                            p["wi_up"], p["wo"], e_offset=cfg.held_offset,
                            axis=None, bias=bias)
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
                              e_offset=e_off, axis="model", bias=bias)

        wspec = (P("model", None, None) if ep else P(None, None, "model"))
        wospec = (P("model", None, None) if ep else P(None, "model", None))
        y, aux = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(bspec, None), P(None, None), wspec, wspec, wospec),
            out_specs=(P(bspec, None), P()),
            check_vma=False,
        )(flat, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
        aux = aux.reshape(())

    y = _add_shared(p, cfg, x, y.reshape(b, s, d))
    return logical(y, "batch", "seq", "embed"), aux


def _add_shared(p, cfg, x, y):
    """``y`` plus the shared expert's output for ``x`` (B, S, D)."""
    if not cfg.n_shared:
        return y
    sp = p["shared"]
    h_g = jnp.einsum("bsd,df->bsf", x, sp["wi_gate"].astype(x.dtype))
    h_u = jnp.einsum("bsd,df->bsf", x, sp["wi_up"].astype(x.dtype))
    h = jax.nn.silu(h_g) * h_u
    from .common import row_parallel_out
    return y + row_parallel_out(h, sp["wo"], cfg.tp_psum)


# ---------------------------------------------------------------------------
#: The routed experts' weights: what a serving scan may hand ``moe_serve``
#: as the whole stack over layers.
EXPERT_WEIGHTS = ("wi_gate", "wi_up", "wo")


def _dropless(cfg, x, p):
    """The held experts' part of the routed result for x (T, D), every
    token-choice computed.  Returns (y (T, D), counts (2,) int32: the
    token-choices on held experts, the held experts with any).  With
    ``p["layer"]`` the expert weights are the whole stack over layers
    and the kernel reads that layer's in place."""
    from ..kernels.grouped_matmul import gmm_tiles, layout
    t, d = x.shape
    k = cfg.top_k
    held = p["wi_gate"].shape[-3]
    stack = p.get("layer")
    with jax.named_scope("moe_route"):
        idx, w, _ = _route(cfg, x, p["router"], p.get("router_bias"))
    local = idx.reshape(-1) - cfg.held_offset               # (T*k,)
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)     # every other expert: one bin
    onehot = jax.nn.one_hot(group, held + 1, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                               group[:, None], axis=1)[:, 0]
    sizes = jnp.sum(onehot, axis=0)[:held]
    # Each token picks an expert at most once: at most min(k, held) of
    # its choices are held here.
    lay = layout(sizes, t * min(k, held))
    dest = jnp.where(mine, lay.row_start[jnp.minimum(group, held - 1)]
                     + rank, lay.rows)
    rows = jnp.zeros((lay.rows, d), x.dtype).at[dest].set(
        jnp.repeat(x, k, axis=0), mode="drop")
    h = gmm_tiles(rows, p["wi_gate"], lay, w_up=p["wi_up"], layer=stack)
    out = gmm_tiles(h, p["wo"], lay, layer=stack)
    # Rows of tiles past the active ones were never written: select,
    # never multiply, so whatever they hold cannot reach the sum.
    yk = jnp.where(mine[:, None],
                   out[jnp.minimum(dest, lay.rows - 1)].astype(jnp.float32)
                   * w.reshape(-1, 1), 0.0)
    y = jnp.sum(yk.reshape(t, k, d), axis=1).astype(x.dtype)
    counts = jnp.stack([jnp.sum(mine), jnp.sum(sizes > 0)]).astype(
        jnp.int32)
    return y, counts


def moe_serve(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Serving form of :func:`moe_apply`: x (B, S, D) -> (out, counts).

    On one device (no ``model`` mesh axis) the dropless grouped-matmul
    path; under a model axis the expert-parallel capacity path of
    training.  ``counts`` (2,) int32 gives the token-choices that fell
    on the experts held here and how many of them were hit (both 0 on
    the capacity path, which does not count them).  ``p`` may hold the
    expert weights as the whole stack over layers with ``p["layer"]``
    this layer's index (``transformer``'s serving scans do): the
    grouped matmul then reads the layer's weights in place."""
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        if "layer" in p:
            p = {k: (v[p["layer"]] if k in EXPERT_WEIGHTS else v)
                 for k, v in p.items() if k != "layer"}
        y, _ = moe_apply(p, cfg, x)
        return y, jnp.zeros((2,), jnp.int32)
    b, s, d = x.shape
    y, counts = _dropless(cfg, x.reshape(b * s, d), p)
    y = _add_shared(p, cfg, x, y.reshape(b, s, d))
    return logical(y, "batch", "seq", "embed"), counts
