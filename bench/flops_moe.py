"""Operations and bytes of DeepSeek-V3-style models (latent attention,
a dense prefix, routed and shared experts), from shapes alone: the
yardstick of ``moe_gmm_roofline``, ``decode_roofline.dsv3`` and
``mfu.dsv3``.  ``bench/flops.py`` counts the other models; as there, a
multiply-add is two operations and the counts are what the mathematics
of the configuration requires, never what a program happens to do.

A configuration here is a Hugging Face style ``config.json`` with the
share held on this chip: ``n_routed_experts`` experts held of the
``published`` count.  Routed work is counted only for the experts held
here, at their expected share of the tokens' choices: a token sends
``num_experts_per_tok · held / published`` of its choices here, and
hits each held expert with probability ``num_experts_per_tok /
published``.  ``gmm_cost`` alone takes rows and experts as given: it
is the yardstick of a kernel, judged on the work it was handed.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def shape(cfg: dict) -> Dict[str, int]:
    """The widths and counts of the configuration."""
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
                qr=cfg["q_lora_rank"], kvr=cfg["kv_lora_rank"],
                dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
                dv=cfg["v_head_dim"], ff=cfg["intermediate_size"],
                mff=cfg["moe_intermediate_size"], v=cfg["vocab_size"],
                layers=layers, dense=dense, moe=layers - dense,
                held=cfg["n_routed_experts"],
                experts=cfg["published"]["n_routed_experts"],
                topk=cfg["num_experts_per_tok"],
                shared=cfg["n_shared_experts"])


def mla_params(cfg: dict) -> int:
    """Matrix weights of one latent-attention layer: the q_a and q_b
    projections, the kv_a projection (latent and rotary key), k_up and
    v_up (kv_b), and the output projection."""
    s = shape(cfg)
    return (s["d"] * s["qr"] + s["qr"] * s["h"] * (s["dn"] + s["dr"])
            + s["d"] * (s["kvr"] + s["dr"]) + s["kvr"] * s["h"] * s["dn"]
            + s["kvr"] * s["h"] * s["dv"] + s["h"] * s["dv"] * s["d"])


def expert_params(cfg: dict) -> int:
    """Weights of one routed expert (gate, up and down)."""
    s = shape(cfg)
    return 3 * s["d"] * s["mff"]


def token_params(cfg: dict) -> int:
    """Matrix weights that every token multiplies, routed experts left
    out: attention in every layer, the dense prefix's MLP, the router
    and the shared expert in every expert layer."""
    s = shape(cfg)
    return (s["layers"] * mla_params(cfg) + s["dense"] * 3 * s["d"] * s["ff"]
            + s["moe"] * (s["d"] * s["experts"]
                          + s["shared"] * expert_params(cfg)))


def attention_flops(cfg: dict, context: float) -> float:
    """Operations of one token's attention in every layer over
    ``context`` positions (itself included): scores over the nope and
    rotary parts of the key, then the values."""
    s = shape(cfg)
    return 2.0 * s["layers"] * s["h"] * (s["dn"] + s["dr"] + s["dv"]) \
        * context


def expected_choices(cfg: dict) -> float:
    """Token-choices a token sends to the experts held here, summed
    over the expert layers, on average over routing."""
    s = shape(cfg)
    return s["moe"] * s["topk"] * s["held"] / s["experts"]


def token_flops(cfg: dict, context: float, head: bool,
                choices: float = None) -> float:
    """Operations to run one token through every layer while it attends
    to ``context`` positions, with ``choices`` routed token-choices on
    the experts held here (the expected share by default), plus the
    output head when ``head``."""
    s = shape(cfg)
    choices = expected_choices(cfg) if choices is None else choices
    return (2.0 * token_params(cfg) + attention_flops(cfg, context)
            + 2.0 * expert_params(cfg) * choices
            + (2.0 * s["d"] * s["v"] if head else 0.0))


def prefill_flops(cfg: dict, n: int) -> float:
    """Operations of prefilling a prompt of ``n`` tokens, the head on
    the last one only."""
    s = shape(cfg)
    return (n * (2.0 * token_params(cfg)
                 + 2.0 * expert_params(cfg) * expected_choices(cfg))
            + attention_flops(cfg, n * (n + 1) / 2.0) + 2.0 * s["d"] * s["v"])


def expected_experts_hit(cfg: dict, tokens: int) -> float:
    """Held experts that ``tokens`` tokens hit, summed over the expert
    layers, on average over routing: each held expert is missed by all
    of them with probability ``(1 - num_experts_per_tok /
    published)^tokens``."""
    s = shape(cfg)
    miss = (1.0 - s["topk"] / s["experts"]) ** tokens
    return s["moe"] * s["held"] * (1.0 - miss)


def gmm_cost(cfg: dict, rows: float, experts_hit: float,
             itemsize: int = 2) -> Tuple[float, float]:
    """Operations and bytes of the grouped matmuls that run ``rows``
    token-choices through the held experts, ``experts_hit`` expert
    layers' worth of experts hit (both summed over layers and calls):
    each hit expert's weights read once, each row read in and written
    out once.  The rows and experts are the kernel's, as the program's
    counters give them, the choices of a decode step's empty slots
    among them."""
    s = shape(cfg)
    flops = 2.0 * rows * expert_params(cfg)
    byts = itemsize * (experts_hit * expert_params(cfg) + 2.0 * rows * s["d"])
    return flops, byts


def decode_cost(cfg: dict, contexts: Sequence[int], slots: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """Operations and bytes of one decode step over the live slots,
    each attending to its ``contexts`` entry: every weight outside the
    routed experts once, the head among them, the held experts that
    the live tokens hit and their token-choices (expected shares), the
    live latent rows read and the new ones written, and the float32
    logits of every slot of the program."""
    s = shape(cfg)
    live = len(contexts)
    flops = sum(token_flops(cfg, c, head=True) for c in contexts)
    row = s["layers"] * (s["kvr"] + s["dr"]) * itemsize
    byts = (itemsize * (token_params(cfg) + s["d"] * s["v"]
                        + expected_experts_hit(cfg, live)
                        * expert_params(cfg))
            + row * (sum(contexts) + live) + 4.0 * slots * s["v"])
    return flops, byts
