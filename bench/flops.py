"""Operations and bytes that the algorithms need, from shapes alone.

These are the yardstick of the roofline and utilization metrics: they
count what the mathematics of the configuration requires (a
multiply-add is two operations), never what a particular program
happens to do, so no program change can move them.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple


# ---------------------------------------------------------------------------
# Convolutional networks (the compiler path)
# ---------------------------------------------------------------------------
def cnn_layers(cfg: dict) -> List[Tuple[str, Dict[str, int]]]:
    """The layers of a VGG-style configuration with their shapes:
    3x3 'same' convolutions in blocks, each block closed by a 2x2 max
    pool, then dense layers on the flattened map."""
    h, w, c = cfg["input"]
    layers = []
    for width, convs in cfg["blocks"]:
        for _ in range(convs):
            layers.append(("conv", dict(h=h, w=w, cin=c, cout=width,
                                        k=cfg["kernel"])))
            c = width
        h, w = h // 2, w // 2
    n = h * w * c
    for units in cfg["dense"]:
        layers.append(("dense", dict(cin=n, cout=units)))
        n = units
    return layers


def cnn_params(cfg: dict) -> int:
    """Weights and biases of every layer."""
    total = 0
    for kind, s in cnn_layers(cfg):
        k2 = s["k"] * s["k"] if kind == "conv" else 1
        total += s["cin"] * s["cout"] * k2 + s["cout"]
    return total


def cnn_macs(cfg: dict, kind: str) -> int:
    """Multiply-adds per image of the ``kind`` ("conv" or "dense")
    layers."""
    total = 0
    for k, s in cnn_layers(cfg):
        if k != kind:
            continue
        if k == "conv":
            total += s["h"] * s["w"] * s["cin"] * s["cout"] * s["k"] ** 2
        else:
            total += s["cin"] * s["cout"]
    return total


def cnn_flops(cfg: dict, batch: int) -> float:
    """Operations of one call: two per multiply-add of every
    convolution and dense layer."""
    return 2.0 * batch * (cnn_macs(cfg, "conv") + cnn_macs(cfg, "dense"))


def dense_cost(cfg: dict, batch: int, itemsize: int = 4) -> Tuple[float,
                                                                  float]:
    """Operations and bytes of the dense layers of one call: each layer
    reads its weights and bias once and its input, and writes its
    output, at ``itemsize`` bytes a value."""
    flops = byts = 0.0
    for kind, s in cnn_layers(cfg):
        if kind != "dense":
            continue
        flops += 2.0 * batch * s["cin"] * s["cout"]
        byts += itemsize * (s["cin"] * s["cout"] + s["cout"]
                            + batch * (s["cin"] + s["cout"]))
    return flops, byts


# ---------------------------------------------------------------------------
# Decoder-only transformers (the serving path)
# ---------------------------------------------------------------------------
def lm_shape(cfg: dict) -> Dict[str, int]:
    """The widths of a Hugging Face style ``config.json``."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), ff=cfg["intermediate_size"],
                v=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                bias=int(bool(cfg.get("attention_bias", False))))


def lm_layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer: Q, K, V and output projections
    (with Q/K/V biases where the model has them), the gated MLP, and
    the two RMSNorm gains."""
    s = lm_shape(cfg)
    q, kv = s["h"] * s["hd"], s["hkv"] * s["hd"]
    attn = s["d"] * (q + 2 * kv) + q * s["d"] + s["bias"] * (q + 2 * kv)
    return attn + 3 * s["d"] * s["ff"] + 2 * s["d"]


def lm_layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer that multiply every token."""
    s = lm_shape(cfg)
    q, kv = s["h"] * s["hd"], s["hkv"] * s["hd"]
    return s["d"] * (q + 2 * kv) + q * s["d"] + 3 * s["d"] * s["ff"]


def lm_token_flops(cfg: dict, context: int, head: bool) -> float:
    """Operations to run one token through every layer while it attends
    to ``context`` positions (itself included), plus the output head
    when ``head``."""
    s = lm_shape(cfg)
    per_layer = (2.0 * lm_layer_matmul_params(cfg)
                 + 4.0 * s["h"] * s["hd"] * context)
    return s["layers"] * per_layer + (2.0 * s["d"] * s["v"] if head else 0)


def lm_prefill_flops(cfg: dict, start: int, n: int) -> float:
    """Operations of prefilling ``n`` prompt tokens at positions
    ``[start, start + n)``, with the head on the last one only."""
    s = lm_shape(cfg)
    per_layer = 2.0 * lm_layer_matmul_params(cfg) * n
    # token at position p attends to p + 1 positions
    ctx = n * start + n * (n + 1) / 2.0
    per_layer_attn = 4.0 * s["h"] * s["hd"] * ctx
    return s["layers"] * (per_layer + per_layer_attn) + 2.0 * s["d"] * s["v"]


def lm_weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    """Bytes of the weights one decode step reads: every layer and the
    output head (the embedding is gathered a row a token)."""
    s = lm_shape(cfg)
    return itemsize * (s["layers"] * lm_layer_params(cfg) + s["d"] * s["v"])


def lm_decode_cost(cfg: dict, contexts: Sequence[int], slots: int,
                   itemsize: int = 2) -> Tuple[float, float]:
    """Operations and bytes of one decode step over the live slots,
    each attending to its ``contexts`` entry: the weights once, the
    live K/V rows read and the new ones written, and the float32
    logits of every slot of the program."""
    s = lm_shape(cfg)
    flops = sum(lm_token_flops(cfg, c, head=True) for c in contexts)
    kv_row = s["layers"] * 2 * s["hkv"] * s["hd"] * itemsize
    byts = (lm_weight_bytes(cfg, itemsize) + kv_row * sum(contexts)
            + 4.0 * slots * s["v"])
    return flops, byts
