"""Shape-aware kernel selection — the compile-time cost model.

The paper's thesis is that the compiler should exploit "statically
known properties of the network"; whether a custom kernel beats the
stock lowering is exactly such a property.  This module decides, per
node and **before any code is traced**, which kernel the lowering rules
should emit, from nothing but the inferred shapes, the batch size the
program is being specialized for, and the target:

* ``dense`` under the ``"pallas"`` target uses the fused Pallas matmul
  only when the M/K/N tile picture makes sense — the block working set
  must fit VMEM and the MXU-granule padding waste must stay bounded.  A
  batch-1 GEMV against a 32×2 head pads 256× and is *still* worth
  fusing (the whole weight rides one MXU pass); a degenerate
  sub-granule matmul (1×1 "dense" = a scalar multiply) pads ~16000×
  and loses to XLA's scalar code, so it falls back to lax.
* ``activation`` under ``"pallas"`` + ``precision="fast"`` uses the
  Pallas fast-act kernel only on a real TPU with a lane-aligned minor
  dim; anywhere else the jnp reference (identical §3.4 math) wins.
* ``decode_attention`` under ``"pallas"`` requires the head dim to be a
  multiple of the 128-lane tile; otherwise the jnp reference lowers it.

Decisions are returned as :class:`KernelChoice` records (kernel + the
reason, human-readable) and surfaced through
``Executable.cost_summary()["kernel_selection"]`` so "why didn't my
layer use the fused kernel?" is answerable without a debugger.

These heuristics are the *prior*: with ``CompileOptions(autotune=
"cached"|"full")`` the profile-guided tuner (:mod:`repro.autotune`)
overrides individual choices with micro-benchmarked winners
(``source="measured"``, tuned block geometry attached); with the
default ``autotune="off"`` the decisions below are final and
bit-identical to the pre-autotuner selector.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from .graph import Graph
from ..kernels.platform import on_tpu
from ..kernels.tiles import (LANE, VMEM_BUDGET_BYTES, block_vmem_bytes,
                             ceil_to, pick_block, sublane_for)

#: Padded-MACs / logical-MACs bound for the fused matmul (lane/sublane
#: granule waste).  The table-1 suite's smallest head (32×2, batch 1)
#: wastes 256× and measurably still wins fused; a sub-granule scalar op
#: (1×1) wastes ~16k× and does not.
MAX_PAD_WASTE = 1024.0


@dataclasses.dataclass(frozen=True)
class KernelChoice:
    """One selector decision, as shown in ``cost_summary()``.

    ``source`` records whether the decision is the static heuristic's
    prior or a micro-benchmarked winner from :mod:`repro.autotune`;
    measured choices also carry the winning ``block`` geometry (honored
    by the Pallas lowering rules instead of recomputing ``pick_block``)
    and the per-candidate ``measured_us`` table.
    """

    node: str
    op: str
    kernel: str   # e.g. "pallas.fused_matmul", "lax.dot", "jnp.ref"
    reason: str
    source: str = "heuristic"          # "heuristic" | "measured"
    block: Optional[Tuple[int, ...]] = None
    measured_us: Optional[Dict[str, float]] = None

    def to_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


def _select_dense(node, in_spec, batch_size: int, n: int) -> KernelChoice:
    rows = max(1, in_spec.size // max(1, in_spec.shape[-1]))
    m = batch_size * rows
    k = in_spec.shape[-1]
    # Granules and the VMEM working set are dtype-parametrized: bf16
    # packs twice the elements per byte, so its sublane granule doubles
    # and its K-dim block cap grows instead of idling half the budget.
    # A quant.* annotation overrides the tensor dtype — the kernel will
    # consume int8 (itemsize 1) or bf16 (2) operands regardless of what
    # flows in as f32.
    qm = node.attrs.get("quant.mode")
    itemsize = {"int8": 1, "bf16": 2}.get(
        qm, int(np.dtype(in_spec.dtype).itemsize))
    if qm == "int8" and not on_tpu():
        # Backend-aware prior: off-TPU the Pallas q8 kernel only runs in
        # interpret mode, while the reference lax int8 lowering compiles
        # to real vectorized code — the measured winner by a wide margin.
        return KernelChoice(
            node.name, "dense", "lax.dot",
            "int8 site off-TPU: reference lax int8 lowering beats "
            "interpret-mode Pallas")
    sub = sublane_for(itemsize)
    m_pad, k_pad, n_pad = ceil_to(m, sub), ceil_to(k, LANE), ceil_to(n, LANE)

    bm, bk, bn = pick_block(m, k, n, itemsize)
    # VMEM legality: with today's pick_block caps the working set always
    # fits; this check is what *keeps* that true if the block geometry
    # in kernels/tiles.py is ever retuned upward.
    vmem = block_vmem_bytes(bm, bk, bn, itemsize)
    if vmem > VMEM_BUDGET_BYTES:
        return KernelChoice(
            node.name, "dense", "lax.dot",
            f"block working set {vmem // 1024} KiB exceeds VMEM budget "
            f"{VMEM_BUDGET_BYTES // 1024} KiB (M={m} K={k} N={n})")
    waste = (m_pad * k_pad * n_pad) / float(m_pad * k * n)
    if waste > MAX_PAD_WASTE:
        return KernelChoice(
            node.name, "dense", "lax.dot",
            f"sub-granule matmul: lane padding wastes {waste:.0f}x "
            f"(> {MAX_PAD_WASTE:.0f}x) at M={m} K={k} N={n}")
    kernel = ("pallas.fused_matmul_q8" if qm == "int8"
              else "pallas.fused_matmul")
    return KernelChoice(
        node.name, "dense", kernel,
        f"M={m} K={k} N={n} tiles to ({bm},{bk},{bn}), "
        f"{vmem // 1024} KiB VMEM, {waste:.1f}x pad waste",
        block=(bm, bk, bn))


def _select_activation(node, in_spec, precision: str) -> KernelChoice:
    fn = node.attrs["fn"]
    if precision != "fast":
        return KernelChoice(node.name, "activation", "jnp.act",
                            "exact precision: stock activation")
    if fn not in ("tanh", "sigmoid"):
        return KernelChoice(node.name, "activation", "jnp.act",
                            f"fast {fn} has no Pallas kernel form")
    if not on_tpu():
        return KernelChoice(
            node.name, "activation", "jnp.act",
            "no TPU: interpret-mode Pallas loses to the jnp reference")
    if in_spec.shape and in_spec.shape[-1] % LANE == 0:
        return KernelChoice(node.name, "activation", "pallas.fast_act",
                            f"minor dim {in_spec.shape[-1]} is lane-aligned")
    minor = in_spec.shape[-1] if in_spec.shape else 1
    return KernelChoice(
        node.name, "activation", "jnp.act",
        f"minor dim {minor} not a multiple of {LANE} lanes")


def _select_decode_attention(node, q_spec) -> KernelChoice:
    h, d = q_spec.shape
    if d % LANE:
        return KernelChoice(
            node.name, "decode_attention", "jnp.ref",
            f"head dim {d} not a multiple of the {LANE}-lane tile")
    return KernelChoice(
        node.name, "decode_attention", "pallas.decode_attention",
        f"H={h} D={d}: online-softmax Pallas kernel")


def select_kernels(
    graph: Graph,
    *,
    batch_size: int,
    target: Optional[str],
    precision: str = "exact",
) -> Dict[str, KernelChoice]:
    """The static selection for one (graph, batch_size, target)
    compilation.  Only ops with a kernel decision to make appear in the
    result; everything else lowers through its generic rule."""
    if target != "pallas":
        return {}
    specs = graph.infer_shapes()
    choices: Dict[str, KernelChoice] = {}
    for node in graph.nodes:
        in_spec = specs[node.inputs[0]] if node.inputs else None
        if node.op == "dense":
            # Logical N: the pre-padding width if the layout pass padded,
            # else the kernel's output dim under its recorded layout.
            kshape = graph.params[node.params["kernel"]].shape
            cout = (kshape[0] if node.attrs.get("kernel_layout") == "oi"
                    else kshape[-1])
            n = int(node.attrs.get("orig_cout", cout))
            choices[node.name] = _select_dense(node, in_spec, batch_size, n)
        elif node.op == "activation":
            choices[node.name] = _select_activation(node, in_spec, precision)
        elif node.op == "decode_attention":
            choices[node.name] = _select_decode_attention(node, in_spec)
    return choices
