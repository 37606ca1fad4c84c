"""Calibration-driven low-precision quantization (the ROADMAP's
"TensorRT playbook": calibrated ranges + per-site tactic selection).

The pass consumes a quantization *request* riding on ``graph.quant``
(the same contract ``dist`` uses — targets attach it from
``CompileOptions(precision=..., calibrate=...)``), and annotates the
graph instead of rewriting arithmetic:

* a **calibration walk** runs ``calibrate`` seeded sample batches
  through the oracle semantics of the *current* (fused, folded) graph,
  recording per-tensor ``|x|`` abs-max and 99.9th-percentile ranges;
* every eligible site (``dense``, ``conv2d``) is annotated with
  ``quant.mode`` plus — for int8 — the calibrated per-tensor input
  scale (``quant.x_scale``) and per-output-channel weight scales
  (``quant.w_scale``, computed from the static f32 weights).  Zero
  points are always 0 (symmetric quantization; ``quant.zp`` records
  it);
* ``mode="mixed"`` measures per-site f32/bf16/int8 candidates under
  the autotune :class:`~repro.autotune.measure.Deadline`, persists
  winners in the fingerprinted tactic cache, and only picks a narrow
  dtype where it is both faster *and* within the accuracy budget
  (max_abs_err vs the f32 calibration outputs).

Annotations are plain node attrs, so they flow into
``Graph.structure_hash()`` — executable and tactic cache keys stay
correct with no extra plumbing — and survive ``serialize()`` through
the container's attr round-trip.  The actual low-precision arithmetic
lives in the lowering rules and ``repro.kernels.qmath``: every target
(interpret/jit/pallas) reads the same attrs and runs the same shared
expressions, which is what keeps them golden-comparable.

Scheduling: after ``fuse_activation.post_bn`` (calibration must see
the folded weights the compiled program will actually quantize) and
before ``optimize_layout`` (the walk interprets logical ``(K, N)``
kernels; layout transposes/pads afterwards, and the per-channel scales
are layout-invariant).

Backend-aware prior (documented in docs/quantization.md): int8 conv
sites stay f32 off-TPU — XLA's CPU int8 convolutions are slower than
f32, so quantizing them would trade accuracy for a slowdown; int8
dense sites lower to the reference ``lax.dot_general`` int8 path on
CPU and the dedicated Pallas q8 kernel on TPU.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..graph import Graph, Node
from .manager import register_pass
from ...kernels import qmath
from ...kernels.platform import on_tpu

#: Ops the pass may rewrite to low precision.
QUANT_OPS = ("dense", "conv2d")
#: Sample batches when the request does not say (CompileOptions leaves
#: ``calibrate=None``).
DEFAULT_CALIBRATE = 4
#: Rows per calibration batch.
CALIBRATION_BATCH = 4
#: Accuracy budget (max_abs_err vs the f32 calibration outputs) for
#: ``mode="mixed"`` when the request carries none.
DEFAULT_PRECISION_BUDGET = 0.05
#: Default wall-clock budget for mixed-mode measurement.
DEFAULT_MEASURE_BUDGET_MS = 1000.0

_MODES = ("f32", "bf16", "int8", "mixed")


def _kernel_out_axis(node: Node) -> int:
    """Output-channel axis of the site's kernel param: dense (K, N) →
    1, conv2d HWIO → 3.  The pass runs pre-layout, so dense kernels are
    always logical "io"."""
    return 1 if node.op == "dense" else 3


def _apply_manual_epilogue(y, node: Node, params):
    """SimpleNN applies fused epilogues as separate steps but skips the
    folded post-activation affine; the calibration walk needs both, so
    the recorded ranges match what the compiled program feeds each
    quantized site."""
    from ..ops_common import apply_activation
    if node.epilogue and node.epilogue != "linear":
        y = apply_activation(node.epilogue, y, node.epilogue_attrs)
    pa = node.epilogue_attrs.get("post_affine")
    if pa:
        import jax.numpy as jnp
        y = y * jnp.asarray(params[pa[0]]) + jnp.asarray(params[pa[1]])
    return y


def _calibrate(graph: Graph, batches: int,
               sites: List[Node]) -> Tuple[Dict, Dict, Dict]:
    """Seeded oracle walk over the current graph.  Returns
    ``(ranges, first_inputs, first_outputs)``: per-tensor
    ``{"absmax", "p999"}`` stats over every batch, plus the first
    batch's input/f32-output arrays for each site (what mixed-mode
    accuracy checks diff against)."""
    import jax.numpy as jnp
    from ..simple import SimpleNN

    sim = SimpleNN(graph)
    site_names = {n.name for n in sites}
    rng = np.random.default_rng(0)
    ranges: Dict[str, Dict[str, float]] = {}
    first_inputs: Dict[str, np.ndarray] = {}
    first_outputs: Dict[str, np.ndarray] = {}
    for bi in range(batches):
        env: Dict[str, jnp.ndarray] = {
            name: jnp.asarray(
                rng.standard_normal((CALIBRATION_BATCH,) + spec.shape)
                .astype(np.float32))
            for name, spec in graph.inputs.items()
        }
        for node in graph.toposort():
            y = sim._eval(node, env, CALIBRATION_BATCH)
            y = _apply_manual_epilogue(y, node, graph.params)
            env[node.output] = y
            if bi == 0 and node.name in site_names:
                first_inputs[node.name] = np.asarray(env[node.inputs[0]])
                first_outputs[node.name] = np.asarray(y)
        for name, val in env.items():
            a = np.abs(np.asarray(val, dtype=np.float32))
            r = ranges.setdefault(name, {"absmax": 0.0, "p999": 0.0})
            r["absmax"] = max(r["absmax"], float(a.max()) if a.size else 0.0)
            if a.size:
                r["p999"] = max(r["p999"], float(np.percentile(a, 99.9)))
    return ranges, first_inputs, first_outputs


def _annotate_int8(node: Node, graph: Graph, ranges: Dict,
                   method: str) -> None:
    stat = ranges[node.inputs[0]]
    absmax = stat["p999"] if method == "percentile" else stat["absmax"]
    w = graph.params[node.params["kernel"]]
    scales = qmath.channel_scales(w, _kernel_out_axis(node))
    node.attrs["quant.mode"] = "int8"
    node.attrs["quant.method"] = method
    node.attrs["quant.x_scale"] = qmath.tensor_scale(absmax)
    # A tuple, matching the IR's attr convention (the container's JSON
    # round trip re-tuplifies lists, so tuples survive save/load as-is).
    node.attrs["quant.w_scale"] = tuple(round(float(s), 10) for s in scales)
    node.attrs["quant.zp"] = 0


def _static_site_mode(node: Node, mode: str, on_tpu: bool) -> Optional[str]:
    """The non-measured prior: which precision a site gets under a
    static ``bf16``/``int8`` request.  ``None`` = stay f32."""
    if mode == "bf16":
        return "bf16"
    if mode == "int8":
        if node.op == "conv2d" and not on_tpu:
            return None    # XLA CPU int8 conv loses to f32 — keep exact
        return "int8"
    return None


# ---------------------------------------------------------------------------
# mixed mode: per-site measured tactic selection
# ---------------------------------------------------------------------------
def _site_runner(node: Node, graph: Graph, cand: str, x: np.ndarray,
                 ranges: Dict, method: str):
    """A jitted callable + args computing this site at precision
    ``cand`` — the same expressions the lowering rules emit, measured
    on the first calibration batch."""
    import functools

    import jax
    import jax.numpy as jnp

    from ...kernels.fused_matmul.ops import fused_matmul, fused_matmul_q8
    from ..ops_common import lax_padding

    w = jnp.asarray(graph.params[node.params["kernel"]])
    b = (jnp.asarray(graph.params[node.params["bias"]])
         if "bias" in node.params else None)
    fn = (node.epilogue
          if node.epilogue not in (None, "linear", "softmax") else None)
    xj = jnp.asarray(x)
    if node.op == "dense":
        if cand == "int8":
            stat = ranges[node.inputs[0]]
            absmax = stat["p999"] if method == "percentile" else stat["absmax"]
            run = jax.jit(functools.partial(
                fused_matmul_q8,
                x_scale=qmath.tensor_scale(absmax),
                w_scales=qmath.channel_scales(np.asarray(w), 1),
                fn=fn))
        elif cand == "bf16":
            base = functools.partial(fused_matmul, fn=fn)
            run = jax.jit(lambda x, w, b: base(
                x.astype(jnp.bfloat16), w.astype(jnp.bfloat16), b))
        else:
            run = jax.jit(functools.partial(fused_matmul, fn=fn))
        return run, (xj, w, b)

    # conv2d
    strides = node.attrs["strides"]
    padding = lax_padding(node.attrs["padding"])

    def conv(x, w, b, *, dtype=None, pet=None):
        if dtype is not None:
            x, w = x.astype(dtype), w.astype(dtype)
        y = jax.lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            **({"preferred_element_type": pet} if pet else {}))
        if pet is jnp.int32:
            y = y.astype(jnp.float32)
        if b is not None:
            y = y + b
        return y

    if cand == "int8":
        stat = ranges[node.inputs[0]]
        absmax = stat["p999"] if method == "percentile" else stat["absmax"]
        xs = qmath.tensor_scale(absmax)
        ws = qmath.channel_scales(np.asarray(w), 3)
        deq = qmath.dequant_scales(xs, ws)

        def run_q8(x, w, b):
            xq = qmath.quantize_q8(x, jnp.float32(xs))
            wq = qmath.quantize_q8(w, jnp.asarray(ws)[None, None, None, :])
            y = jax.lax.conv_general_dilated(
                xq, wq, window_strides=strides, padding=padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.int32)
            y = y.astype(jnp.float32) * deq
            if b is not None:
                y = y + b
            return y

        return jax.jit(run_q8), (xj, w, b)
    if cand == "bf16":
        return jax.jit(functools.partial(
            conv, dtype=jnp.bfloat16, pet=jnp.float32)), (xj, w, b)
    return jax.jit(conv), (xj, w, b)


def _tune_mixed(nodes: List[Node], graph: Graph, ranges: Dict,
                first_inputs: Dict, req: Dict, on_tpu: bool,
                method: str) -> Tuple[Dict[str, str], Dict]:
    """Measured per-site precision selection: candidates are f32 plus
    every narrow dtype the static prior would allow; the winner is the
    fastest candidate whose max_abs_err against the f32 site output on
    the calibration batch stays within the budget.  Winners persist in
    the shared fingerprinted tactic cache keyed by (site shape ×
    epilogue × budget)."""
    from ...autotune.cache import environment_fingerprint, tactic_key
    from ...autotune.measure import Deadline, bench_min_us

    budget = req.get("budget") or DEFAULT_PRECISION_BUDGET
    measure = req.get("measure", True)
    fp = environment_fingerprint()   # may bring the backend up
    deadline = Deadline(req.get("budget_ms", DEFAULT_MEASURE_BUDGET_MS)
                        if measure else 0.0)
    cache = None
    if req.get("use_cache", True):
        from ...autotune import open_tactic_cache
        cache = open_tactic_cache(req.get("cache_dir"))

    decisions: Dict[str, str] = {}
    entries: Dict[str, dict] = {}
    specs = graph.infer_shapes()
    for node in nodes:
        in_spec = specs[node.inputs[0]]
        cands = ["f32"]
        for cand in ("bf16", "int8"):
            if _static_site_mode(node, cand, on_tpu) == cand:
                cands.append(cand)
        desc = {"kind": "precision", "op": node.op,
                "in_shape": list(in_spec.shape), "in_dtype": in_spec.dtype,
                "kshape": list(graph.params[node.params["kernel"]].shape),
                "epilogue": node.epilogue or "",
                "has_bias": "bias" in node.params,
                "budget": budget, "method": method,
                "batch": CALIBRATION_BATCH, "tpu": on_tpu}
        key = tactic_key(desc, fp)
        entry = cache.load(key, fp) if cache is not None else None
        if entry is None and measure and not deadline.expired():
            x = first_inputs[node.name]
            measured, errs = {}, {}
            want = None
            for cand in cands:
                if deadline.expired() and cand != "f32":
                    break
                try:
                    run, args = _site_runner(node, graph, cand, x,
                                             ranges, method)
                    out = np.asarray(run(*args))
                except Exception:
                    continue
                if cand == "f32":
                    want = out
                    errs[cand] = 0.0
                else:
                    errs[cand] = (float(np.abs(out - want).max())
                                  if want is not None else float("inf"))
                us = bench_min_us(run, args, reps=5, warmup=1,
                                  deadline=deadline)
                if us is not None:
                    measured[cand] = us
            ok = [c for c in measured
                  if errs.get(c, float("inf")) <= budget or c == "f32"]
            if ok:
                winner = min(ok, key=lambda c: measured[c])
                entry = {"winner": winner,
                         "measured_us": {k: round(v, 3)
                                         for k, v in measured.items()},
                         "max_abs_err": {k: round(v, 8)
                                         for k, v in errs.items()},
                         "desc": desc, "fingerprint": fp}
                if cache is not None:
                    cache.store(key, entry)
        if entry is not None:
            decisions[node.name] = entry["winner"]
            entries[key] = entry
        else:
            decisions[node.name] = "f32"   # no data: stay exact
    report = {"spent_ms": round(deadline.spent_ms(), 3),
              "budget": budget, "entries": len(entries)}
    return decisions, report


# ---------------------------------------------------------------------------
@register_pass("quantize", after=("fuse_activation.post_bn",),
               before=("optimize_layout",))
def quantize(graph: Graph) -> Tuple[Graph, Dict]:
    """Annotate eligible sites with calibrated ``quant.*`` attrs per
    the request on ``graph.quant``; a no-op (zero annotations, ``quant``
    cleared) without a request or under ``mode="f32"``."""
    req = graph.quant
    if not req or req.get("mode") in (None, "f32"):
        if req:
            graph.quant = {"mode": "f32"}
        return graph, {"sites": 0}
    mode = req["mode"]
    if mode not in _MODES:
        raise ValueError(f"unknown quantization mode {mode!r}; "
                         f"expected one of {_MODES}")
    method = req.get("method", "absmax")
    tpu = on_tpu()
    sites = [n for n in graph.nodes
             if n.op in QUANT_OPS and "kernel" in n.params]

    counts = {"f32": 0, "bf16": 0, "int8": 0}
    stats: Dict[str, object] = {"sites": len(sites), "mode": mode}
    ranges: Dict = {}
    need_calibration = mode in ("int8", "mixed")
    first_inputs: Dict[str, np.ndarray] = {}
    if need_calibration and sites:
        batches = int(req.get("calibrate") or DEFAULT_CALIBRATE)
        ranges, first_inputs, _ = _calibrate(graph, batches, sites)
        stats["calibrate_batches"] = batches
        stats["calibrated_tensors"] = len(ranges)

    if mode == "mixed" and sites:
        decisions, tune_report = _tune_mixed(
            sites, graph, ranges, first_inputs, req, tpu, method)
        stats["mixed"] = tune_report
    else:
        decisions = {n.name: (_static_site_mode(n, mode, tpu) or "f32")
                     for n in sites}

    for node in sites:
        site_mode = decisions.get(node.name, "f32")
        counts[site_mode] += 1
        if site_mode == "bf16":
            node.attrs["quant.mode"] = "bf16"
        elif site_mode == "int8":
            _annotate_int8(node, graph, ranges, method)
    stats.update(counts)
    # The surviving graph-level record is semantic only: the mode (and
    # per-mode site counts for introspection).  Request-side knobs that
    # must not leak into structure_hash — cache_dir, measurement
    # budgets — are consumed here and dropped.
    graph.quant = {"mode": mode, "decisions": dict(counts)}
    return graph, stats
