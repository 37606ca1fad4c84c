"""Graph-level decision tuning — measure what the *passes* guess.

PR 5's kernel autotuner measures per-node *lowering* choices; this
module extends the same measure-once-remember-forever machinery to the
decisions the pass pipeline makes structurally:

* **fusion** — ``fuse_activation`` fuses every legal producer→activation
  pair; per site that is a guess (XLA sometimes schedules the unfused
  pair better on CPU).  Candidate choices: ``"fuse"`` / ``"no_fuse"``.
* **layout** — ``optimize_layout`` picks the dense kernel's storage
  layout (``"oi"`` contraction-major vs ``"io"``) from a row-count
  heuristic.  Candidate choices: ``"oi"`` / ``"io"``.
* **pipeline** — whole-pipeline variants from
  :func:`repro.core.passes.manager.pipeline_candidates`
  (``PassManager.default().without(...)`` registry surgery), measured on
  the fully lowered graph.

Each site is keyed by a **graph-region digest** — a canonical hash of
the affected subgraph's structure, shapes and dtypes that is invariant
to node naming and insertion order (see :func:`region_digest`) — so a
measured winner transfers to any model containing the same region, and
winners persist in the same fingerprinted
:class:`~repro.autotune.cache.TacticCache` the kernel tuner uses:
``CompileOptions(autotune="cached")`` replays every decision
cross-process with zero measurement.

Decisions are *applied* through tuning-site hooks the passes expose
(``tune.fuse`` / ``tune.layout`` node attrs, honored by
``fuse_activation`` and ``optimize_layout``); with ``autotune="off"``
no attr is ever written and the pipeline is bit-identical to the
heuristic compiler.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.graph import Graph, ACTIVATIONS
from ..core.passes.fuse_activation import FUSABLE_PRODUCERS, TUNE_FUSE_ATTR
from ..core.passes.layout import TUNE_LAYOUT_ATTR
from ..core.passes.manager import PassManager, pipeline_candidates
from ..core.selection import select_kernels
from .cache import TacticCache, environment_fingerprint, tactic_key
from .measure import Deadline, bench_min_us
from .tuner import MEASURE_REPS, MEASURE_WARMUP

#: Bump when the digest canonicalization or the decision semantics
#: change — old cache entries must miss, not replay a stale meaning.
GRAPH_DECISION_VERSION = 1

#: Fraction of ``autotune_budget_ms`` graph-level tuning may spend;
#: the remainder is reserved for the per-node kernel tuner so a slow
#: pipeline-variant measurement can never starve kernel tactics.
GRAPH_BUDGET_FRACTION = 0.5


# ---------------------------------------------------------------------------
# region digest
# ---------------------------------------------------------------------------
def _node_struct_hash(node, graph: Graph, specs, internal: Dict[str, str]
                      ) -> str:
    """Canonical hash of one node: op, attrs (minus ``tune.*``), param
    roles with shapes/dtypes, epilogue, and inputs identified either by
    the producing region-node's hash (internal) or by shape+dtype
    (external) — never by tensor or node *name*."""
    ins = []
    for t in node.inputs:
        if t in internal:
            ins.append(["ref", internal[t]])
        else:
            s = specs[t]
            ins.append(["ext", list(s.shape), s.dtype])
    attrs = {k: v for k, v in sorted(node.attrs.items())
             if not k.startswith("tune.")}
    params = sorted(
        (role, list(graph.params[p].shape), str(graph.params[p].dtype))
        for role, p in node.params.items())
    payload = json.dumps(
        [node.op, attrs, params, node.epilogue,
         dict(sorted(node.epilogue_attrs.items())), ins],
        sort_keys=True, default=str)
    return hashlib.sha256(payload.encode()).hexdigest()


def region_digest(graph: Graph, node_names: Sequence[str]) -> str:
    """Digest of the subgraph induced by ``node_names``.

    Invariant to node/tensor naming and to node insertion order (each
    node hashes to a pure function of its content and its region-internal
    producers' hashes; the digest is over the *sorted* hash set), but
    sensitive to any structure, shape or dtype edit — exactly the
    identity a transferred tuning decision is valid for.
    """
    names = set(node_names)
    region = [n for n in graph.toposort() if n.name in names]
    if len(region) != len(names):
        missing = names - {n.name for n in region}
        raise KeyError(f"region names not in graph: {sorted(missing)}")
    specs = graph.infer_shapes()
    internal: Dict[str, str] = {}
    hashes: List[str] = []
    for node in region:
        h = _node_struct_hash(node, graph, specs, internal)
        internal[node.output] = h
        hashes.append(h)
    payload = json.dumps([f"v{GRAPH_DECISION_VERSION}", sorted(hashes)])
    return hashlib.sha256(payload.encode()).hexdigest()


# ---------------------------------------------------------------------------
# tuning sites
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DecisionSite:
    """One graph-level tuning site: the decision kind, the node carrying
    the decision attr (``""`` for the whole-graph pipeline site), the
    region it is keyed by, and the candidate choice labels."""

    kind: str                     # "fusion" | "layout" | "pipeline"
    node: str
    region: Tuple[str, ...]
    digest: str
    choices: Tuple[str, ...]


def enumerate_sites(graph: Graph, *, passes: Optional[Sequence[str]] = None
                    ) -> List[DecisionSite]:
    """The tunable graph-level decisions of ``graph``, cheapest first.

    Fusion sites mirror ``fuse_activation``'s legality conditions on
    the *input* graph (direct producer→activation adjacency, single
    consumer); layout sites are every dense node; the single pipeline
    site is only emitted when the caller did not pin an explicit pass
    list (an explicit ``CompileOptions.passes`` is a user decision, not
    a tunable one).
    """
    sites: List[DecisionSite] = []
    for node in graph.nodes:
        if node.op == "dense":
            sites.append(DecisionSite(
                "layout", node.name, (node.name,),
                region_digest(graph, (node.name,)), ("io", "oi")))
    for act in graph.nodes:
        if act.op != "activation" or not ACTIVATIONS.get(
                act.attrs.get("fn"), False):
            continue
        src = graph.producer(act.inputs[0])
        if src is None or src.op not in FUSABLE_PRODUCERS:
            continue
        if src.epilogue not in (None, "linear"):
            continue
        if len(graph.consumers(src.output)) != 1:
            continue
        region = (src.name, act.name)
        sites.append(DecisionSite(
            "fusion", act.name, region,
            region_digest(graph, region), ("fuse", "no_fuse")))
    if passes is None and len(graph.nodes) > 1:
        variants = pipeline_candidates()
        sites.append(DecisionSite(
            "pipeline", "", tuple(n.name for n in graph.nodes),
            region_digest(graph, [n.name for n in graph.nodes]),
            tuple(variants)))
    return sites


def extract_region(graph: Graph, node_names: Sequence[str]) -> Graph:
    """A standalone mini-graph of just the named nodes: external inputs
    become graph inputs (shape+dtype from inference), referenced params
    are copied, and every region output not consumed inside the region
    becomes a graph output.  This is what decision candidates are
    measured on — the region's real shapes, isolated from the rest of
    the model."""
    names = set(node_names)
    region = [n for n in graph.toposort() if n.name in names]
    specs = graph.infer_shapes()
    produced = {n.output for n in region}
    mini = Graph()
    for node in region:
        for t in node.inputs:
            if t not in produced and t not in mini.inputs:
                mini.add_input(t, specs[t].shape, specs[t].dtype)
    for node in region:
        for p in node.params.values():
            if p not in mini.params:
                mini.add_param(p, graph.params[p])
        mini.add_node(node.op, node.name, list(node.inputs),
                      output=node.output, attrs=dict(node.attrs),
                      params=dict(node.params))
    consumed = {t for n in region for t in n.inputs}
    outs = [n.output for n in region if n.output not in consumed]
    mini.set_outputs(outs or [region[-1].output])
    return mini


# ---------------------------------------------------------------------------
# applying decisions
# ---------------------------------------------------------------------------
def apply_choice(graph: Graph, site: DecisionSite, choice: str) -> None:
    """Write the decision attr the pass hooks read.  Pipeline choices
    are not attrs (the caller swaps the pass list instead)."""
    if site.kind == "pipeline":
        return
    node = next(n for n in graph.nodes if n.name == site.node)
    if site.kind == "fusion":
        node.attrs[TUNE_FUSE_ATTR] = (choice == "fuse")
    elif site.kind == "layout":
        node.attrs[TUNE_LAYOUT_ATTR] = choice
    else:
        raise ValueError(f"unknown decision kind {site.kind!r}")


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------
def _compiled_probe(graph: Graph, pipeline, *, target: str, precision: str,
                    batch_size: int):
    """(jitted fn, args) running ``graph`` through ``pipeline`` and the
    real lowering/selection stack on seeded synthetic inputs — the same
    program shape the decision will produce in the executable."""
    import jax
    import jax.numpy as jnp

    from ..core.lowering import execute_graph

    g2, _ = PassManager(pipeline).run(graph)
    selection = select_kernels(g2, batch_size=batch_size, target=target,
                               precision=precision)
    params = {k: jnp.asarray(v) for k, v in g2.params.items()}
    input_names = list(g2.inputs)

    def program(*args):
        env = dict(zip(input_names, args))
        return execute_graph(g2, env, params, precision=precision,
                             target=target, batch_size=batch_size,
                             selection=selection)

    rng = np.random.default_rng(0)
    args = []
    for n in input_names:
        spec = g2.inputs[n]
        a = rng.standard_normal((batch_size,) + spec.shape).astype(np.float32)
        args.append(jnp.asarray(a).astype(spec.dtype))
    return jax.jit(program), args


def _measure_site(site: DecisionSite, graph: Graph, *, target: str,
                  precision: str, passes: Optional[Sequence[str]],
                  batch_size: int, deadline: Deadline) -> Optional[dict]:
    """Benchmark every choice at ``site``; returns a cache entry for the
    winner or None if the budget ran out / every candidate failed."""
    measured: Dict[str, float] = {}
    best: Optional[Tuple[str, float]] = None
    variants = pipeline_candidates() if site.kind == "pipeline" else None
    default_pipeline = (tuple(passes) if passes is not None
                        else PassManager.default().pipeline)
    for choice in site.choices:
        if deadline.expired():
            break
        try:
            if site.kind == "pipeline":
                mini = graph.copy()
                pipeline = variants[choice]
            else:
                mini = extract_region(graph, site.region)
                apply_choice(mini, site, choice)
                pipeline = default_pipeline
            fn, args = _compiled_probe(mini, pipeline, target=target,
                                       precision=precision,
                                       batch_size=batch_size)
        except Exception:
            continue        # an unbuildable candidate is not a winner
        us = bench_min_us(fn, args, reps=MEASURE_REPS,
                          warmup=MEASURE_WARMUP, deadline=deadline)
        if us is None:
            continue
        measured[choice] = us
        if best is None or us < best[1]:
            best = (choice, us)
    if best is None:
        return None
    winner, us = best
    return {
        "kind": site.kind,
        "winner": winner,
        "best_us": us,
        "measured_us": {k: round(v, 3) for k, v in measured.items()},
        "fingerprint": environment_fingerprint(),
    }


def _site_desc(site: DecisionSite, *, target: str, precision: str,
               batch_size: int) -> dict:
    """The tactic-cache key descriptor for one decision site.  Pipeline
    sites mix in the variant *contents* (pass lists), so renaming or
    re-composing a variant misses cleanly instead of replaying the old
    meaning under a reused label."""
    desc = {
        "graph_decision": site.kind,
        "v": GRAPH_DECISION_VERSION,
        "digest": site.digest,
        "target": target,
        "precision": precision,
        "batch": batch_size,
        "choices": list(site.choices),
    }
    if site.kind == "pipeline":
        desc["variants"] = {k: list(v)
                            for k, v in pipeline_candidates().items()}
    return desc


# ---------------------------------------------------------------------------
# the tuning pass
# ---------------------------------------------------------------------------
def tune_graph_decisions(
    graph: Graph,
    *,
    target: str,
    precision: str,
    passes: Optional[Sequence[str]],
    mode: str,
    budget_ms: Optional[float],
    cache: Optional[TacticCache],
    batch_size: int = 1,
) -> Tuple[Graph, Optional[Tuple[str, ...]], dict]:
    """Tune the graph-level decisions of ``graph``.

    Returns ``(decided_graph, pipeline, report)`` where ``decided_graph``
    is a copy with winning decision attrs applied, ``pipeline`` is the
    chosen pass list (``None`` = the caller's default), and ``report``
    records every site with its winner, source and per-candidate µs
    (plus the raw cache ``entries`` for capture bundles).

    ``mode="cached"`` consults the tactic cache only — deterministic,
    zero measurement, what replay uses.  ``mode="full"`` additionally
    measures unknown sites within ``budget_ms * GRAPH_BUDGET_FRACTION``
    (decisions are measured at ``batch_size``; they apply to every batch
    specialization of the executable, since the pass pipeline runs once
    per compile, not once per batch).

    Sites without a valid cache entry or measurement keep the pass
    heuristics — like the kernel tuner, tuning can only ever *change* a
    decision on the strength of a measurement.
    """
    if mode not in ("cached", "full"):
        raise ValueError(f"autotune mode must be 'cached' or 'full' here, "
                         f"got {mode!r}")
    sites = enumerate_sites(graph, passes=passes)
    graph_budget = (budget_ms * GRAPH_BUDGET_FRACTION
                    if (mode == "full" and budget_ms is not None) else
                    (None if mode == "full" else 0.0))
    # Fingerprinting reads the backend, which brings it up on a first
    # call: set-up, not measurement, so it runs before the clock starts.
    fingerprint = environment_fingerprint()
    deadline = Deadline(graph_budget)
    decided = graph.copy()
    pipeline: Optional[Tuple[str, ...]] = (tuple(passes)
                                           if passes is not None else None)
    entries: Dict[str, dict] = {}
    site_rows: List[dict] = []
    for site in sites:
        desc = _site_desc(site, target=target, precision=precision,
                          batch_size=batch_size)
        key = tactic_key(desc, fingerprint)
        entry = cache.load(key, fingerprint) if cache is not None else None
        source = "cached" if entry is not None else None
        if entry is None and mode == "full" and not deadline.expired():
            # Pipeline variants are measured on the whole graph *with*
            # the site decisions chosen so far applied — the program the
            # winning pipeline will actually compile.
            basis = decided if site.kind == "pipeline" else graph
            entry = _measure_site(site, basis, target=target,
                                  precision=precision, passes=passes,
                                  batch_size=batch_size, deadline=deadline)
            if entry is not None:
                source = "measured"
                if cache is not None:
                    cache.store(key, entry)
        row = {"kind": site.kind, "node": site.node, "digest": site.digest,
               "choices": list(site.choices)}
        if entry is not None and entry.get("winner") in site.choices:
            entries[key] = entry
            row.update(winner=entry["winner"], source=source,
                       best_us=entry.get("best_us"),
                       measured_us=dict(entry.get("measured_us", {})))
            if site.kind == "pipeline":
                if entry["winner"] != "default":
                    pipeline = tuple(pipeline_candidates()[entry["winner"]])
            else:
                apply_choice(decided, site, entry["winner"])
        else:
            row.update(winner=None, source="heuristic")
        site_rows.append(row)
    report = {
        "mode": mode,
        "budget_ms": graph_budget,
        "spent_ms": round(deadline.spent_ms(), 3),
        "sites": site_rows,
        "pipeline": list(pipeline) if pipeline is not None else None,
        "cache": cache.stats() if cache is not None else None,
        "entries": entries,
    }
    return decided, pipeline, report
