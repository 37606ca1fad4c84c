"""The profile-guided tuning pass: heuristic prior → measured winners.

``tune_selection`` takes the static selector's per-node decisions (the
*prior*) and refines them:

* ``mode="cached"`` — consult the persistent tactic cache only; nodes
  without a valid entry keep the heuristic choice.  Zero measurement,
  deterministic, safe for production compiles.
* ``mode="full"`` — additionally micro-benchmark the candidate set for
  any node the cache has no entry for, within ``budget_ms`` of wall
  clock (jit compiles of candidates count against the budget), and
  record each winner in the cache for every future process.

Identical shapes share one measurement within a pass (a 40-layer MLP
with one repeated dense geometry measures it once), and the tuned
:class:`~repro.core.selection.KernelChoice` records ``source=
"measured"``, the winning block geometry, and every candidate's µs so
``cost_summary()`` can answer "why this kernel, and by how much".
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..core.selection import KernelChoice
from .cache import TacticCache, environment_fingerprint, tactic_key
from .measure import Deadline, bench_min_us
from .tactics import Tactic, candidates_for_node

#: Micro-benchmark reps per candidate (min-of-reps estimator).
MEASURE_REPS = 5
MEASURE_WARMUP = 1

AUTOTUNE_MODES = ("off", "cached", "full")


def _measure_candidates(node_tactics, deadline: Deadline
                        ) -> Optional[dict]:
    """Benchmark every candidate; return a cache entry for the winner,
    or None if the budget ran out before any candidate finished."""
    measured: Dict[str, float] = {}
    best: Optional[Tuple[Tactic, float]] = None
    for tactic, fn, args in node_tactics.make_candidates():
        # Once over budget, stop *before* the next candidate's jit
        # compile — otherwise an expired deadline would still pay for
        # compiling the whole candidate set just to discard it.
        if deadline.expired():
            break
        us = bench_min_us(fn, args, reps=MEASURE_REPS,
                          warmup=MEASURE_WARMUP, deadline=deadline)
        if us is None:
            continue
        measured[tactic.label] = us
        if best is None or us < best[1]:
            best = (tactic, us)
    if best is None:
        return None
    tactic, us = best
    return {
        "winner": tactic.kernel,
        "winner_label": tactic.label,
        "block": list(tactic.block) if tactic.block else None,
        "best_us": us,
        "measured_us": {k: round(v, 3) for k, v in measured.items()},
        "desc": node_tactics.desc,
        "fingerprint": environment_fingerprint(),
    }


def _measured_choice(node, op: str, entry: dict, prior: KernelChoice
                     ) -> KernelChoice:
    n_cands = len(entry.get("measured_us", {}))
    best_us = entry.get("best_us")
    reason = (f"measured {entry.get('winner_label', entry['winner'])}"
              + (f" = {best_us:.1f}us" if isinstance(best_us, (int, float))
                 else "")
              + f" (best of {n_cands} tactics; "
              f"heuristic prior: {prior.kernel})")
    block = entry.get("block")
    return KernelChoice(
        node.name, op, entry["winner"], reason,
        source="measured",
        block=tuple(block) if block else None,
        measured_us=dict(entry.get("measured_us", {})))


def tune_selection(
    graph,
    selection: Dict[str, KernelChoice],
    *,
    batch_size: int,
    precision: str,
    mode: str,
    budget_ms: Optional[float],
    cache: Optional[TacticCache],
) -> Tuple[Dict[str, KernelChoice], dict]:
    """Refine ``selection`` with cached/measured tactics.

    Returns ``(tuned_selection, report)``; on any per-node failure the
    heuristic choice survives untouched — autotuning can only ever
    *change* a decision on the strength of a measurement.
    """
    if mode not in ("cached", "full"):
        raise ValueError(f"autotune mode must be 'cached' or 'full' here, "
                         f"got {mode!r}")
    fingerprint = environment_fingerprint()   # may bring the backend up
    deadline = Deadline(budget_ms if mode == "full" else None)
    memo: Dict[str, dict] = {}
    tuned: Dict[str, KernelChoice] = dict(selection)
    measured_nodes, cached_nodes, heuristic_nodes = [], [], []

    specs = graph.infer_shapes()
    for node in graph.nodes:
        prior = selection.get(node.name)
        if prior is None:
            continue
        nt = candidates_for_node(node, graph, specs,
                                 batch_size=batch_size, precision=precision)
        if nt is None:        # single legal implementation: nothing to tune
            continue
        key = tactic_key(nt.desc, fingerprint)
        entry = memo.get(key)
        from_memo = entry is not None
        if entry is None and cache is not None:
            entry = cache.load(key, fingerprint)
            if entry is not None:
                memo[key] = entry
        if entry is None and mode == "full" and not deadline.expired():
            entry = _measure_candidates(nt, deadline)
            if entry is not None:
                memo[key] = entry
                if cache is not None:
                    cache.store(key, entry)
                measured_nodes.append(node.name)
        elif entry is not None and not from_memo and cache is not None:
            cached_nodes.append(node.name)
        if entry is not None:
            tuned[node.name] = _measured_choice(node, prior.op, entry, prior)
            if from_memo and node.name not in measured_nodes:
                cached_nodes.append(node.name)
        else:
            heuristic_nodes.append(node.name)

    report = {
        "mode": mode,
        "budget_ms": budget_ms,
        "spent_ms": round(deadline.spent_ms(), 3),
        "measured_nodes": measured_nodes,
        "cached_nodes": cached_nodes,
        "heuristic_nodes": heuristic_nodes,
        "cache": cache.stats() if cache is not None else None,
        # Raw cache entries by tactic key — capture bundles persist these
        # so replay can seed a fresh cache and reproduce the selection
        # with mode="cached".  Stripped from cost_summary().
        "entries": dict(memo),
    }
    return tuned, report
