"""Pass ablations: each optimization pass toggled off, measuring node
count, memory-plan arena and runtime on the Table-1 suite — the paper's
§3 design claims, quantified one mechanism at a time.

Variants are registry operations, not hand-edited tuples:
``PassManager.default().without("fold_batchnorm")`` drops every
registered instance of a pass (base-name match, so both
``fuse_activation`` runs disappear together), and the resulting resolved
pipeline is what ``repro.compile`` runs.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax

import repro
from repro.core.passes import PassManager

from .table1_models import SUITE


def variants() -> Dict[str, PassManager]:
    full = PassManager.default()
    return {
        "full": full,
        "no_bn_fold": full.without("fold_batchnorm"),
        "no_act_fusion": full.without("fuse_activation"),
        "no_pad_merge": full.without("fuse_pad"),
        "no_layout": full.without("optimize_layout"),
        "none": PassManager(("canonicalize",)),
    }


def run(models: Sequence[str] = ("C-BH", "MobileNetV2"),
        reps: int = 15) -> List[Dict]:
    rng = np.random.default_rng(0)
    rows = []
    for name in models:
        g = SUITE[name]()
        in_name = next(iter(g.inputs))
        x = rng.standard_normal((1,) + g.inputs[in_name].shape) \
            .astype(np.float32)
        for variant, pm in variants().items():
            exe = repro.compile(g, repro.CompileOptions(passes=pm.pipeline))
            fn = exe.ensure_compiled(batch_size=1)  # time the raw program
            for _ in range(3):
                jax.block_until_ready(fn(x))
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn(x))
            dt = (time.perf_counter() - t0) / reps
            cost = exe.cost_summary()
            # Which graph-level decisions were actually active in this
            # variant: the pass counters (how many sites fused /
            # re-laid-out) plus any autotuned decision report — so a
            # trajectory entry is attributable to its decisions.
            stats = {}
            for p in cost["passes"]:
                for key in ("fused_activations", "transposed", "padded"):
                    if key in p:
                        stats[key] = stats.get(key, 0) + p[key]
            rows.append({
                "model": name,
                "variant": variant,
                "pipeline": list(cost["pipeline"]),
                "nodes": cost["nodes"],
                "arena_kb": cost["memory_plan"]["arena_bytes"] / 1024,
                "inplace": cost["memory_plan"]["inplace_count"],
                "pass_time_ms": sum(p["time_ms"] for p in cost["passes"]),
                "time_ms": dt * 1e3,
                "decisions": stats,
                "autotune": cost.get("graph_decisions"),
            })
    return rows


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--models", nargs="*", metavar="NAME",
                    default=("C-BH", "MobileNetV2"),
                    help=f"subset of {sorted(SUITE)}")
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--json", metavar="PATH",
                    help="also write the rows as a JSON artifact")
    args = ap.parse_args(argv)

    from repro.api.cache import use_compilation_cache
    use_compilation_cache()
    unknown = sorted(set(args.models) - set(SUITE))
    if unknown:
        raise SystemExit(f"unknown models {unknown}; "
                         f"choose from {sorted(SUITE)}")

    rows = run(models=args.models, reps=args.reps)
    hdr = f"{'model':<12} {'variant':<14} {'nodes':>6} {'arena KB':>9} " \
          f"{'inplace':>8} {'ms/call':>8}"
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['model']:<12} {r['variant']:<14} {r['nodes']:>6} "
              f"{r['arena_kb']:>9.1f} {r['inplace']:>8} "
              f"{r['time_ms']:>8.3f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "ablations", "rows": rows}, f,
                      indent=2, sort_keys=True)
        print(f"[ablations] wrote {args.json}")


if __name__ == "__main__":
    main()
