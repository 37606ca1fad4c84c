"""Frontend-layer microbenchmarks: model construction and trace cost.

Two questions the frontend PR answers with numbers:

  1. *Construction scales linearly.*  ``ModelBuilder`` used to re-run
     full-graph shape inference per layer (O(n²) in layers); the
     incremental spec cache makes it O(n).  This script times an
     N-layer MLP build at several depths so a regression back to
     quadratic is obvious (the per-layer cost column would grow with
     depth instead of staying flat).

  2. *Tracing costs what building costs.*  ``repro.trace`` over an
     equivalent plain function should be within noise of the builder —
     both are one ``add_node`` per layer — and the two graphs must
     produce identical compiled outputs.

Usage::

    PYTHONPATH=src python -m benchmarks.frontend_bench [--layers 64 256 1024]
                                                       [--width 64] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, Optional, Sequence

import numpy as np

import repro
from repro.core import ModelBuilder
from repro.frontends import ops as F


def build_mlp(layers: int, width: int):
    mb = ModelBuilder().seed(0)
    h = mb.input((width,))
    for _ in range(layers):
        h = mb.dense(h, width, activation="relu")
    return mb.build([h])


def trace_mlp(params: Dict[str, np.ndarray], layers: int, width: int):
    def fn(input):                                  # noqa: A002 (match builder)
        h = input
        for i in range(layers):
            h = F.dense(h, params[f"dense_{2 * i + 1}/kernel"],
                        params[f"dense_{2 * i + 1}/bias"],
                        activation="relu")
        return h

    return repro.trace(fn, (width,))


def run(layers_list: Sequence[int], width: int) -> Dict[str, Dict[str, float]]:
    rows: Dict[str, Dict[str, float]] = {}
    for layers in layers_list:
        t0 = time.perf_counter()
        g = build_mlp(layers, width)
        t_build = time.perf_counter() - t0

        t0 = time.perf_counter()
        tg = trace_mlp(g.params, layers, width)
        t_trace = time.perf_counter() - t0

        x = np.random.default_rng(0).standard_normal(
            (1, width)).astype(np.float32)
        a = repro.compile(g, target="interpret")(x)
        b = repro.compile(tg, target="interpret")(x)
        err = float(np.abs(np.asarray(list(a.values())[0])
                           - np.asarray(list(b.values())[0])).max())

        rows[str(layers)] = {
            "build_ms": t_build * 1e3,
            "build_us_per_layer": t_build / layers * 1e6,
            "trace_ms": t_trace * 1e3,
            "trace_us_per_layer": t_trace / layers * 1e6,
            "trace_vs_build_err": err,
        }
    return rows


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--layers", type=int, nargs="*", default=[64, 256, 1024])
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--json", metavar="PATH")
    args = ap.parse_args(argv)

    from repro.api.cache import use_compilation_cache
    use_compilation_cache()

    rows = run(args.layers, args.width)
    hdr = (f"{'layers':>7} {'build ms':>9} {'µs/layer':>9} "
           f"{'trace ms':>9} {'µs/layer':>9} {'max err':>9}")
    print(hdr)
    print("-" * len(hdr))
    for n, r in rows.items():
        print(f"{n:>7} {r['build_ms']:>9.1f} {r['build_us_per_layer']:>9.1f} "
              f"{r['trace_ms']:>9.1f} {r['trace_us_per_layer']:>9.1f} "
              f"{r['trace_vs_build_err']:>9.2e}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"bench": "frontend", "rows": rows}, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
