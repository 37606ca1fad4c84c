"""Capture a replayable compile bundle for one Table-1 config.

CI's bench-smoke job runs this for C-BH, then immediately replays the
bundle in the same job (``python -m repro.replay <bundle>``) as a
zero-divergence assert, and uploads it as a build artifact next to
``BENCH_pr.json`` — so any perf or accuracy question about a CI run can
be reproduced offline from the artifact alone.

Usage::

    python -m benchmarks.capture_smoke --config C-BH \
        --out benchmarks/artifacts/capture-C-BH [--autotune full]
"""

from __future__ import annotations

import argparse
import os
from typing import Optional, Sequence

import repro

from .table1_models import SUITE


def capture(config: str, out: str, *, autotune: str = "full",
            budget_ms: float = 1000.0, batch_size: int = 1) -> str:
    """Compile ``config`` with capture enabled; returns the bundle dir."""
    g = SUITE[config]()
    exe = repro.compile(g, repro.CompileOptions(
        target="pallas", autotune=autotune,
        autotune_budget_ms=budget_ms, capture=out))
    exe.ensure_compiled(batch_size)
    return exe.capture_path


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="C-BH",
                    help=f"one of {sorted(SUITE)} (default: C-BH)")
    ap.add_argument("--out", required=True,
                    help="bundle directory to write")
    ap.add_argument("--autotune", default="full",
                    choices=("off", "cached", "full"))
    ap.add_argument("--autotune-budget-ms", type=float, default=1000.0)
    ap.add_argument("--batch-size", type=int, default=1)
    args = ap.parse_args(argv)

    from repro.api.cache import use_compilation_cache
    use_compilation_cache()
    if args.config not in SUITE:
        raise SystemExit(f"unknown config {args.config!r}; "
                         f"choose from {sorted(SUITE)}")
    path = capture(args.config, args.out, autotune=args.autotune,
                   budget_ms=args.autotune_budget_ms,
                   batch_size=args.batch_size)
    n_files = sum(len(f) for _, _, f in os.walk(path))
    print(f"[capture_smoke] wrote bundle {path} ({n_files} files); "
          f"replay with: python -m repro.replay {path}")


if __name__ == "__main__":
    main()
