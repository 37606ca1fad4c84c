#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from: for each seed,
the compared number of the program (the lower reading) and of the
control, the reference at the precision below the configuration's put
in the program's place (the upper reading).

    python3 bench/control.py --workload qwen14b.chat --seconds 20 \\
        --seeds 101,102,103

Each seed is a short run of the cell through its runner, at the cell's
own sizes and load, all in one process; the results go to standard
output as one JSON line per seed, with ``correct`` for the program and
``control_correct`` for the control, each by the run's own comparison.  The benchmark's own runs never run
the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def readings(rec) -> dict:
    """The compared number of the program and of the control on the
    same requests or inputs, each judged by the run's own comparison
    (``record.Run.check``) against the configuration's limit."""
    from bench import record
    (chk,) = rec.checks
    control = rec.control_reading()
    judge = record.Run(rec.config, rec.traffic)
    return {"number": chk["name"], "program": chk["value"],
            "control": control, "limit": chk["limit"],
            "correct": rec.correct,
            "control_correct": judge.check(chk["name"], control,
                                           chk["limit"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib
    import jax
    from bench import device, spec
    from bench.run import CompileWatch, use_compilation_cache
    cell = spec.resolve(spec.load(ROOT), ROOT, args.workload)
    device.check(jax.devices(), cell.chips, device.load_peaks())
    use_compilation_cache(ROOT)
    runner = importlib.import_module(
        f"bench.runners.{cell.config['runner']}")
    say = lambda *a: print(*a, file=sys.stderr, flush=True)
    watch = CompileWatch()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        rec = runner.run(cell, cell.config_module(), seed=seed,
                         seconds=args.seconds, trace_dir=None, t_start=t,
                         watch=watch, say=say)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          **readings(rec), "attempted": rec.attempted,
                          "failed": rec.failed}), flush=True)
        del rec
    return 0


if __name__ == "__main__":
    sys.exit(main())
