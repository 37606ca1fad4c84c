#!/usr/bin/env python3
"""Find the highest rate a serving cell's server sustains: one set-up,
then each rate of ``--rates`` for ``--seconds``, from a drained server.

    python3 bench/sweep.py --workload qwen14b.chat --seed 5 \\
        --seconds 20 --rates 4,6,8,10

For each rate one JSON line: requests due and finished, the requests
still waiting or in flight at the end of the window (a backlog that
grows with the window means the rate is above what the server
sustains), tokens per second, and the tails of time to first token and
of the gap between tokens.  A cell's mix states its rate; this tool is
how it was chosen, and is not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from bench import device, record, spec, stats, traffic
    from bench.runners import serve
    from bench.run import CompileWatch, use_compilation_cache
    cell = spec.resolve(spec.load(ROOT), ROOT, args.workload)
    device.check(jax.devices(), cell.chips, device.load_peaks())
    use_compilation_cache(ROOT)
    say = lambda *a: print(*a, file=sys.stderr, flush=True)
    watch = CompileWatch()
    cfg, mix = cell.config, dict(cell.traffic)
    loop, _ = serve.build(cell, cell.config_module(), args.seed, say)
    rates = [float(r) for r in args.rates.split(",")]
    serve.warm(loop, cell, args.seed, say, watch, rate=rates[0])
    uid0 = 0
    for rate in rates:
        loop.serve([], time.perf_counter(), time.perf_counter() + 600, False,
                   stop_when_idle=True)                  # drain
        mix["rate_per_s"] = rate
        arr = traffic.open_loop(mix, args.seed, args.seconds,
                                cfg["vocab_size"], uid0=uid0)
        uid0 += len(arr)
        rec = record.Run(cfg, mix)
        loop.rec = rec
        t0 = time.perf_counter()
        half = None
        left = loop.serve(arr, t0, t0 + args.seconds / 2, True)
        m = loop.sched.metrics
        half = m.submitted - m.completed
        t1 = time.perf_counter()
        left = loop.serve(left, t0, t0 + args.seconds, True)
        end = m.submitted - m.completed
        rec.window_start, rec.window_end = t0, time.perf_counter()
        ttft = [r.ttft() for r in rec.requests if r.ttft() is not None]
        gaps = [g for r in rec.requests for g in r.gaps()]
        made = sum(s.tokens for s in rec.steps)
        print(json.dumps({
            "rate_per_s": rate, "due": len(arr), "submitted": len(rec.requests),
            "finished": sum(r.finished for r in rec.requests),
            "backlog_half": half, "backlog_end": end,
            "tokens_per_s": made / rec.window_s,
            "ttft_ms_p50": 1e3 * (stats.percentile(ttft, 50) or 0),
            "ttft_ms_p95": 1e3 * (stats.percentile(ttft, 95) or 0),
            "itl_ms_p50": 1e3 * (stats.percentile(gaps, 50) or 0),
            "itl_ms_p95": 1e3 * (stats.percentile(gaps, 95) or 0),
            "steps": len(rec.steps),
            "step_ms_mean": 1e3 * sum(s.end - s.start for s in rec.steps)
            / max(1, len(rec.steps))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
