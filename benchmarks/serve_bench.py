"""Serving benchmarks: shape-polymorphic and serve-hot-loop gates.

Legacy mixed-length mode (fixed-shape vs bucketed)::

    PYTHONPATH=src python -m benchmarks.serve_bench --arch qwen2.5-14b \
        --smoke --requests 16 --slots 4 --max-len 64 --out SERVE_BENCH.json

Drives the continuous-batching scheduler twice over the same synthetic
mixed-length request stream — once fixed-shape (``buckets=None``, the
pre-bucketing scheduler) and once bucketed — and emits one JSON artifact
with both summaries.  The bucketed run is split into a *warm-up wave*
(background compiles land here) and a *steady-state wave* after
``wait_warm()``; the bench asserts the steady wave serves with **zero
request-path compile stalls** (the engine-cache contract) and that its
greedy tokens are identical to the fixed-shape scheduler's, request by
request.  Exit code 1 on either violation, so CI can gate on it.

Mixed-SLO trace mode (``--trace mixed-slo``)::

    PYTHONPATH=src python -m benchmarks.serve_bench --trace mixed-slo \
        --arch qwen2.5-14b --smoke --gate --out SERVE_SLO.json

One trace of short interactive requests (tight ``slo_ms``) interleaved
with long batch requests sharing a system-prompt head, served by three
schedulers: ``fixed`` (the token oracle), ``base`` (the PR-7 feature
set: buckets + fcfs) and ``opt`` (buckets + chunked prefill + prefix
cache + deadline admission).  Reports TTFT p50/p99 (overall and for the
interactive class), steady-wave decode tok/s and ``slo_violations``,
asserts token bit-identity and zero steady-state stalls, and appends to
``benchmarks/artifacts/trajectory/``.  ``--gate`` fails on a >25%
regression of the machine-portable opt/base ratios vs the seeded
``benchmarks/artifacts/serve_baseline.json``; ``--reseed N`` rebuilds
that baseline as the worst ratio over N runs (the ``perf_gate.py``
procedure).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from .perf_gate import append_trajectory

SERVE_BASELINE = "benchmarks/artifacts/serve_baseline.json"


def synth_requests(rng, n, vocab, max_len, max_new, uid0=0):
    """Mixed-length stream: prompt lengths spread over [3, max_len/2)."""
    from repro.serve import Request
    hi = max(5, max_len // 2)
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(0, vocab,
                                        int(rng.integers(3, hi))).astype(
                                            np.int32),
                    max_new_tokens=max_new)
            for i in range(n)]


def drain(sched, reqs):
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    return time.perf_counter() - t0, {c.uid: c.tokens for c in done}


def mixed_slo_requests(rng, n, vocab, max_len, max_new, head, slo_ms,
                       uid0=0):
    """The mixed-SLO trace: even uids are short interactive requests
    with a tight first-token SLO; odd uids are long batch requests (no
    SLO) whose prompts all start with the shared ``head`` (the system
    prompt).  Submitted as one burst, so admission order is exactly
    what the scheduler's policy decides."""
    from repro.serve import Request
    reqs = []
    short_hi = max(5, len(head) // 2)
    tail_hi = max(4, max_len - len(head) - max_new - 1)
    for i in range(n):
        if i % 2 == 0:
            prompt = rng.integers(0, vocab, int(rng.integers(
                3, short_hi))).astype(np.int32)
            slo = slo_ms
        else:
            tail = rng.integers(0, vocab, int(rng.integers(
                3, tail_hi))).astype(np.int32)
            prompt = np.concatenate([head, tail])
            slo = None
        reqs.append(Request(uid=uid0 + i, prompt=prompt,
                            max_new_tokens=max_new, slo_ms=slo))
    return reqs


def wave_stats(sched, uids, wall_s):
    """TTFT percentiles (overall + interactive class), SLO violations
    and throughput for one measured wave."""
    from repro.serve.metrics import percentile
    ms = [sched.request_metrics[u] for u in uids]
    ttfts = [m.ttft for m in ms if m.ttft is not None]
    inter = [m for m in ms if m.deadline is not None]
    inter_ttfts = [m.ttft for m in inter if m.ttft is not None]
    new_tokens = sum(m.new_tokens for m in ms)
    return {
        "wall_s": round(wall_s, 3),
        "requests": len(ms),
        "new_tokens": new_tokens,
        "tok_s": round(new_tokens / wall_s, 2) if wall_s > 0 else None,
        "ttft_p50": percentile(ttfts, 50.0),
        "ttft_p99": percentile(ttfts, 99.0),
        "interactive_ttft_p50": percentile(inter_ttfts, 50.0),
        "interactive_ttft_p99": percentile(inter_ttfts, 99.0),
        "slo_violations": sum(1 for m in inter if m.slo_violated),
        "slo_requests": len(inter),
    }


def run_mixed_slo(args) -> dict:
    """One three-scheduler comparison over the same mixed-SLO trace.
    Returns the report dict (no gating here — the caller gates)."""
    import repro
    from repro.configs import get_config

    cfg = get_config(args.arch, smoke=args.smoke)
    exe = repro.compile(cfg, repro.CompileOptions(target="engine"))
    chunk = args.chunk or max(8, args.max_len // 8)
    head_len = 3 * chunk
    policy = repro.BucketPolicy.default(max_batch=args.slots,
                                        max_len=args.max_len)
    head = np.random.default_rng(7).integers(
        0, cfg.vocab, head_len).astype(np.int32)

    def trace(uid0):
        rng = np.random.default_rng(0)
        return mixed_slo_requests(rng, args.requests, cfg.vocab,
                                  args.max_len, args.max_new, head,
                                  args.slo_ms, uid0=uid0)

    common = dict(slots=args.slots, max_len=args.max_len)
    variants = {
        "fixed": repro.SchedulerOptions(**common),
        "base": repro.SchedulerOptions(buckets=policy, **common),
        "opt": repro.SchedulerOptions(buckets=policy,
                                      admission="deadline",
                                      prefill_chunk=chunk,
                                      prefix_cache=8, **common),
    }
    results, tokens = {}, {}
    for name, opts in variants.items():
        sched = repro.serve(exe, opts)
        _, warm_tokens = drain(sched, trace(uid0=100_000))
        sched.wait_warm()
        pre = sched.summary()
        stalls0 = pre.get("runtime", {}).get("compile_stalls", 0)
        steady = trace(uid0=0)
        wall, steady_tokens = drain(sched, steady)
        summ = sched.summary()
        stats = wave_stats(sched, [r.uid for r in steady], wall)
        stats["steady_state_stalls"] = (
            summ.get("runtime", {}).get("compile_stalls", 0) - stalls0)
        results[name] = {"steady": stats, "summary": summ}
        tokens[name] = warm_tokens | steady_tokens
        sched.shutdown()

    mismatched = {
        name: [uid for uid, t in tokens[name].items()
               if tokens["fixed"][uid] != t]
        for name in ("base", "opt")}
    base_s, opt_s = results["base"]["steady"], results["opt"]["steady"]
    ratios = {
        # machine-portable: both sides of each ratio ran on this host
        "interactive_ttft_p99_ratio": round(
            opt_s["interactive_ttft_p99"] / base_s["interactive_ttft_p99"],
            4) if base_s["interactive_ttft_p99"] else None,
        "tok_s_ratio": round(opt_s["tok_s"] / base_s["tok_s"], 4)
        if base_s["tok_s"] else None,
    }
    return {
        "bench": "serve_mixed_slo",
        "arch": args.arch, "smoke": args.smoke, "slots": args.slots,
        "max_len": args.max_len, "requests": args.requests,
        "max_new": args.max_new, "chunk": chunk, "head_len": head_len,
        "slo_ms": args.slo_ms, "policy": policy.to_dict(),
        "results": results,
        "ratios": ratios,
        "tokens_match": not any(mismatched.values()),
        "mismatched_uids": mismatched,
    }


def gate_mixed_slo(report, baseline_path, max_regression) -> list:
    """Failures for the mixed-SLO gate: opt/base ratios must not regress
    more than ``max_regression`` vs the seeded baseline (TTFT ratio up =
    worse; tok/s ratio down = worse)."""
    try:
        with open(baseline_path) as f:
            base = json.load(f)
    except FileNotFoundError:
        return [f"no serve baseline at {baseline_path} — seed one with "
                f"`python -m benchmarks.serve_bench --trace mixed-slo "
                f"--reseed N`"]
    failures = []
    cur, ref = report["ratios"], base["ratios"]
    ttft_cur, ttft_ref = (cur["interactive_ttft_p99_ratio"],
                          ref["interactive_ttft_p99_ratio"])
    ceil = ttft_ref * (1.0 + max_regression)
    print(f"[serve-gate] interactive ttft_p99 opt/base {ttft_cur:.3f} "
          f"(baseline {ttft_ref:.3f}, ceiling {ceil:.3f}) "
          f"{'OK' if ttft_cur <= ceil else 'REGRESSION'}")
    if ttft_cur > ceil:
        failures.append(
            f"interactive ttft_p99 ratio {ttft_cur:.3f} rose more than "
            f"{max_regression:.0%} above baseline {ttft_ref:.3f}")
    tok_cur, tok_ref = cur["tok_s_ratio"], ref["tok_s_ratio"]
    floor = tok_ref * (1.0 - max_regression)
    print(f"[serve-gate] steady tok/s opt/base {tok_cur:.3f} "
          f"(baseline {tok_ref:.3f}, floor {floor:.3f}) "
          f"{'OK' if tok_cur >= floor else 'REGRESSION'}")
    if tok_cur < floor:
        failures.append(
            f"tok/s ratio {tok_cur:.3f} fell more than "
            f"{max_regression:.0%} below baseline {tok_ref:.3f}")
    return failures


def reseed_mixed_slo(args) -> dict:
    """Worst-over-N baseline for the mixed-SLO gate (the documented
    ``perf_gate.py --reseed`` procedure): highest TTFT ratio and lowest
    tok/s ratio across N runs become the new floors."""
    import platform

    import jax

    runs = []
    for i in range(args.reseed):
        rep = run_mixed_slo(args)
        runs.append(rep["ratios"])
        print(f"[serve-reseed] run {i + 1}/{args.reseed}: "
              f"ttft_ratio {rep['ratios']['interactive_ttft_p99_ratio']} "
              f"tok_s_ratio {rep['ratios']['tok_s_ratio']}")
        append_trajectory({"bench": "serve_mixed_slo", "mode": "reseed",
                           "run": i + 1, "of": args.reseed,
                           "ratios": rep["ratios"]})
    doc = {
        "bench": "serve_mixed_slo",
        "ratios": {
            "interactive_ttft_p99_ratio": max(
                r["interactive_ttft_p99_ratio"] for r in runs),
            "tok_s_ratio": min(r["tok_s_ratio"] for r in runs),
        },
        "env": {"jax": jax.__version__,
                "backend": jax.default_backend(),
                "python": platform.python_version(),
                "machine": platform.machine()},
        "note": (f"seeded by `python -m benchmarks.serve_bench --trace "
                 f"mixed-slo --reseed {args.reseed}` as the WORST "
                 f"opt/base ratio over {args.reseed} runs; the gate "
                 "allows a further fractional drop, so only a "
                 "structural regression in the serve hot loop trips it"),
    }
    with open(args.baseline, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"[serve-reseed] wrote {args.baseline}")
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16,
                    help="total requests; half warm-up, half steady-state")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--out", default=None, help="JSON artifact path")
    ap.add_argument("--allow-stalls", action="store_true",
                    help="report steady-state stalls instead of failing")
    ap.add_argument("--trace", choices=("mixed", "mixed-slo"),
                    default="mixed",
                    help="'mixed' = legacy fixed-vs-bucketed bench; "
                         "'mixed-slo' = interactive+batch trace comparing "
                         "the PR-7 scheduler to the serve-hot-loop one")
    ap.add_argument("--chunk", type=int, default=None,
                    help="prefill chunk for mixed-slo (default max_len//8)")
    ap.add_argument("--slo-ms", type=float, default=300.0,
                    help="first-token SLO for interactive requests (ms)")
    ap.add_argument("--gate", action="store_true",
                    help="mixed-slo: fail on ratio regression vs the "
                         "seeded serve baseline")
    ap.add_argument("--reseed", type=int, metavar="N", default=None,
                    help="mixed-slo: rebuild the serve baseline as the "
                         "worst ratio over N runs instead of gating")
    ap.add_argument("--baseline", default=SERVE_BASELINE,
                    help="serve baseline path for --gate/--reseed")
    ap.add_argument("--max-regression", type=float, default=0.25,
                    help="allowed fractional ratio regression for --gate")
    ap.add_argument("--no-trajectory", action="store_true",
                    help="do not append this run to the perf trajectory")
    args = ap.parse_args(argv)

    from repro.api.cache import use_compilation_cache
    use_compilation_cache()

    if args.trace == "mixed-slo":
        return main_mixed_slo(args)

    import repro
    from repro.configs import get_config

    cfg = get_config(args.arch, smoke=args.smoke)
    exe = repro.compile(cfg, repro.CompileOptions(target="engine"))
    n_warm = args.requests // 2
    n_steady = args.requests - n_warm

    def requests(uid0=0):
        rng = np.random.default_rng(0)
        reqs = synth_requests(rng, args.requests, cfg.vocab, args.max_len,
                              args.max_new, uid0=uid0)
        return reqs[:n_warm], reqs[n_warm:]

    # -- fixed-shape reference ----------------------------------------
    sched = repro.serve(exe, repro.SchedulerOptions(
        slots=args.slots, max_len=args.max_len))
    warm, steady = requests()
    t_fixed, fixed_tokens = drain(sched, warm + steady)
    fixed_summary = sched.summary()

    # -- bucketed: warm-up wave, then the steady-state wave -----------
    policy = repro.BucketPolicy.default(max_batch=args.slots,
                                       max_len=args.max_len)
    sched = repro.serve(exe, repro.SchedulerOptions(
        slots=args.slots, max_len=args.max_len, buckets=policy))
    warm, steady = requests()
    t_warm, warm_tokens = drain(sched, warm)
    warmed = sched.wait_warm()
    stalls0 = sched.summary()["runtime"]["compile_stalls"]
    t_steady, steady_tokens = drain(sched, steady)
    bucketed_summary = sched.summary()
    sched.shutdown()
    steady_stalls = bucketed_summary["runtime"]["compile_stalls"] - stalls0

    mismatched = [uid for uid, toks in (warm_tokens | steady_tokens).items()
                  if fixed_tokens[uid] != toks]
    report = {
        "arch": args.arch, "smoke": args.smoke, "slots": args.slots,
        "max_len": args.max_len, "requests": args.requests,
        "policy": policy.to_dict(),
        "fixed": {"wall_s": round(t_fixed, 3), "summary": fixed_summary},
        "bucketed": {"warm_wall_s": round(t_warm, 3),
                     "steady_wall_s": round(t_steady, 3),
                     "warmed": warmed,
                     "summary": bucketed_summary},
        "steady_state_stalls": steady_stalls,
        "tokens_match": not mismatched,
        "mismatched_uids": mismatched,
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    rt = bucketed_summary["runtime"]
    print(f"[serve_bench] fixed {t_fixed:.2f}s | bucketed warm "
          f"{t_warm:.2f}s steady {t_steady:.2f}s | "
          f"{rt['bucket_hits']} hits / {rt['bucket_misses']} misses / "
          f"{rt['background_compiles']} bg compiles | "
          f"pad waste {rt['pad_waste_frac']:.1%} | "
          f"steady-state stalls {steady_stalls}", flush=True)

    ok = True
    if mismatched:
        print(f"[serve_bench] FAIL: bucketed tokens diverge from "
              f"fixed-shape for uids {mismatched}", file=sys.stderr)
        ok = False
    if steady_stalls and not args.allow_stalls:
        print(f"[serve_bench] FAIL: {steady_stalls} compile stall(s) on "
              f"the request path in steady state", file=sys.stderr)
        ok = False
    return 0 if ok else 1


def main_mixed_slo(args) -> int:
    """Drive the mixed-SLO trace: reseed, or run once and (optionally)
    gate.  Token identity and zero steady-state stalls always fail the
    run; ratio regressions only under ``--gate``."""
    if args.reseed is not None:
        if args.reseed < 1:
            raise SystemExit("--reseed must be >= 1")
        reseed_mixed_slo(args)
        return 0

    report = run_mixed_slo(args)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    for name in ("base", "opt"):
        s = report["results"][name]["steady"]
        print(f"[serve_bench] {name:<5} wall {s['wall_s']:.2f}s "
              f"tok/s {s['tok_s']} "
              f"ttft_p99 {s['ttft_p99']:.3f}s "
              f"(interactive {s['interactive_ttft_p99']:.3f}s) "
              f"slo_violations {s['slo_violations']}/{s['slo_requests']} "
              f"stalls {s['steady_state_stalls']}", flush=True)
    opt = report["results"]["opt"]["summary"]
    print(f"[serve_bench] opt prefix_cache {opt.get('prefix_cache')} "
          f"chunks {opt.get('prefill_chunks')} "
          f"ratios {report['ratios']}", flush=True)

    failures = []
    if not report["tokens_match"]:
        failures.append(f"token streams diverge from the fixed-shape "
                        f"oracle: {report['mismatched_uids']}")
    for name in ("base", "opt"):
        n = report["results"][name]["steady"]["steady_state_stalls"]
        if n and not args.allow_stalls:
            failures.append(f"{name}: {n} compile stall(s) on the "
                            f"request path in steady state")
    if args.gate:
        failures += gate_mixed_slo(report, args.baseline,
                                   args.max_regression)
    if not args.no_trajectory:
        append_trajectory({**report,
                           "gate": {"enabled": args.gate,
                                    "baseline": args.baseline,
                                    "verdict": "fail" if failures else "ok",
                                    "failures": failures}})
    for msg in failures:
        print(f"[serve_bench] FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
