"""The compiler path: a network through ``repro.compile``, called by one
closed-loop caller.

Set-up makes the weights from the seed, builds the graph, compiles it
for the cell's batch, and calls it until every program is loaded.  The
window then times each call from ``Executable.__call__`` on host inputs
to ``block_until_ready`` of its outputs.  The outputs of a seeded
sample of the window's calls are kept and, once the program is freed,
compared with the configuration's plain reference.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from .. import record, trace_reduce, traffic

WARM_CALLS = 3
#: The precision below float32 at ``highest``: three bf16 passes.
CONTROL = "high"


def run(cell, cfgmod, *, seed, seconds, trace_dir, t_start, watch, say,
        broken=None):
    """One run of a compile-path cell.  ``broken``, for tests only,
    wraps the executable to plant a fault under the timed path."""
    import jax
    import repro
    from .. import device

    cfg, mix = cell.config, cell.traffic
    rec = record.Run(config=cfg, traffic=mix, batch=mix["batch"])
    if cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    wkey = traffic.jax_key(seed, 0)
    t = time.perf_counter()
    say(f"[setup] process and device up {t - t_start:.3f}s")
    host_w = {k: np.asarray(v)
              for k, v in cfgmod.make_weights(cfg, wkey).items()}
    graph = cfgmod.build_graph(cfg, host_w)
    del host_w
    say(f"[setup] weights made and graph built {time.perf_counter() - t:.3f}s")
    t = time.perf_counter()
    exe = repro.compile(graph, repro.CompileOptions(**cfg["compile_options"]))
    if broken is not None:
        exe = broken(exe)
    pool = traffic.closed_pool(mix, seed, cfgmod.input_shape(cfg))
    for i in range(WARM_CALLS):
        jax.block_until_ready(exe(input=pool[i % len(pool)]))
    summary = exe.cost_summary()
    rec.counters["passes_ms"] = sum(p.get("time_ms", 0.0)
                                    for p in summary.get("passes", []))
    sel = summary.get("kernel_selection", {}).get(mix["batch"], [])
    rec.counters["dense_kernels"] = sorted(
        {c["kernel"] for c in sel if c["op"] == "dense"})
    rec.counters["dense_kernels_per_call"] = sum(
        c["op"] == "dense" and c["kernel"] == "pallas.fused_matmul"
        for c in sel)
    slow = sorted(((p.get("pass", "?"), round(p.get("time_ms", 0.0), 1))
                   for p in summary.get("passes", [])), key=lambda x: -x[1])
    say(f"[setup] compile+warm {time.perf_counter() - t:.3f}s "
        f"passes_ms={rec.counters['passes_ms']!r} slowest={slow[:4]} "
        f"dense_kernels={rec.counters['dense_kernels']} "
        f"compiles={watch.snapshot()}")

    g = traffic.rng(seed, traffic.STREAM_WINDOW)
    keep = cfg["check"]["samples"]
    kept = []                               # (call index, pool index, out)
    before = watch.snapshot()
    if trace_dir:
        trace_reduce.start(trace_dir)
    rec.setup_s = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation("bench.window"):
        rec.window_start = start = time.perf_counter()
        end = start + seconds
        i = 0
        while True:
            p = i % len(pool)
            with jax.profiler.TraceAnnotation("bench.call"):
                a = time.perf_counter()
                out = jax.block_until_ready(exe(input=pool[p]))
                b = time.perf_counter()
            rec.calls.append((a, b))
            # reservoir sample of the window's calls, drawn from the seed
            if len(kept) < keep:
                kept.append((i, p, out))
            else:
                j = int(g.integers(0, i + 1))
                if j < keep:
                    kept[j] = (i, p, out)
            i += 1
            if b >= end:
                break
        rec.window_end = b
    if trace_dir:
        jax.profiler.stop_trace()
    rec.counters["traced_calls"] = len(rec.calls) if trace_dir else 0
    in_window = watch.since(before, watch.snapshot())
    rec.counters["window_compiles"] = in_window
    rec.attempted = len(rec.calls)
    durs = [b - a for a, b in rec.calls]
    say(f"[window] calls={len(rec.calls)} batch={mix['batch']} "
        f"window_s={rec.window_s!r} mean_ms={1e3 * float(np.mean(durs))!r} "
        f"p50_ms={1e3 * float(np.percentile(durs, 50))!r} "
        f"p95_ms={1e3 * float(np.percentile(durs, 95))!r} compiles={in_window}")
    rec.device_json = device.describe(jax.devices(), cell.chips)
    say(f"[memory] peak_bytes_in_use={rec.device_json['memory_peak_bytes']}")

    # The program's state goes before the reference runs.
    outs = {i: {k: np.asarray(v) for k, v in o.items()} for i, _, o in kept}
    pairs = [(i, p) for i, p, _ in sorted(kept, key=lambda k: k[0])]
    del exe, out
    kept.clear()
    gc.collect()
    rec.correct = check(cfg, cfgmod, wkey, pool, outs, pairs, rec, say)
    return rec


def error(cfg, cfgmod, weights, pool, outs, pairs, precision="highest"):
    """The compared number: the largest |program - reference| over the
    kept calls' outputs, over the largest reference output."""
    import jax
    ref_fn = jax.jit(lambda w, x: cfgmod.reference(cfg, w, x, precision))
    err, bad = 0.0, []
    for i, p in pairs:
        (got,) = outs[i].values()
        want = np.asarray(ref_fn(weights, pool[p]), np.float64)
        got = np.asarray(got, np.float64)
        if got.shape != want.shape or not np.isfinite(got).all():
            bad.append(i)
            continue
        scale = float(np.max(np.abs(want)))
        err = max(err, float(np.max(np.abs(got - want))) / scale)
    return err, bad


def check(cfg, cfgmod, wkey, pool, outs, pairs, rec, say) -> bool:
    """Compare the kept outputs with the reference, the weights made
    anew from the seed."""
    weights = cfgmod.make_weights(cfg, wkey)
    t = time.perf_counter()
    err, bad = error(cfg, cfgmod, weights, pool, outs, pairs)
    rec.failed = len(bad)
    say(f"[check] compared {len(pairs)} calls against the reference in "
        f"{time.perf_counter() - t:.3f}s; malformed outputs {bad}")
    ok = rec.check(cfg["check"]["number"], err, cfg["check"]["limit"])
    # The same number for the reference at the precision below the
    # configuration's in the program's place (bench/control.py).
    rec.control_reading = lambda: error(cfg, cfgmod, weights, pool, outs,
                                        pairs, precision=CONTROL)[0]
    return ok and not bad
