#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload vgg19.b1 --seed 7 --seconds 10 --trace 0

From the root of a checkout.  The cell (a configuration under a traffic
mix) is looked up by name in ``BENCHMARK.json``; its files are found by
name (``bench/spec.py``).  The run loads, warms up every shape the cell
uses (set-up), measures for ``--seconds``, then checks what the timed
path produced against the configuration's plain reference.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and prints its per-layer
metrics, with ``busy_s``/``window_s`` and a ``breakdown``.  The last
line of standard output is one JSON object; the last lines of standard
error give each compared number beside its limit.  Off a TPU, or with
fewer chips than the cell asks for, the run exits nonzero and prints no
result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def say(*parts) -> None:
    """An earlier line: progress and counts, on standard error."""
    print(*parts, file=sys.stderr, flush=True)


def use_compilation_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``; every program is kept,
    so that only a cell's first run in a checkout compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileWatch:
    """Counts compilations and persistent-cache lookups, so a run can
    show that its window compiled nothing."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses",
              "/jax/compilation_cache/compile_requests_use_cache":
                  "compile_requests"}
    DURATION = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring as m
        self.counts = {v: 0 for v in self.EVENTS.values()}
        self.counts["backend_compiles"] = 0
        m.register_event_listener(self._event)
        m.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def _duration(self, event, duration, **_):
        if event == self.DURATION:
            self.counts["backend_compiles"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}

    def close(self) -> None:
        import jax.monitoring as m
        m.unregister_event_listener(self._event)
        m.unregister_event_duration_listener(self._duration)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [ROOT]
    from bench import device, spec, trace_reduce, traffic
    try:
        cell = spec.resolve(spec.load(ROOT), ROOT, args.workload)
        traffic.check_mix(cell.traffic)
    except (spec.SpecError, ValueError) as e:
        say(f"bench: {e}")
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        say(f"bench: no system under test (no repro package under {src})")
        return 2
    sys.path[:0] = [src]
    # Built from the checkout alone: no executable cache, capture
    # bundle or IR dump that the environment points at.
    for var in ("REPRO_CACHE_DIR", "REPRO_CAPTURE_DIR", "REPRO_DUMP_IR"):
        os.environ.pop(var, None)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    try:
        peaks = device.check(jax.devices(), cell.chips, device.load_peaks())
    except device.DeviceError as e:
        say(f"bench: {e}")
        return 1
    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    cache_dir = use_compilation_cache(ROOT)
    say(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"compilation_cache={cache_dir}")
    tag = f"({dev.platform} {dev.device_kind} x{len(devices)})"

    def say_dev(*parts):
        say(*parts, tag)
    runner = importlib.import_module(
        f"bench.runners.{cell.config['runner']}")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    watch = CompileWatch()
    try:
        run = runner.run(cell, cell.config_module(), seed=args.seed,
                         seconds=args.seconds, trace_dir=trace_dir,
                         t_start=T0, watch=watch, say=say_dev)
        run.peaks = peaks
        run.device = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices)}
        if trace_dir:
            run.trace = trace_reduce.reduce(
                trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
    finally:
        watch.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()

    wanted = spec.metric_names(cell, bool(args.trace))
    metrics = {}
    for name, m in wanted.items():
        value = cell.metric_reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    dev_out = dict(run.device_json)
    if args.trace:
        dev_out["busy_s"] = run.trace.busy_s
        dev_out["window_s"] = run.trace.window_s
    say(f"[result] device={json.dumps(dev_out)} attempted={run.attempted} "
        f"failed={run.failed} metrics={json.dumps(metrics)}")
    for c in run.checks:
        say(f"[check] {c['name']} = {c['value']!r}  limit {c['limit']!r}  "
            f"({'ok' if c['ok'] else 'FAILED'})")
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": dev_out}
    if args.trace:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in run.checks}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
