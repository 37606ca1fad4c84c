"""Sharded-compilation smoke benchmark (CI: bench-smoke, shard-smoke)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m benchmarks.shard_bench --smoke \\
        --out SHARD.json

Two sections, both hard gates (exit 1 on violation):

* **compile** — a Table-1 config plus a dense MLP block compiled
  unsharded and with ``CompileOptions(mesh=...)``; outputs must be
  **bit-identical** (sharding is placement, never math), and the report
  records the propagated placement + per-axis collective estimates from
  ``cost_summary()["sharding"]``.
* **serve** — the engine smoke config served once on a single device
  and once on a ``data×model`` mesh over the same request trace; greedy
  tokens must match uid for uid, ``summary()["faults"]`` must be empty,
  and the report carries the per-axis collective counts / bytes parsed
  from the decode program's post-optimization HLO.

The mesh shrinks to whatever the visible device set supports (CI forces
8 virtual host devices via ``XLA_FLAGS``), so the bench also runs — as
a pure 1-device identity check — on a bare machine.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from .perf_gate import append_trajectory


def _pick_mesh(want: str):
    """The requested mesh if the device set can fill it, else the
    1×1 fallback (still a valid identity check)."""
    import jax
    import repro

    spec = repro.MeshSpec.parse(want)
    if spec.size <= len(jax.devices()):
        return spec
    return repro.MeshSpec.parse("data=1,model=1")


def _mlp_graph():
    from repro.core import ModelBuilder

    mb = ModelBuilder().seed(11)
    x = mb.input((64,))
    h = mb.dense(x, 128, activation="relu")
    h = mb.dense(h, 64)
    return mb.build([h])


def bench_compile(mesh_spec, batch: int) -> dict:
    """Unsharded vs sharded compile of the MLP block + one Table-1
    config.  A 1-device mesh must be **bit**-identical (the acceptance
    bar); the real mesh must stay allclose — a row-parallel psum may
    legally reassociate the contraction's float reduction across
    devices, but placement never changes the math beyond that."""
    import repro
    from repro.api.capture import seeded_inputs
    from .table1_models import SUITE

    one = repro.MeshSpec.parse("data=1,model=1")
    out = {}
    for name, graph in (("mlp-block", _mlp_graph()),
                        ("C-BH", SUITE["C-BH"]())):
        inputs = seeded_inputs(graph, batch)
        base = repro.compile(graph, repro.CompileOptions())(**inputs)
        single = repro.compile(graph,
                               repro.CompileOptions(mesh=one))(**inputs)
        identical = all(
            np.array_equal(np.asarray(base[k]), np.asarray(single[k]))
            for k in base)
        t0 = time.perf_counter()
        exe = repro.compile(graph, repro.CompileOptions(mesh=mesh_spec))
        sharded = exe(**inputs)
        wall = time.perf_counter() - t0
        close = all(
            np.allclose(np.asarray(base[k]), np.asarray(sharded[k]),
                        rtol=1e-5, atol=1e-6)
            for k in base)
        max_diff = max(
            float(np.max(np.abs(np.asarray(base[k], dtype=np.float64)
                                - np.asarray(sharded[k], dtype=np.float64))))
            for k in base)
        sh = exe.cost_summary()["sharding"]
        out[name] = {
            "bit_identical_1dev": identical,
            "allclose_mesh": close,
            "max_abs_diff_mesh": max_diff,
            "compile_and_run_s": round(wall, 3),
            "tensors": sh["tensors"],
            "collectives": sh["collectives"],
        }
    return out


def bench_serve(mesh_spec, args) -> dict:
    """Single-device vs meshed scheduler over one trace: token identity,
    faults, throughput, and the HLO-derived per-axis collectives."""
    import repro
    from repro.configs import get_config
    from repro.serve import Request

    cfg = get_config(args.arch, smoke=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab,
                            int(rng.integers(3, args.max_len // 4)))
               for _ in range(args.requests)]

    def run(mesh):
        exe = repro.compile(cfg, repro.CompileOptions(target="engine",
                                                      mesh=mesh))
        sched = repro.serve(exe, repro.SchedulerOptions(
            slots=args.slots, max_len=args.max_len))
        t0 = time.perf_counter()
        for i, p in enumerate(prompts):
            sched.submit(Request(uid=i, prompt=p,
                                 max_new_tokens=args.max_new))
        done = sched.run()
        wall = time.perf_counter() - t0
        summary = sched.summary()
        sched.shutdown()
        return {c.uid: list(c.tokens) for c in done}, summary, wall

    ref, _, wall_1dev = run(None)
    got, summary, wall_mesh = run(mesh_spec)

    # Bucketed meshed wave: warm up, then the steady wave must serve
    # with ZERO request-path compile stalls (the engine-cache contract
    # holds under a mesh too) and the oracle token stream.
    exe = repro.compile(cfg, repro.CompileOptions(target="engine",
                                                  mesh=mesh_spec))
    policy = repro.BucketPolicy.default(max_batch=args.slots,
                                        max_len=args.max_len)
    sched = repro.serve(exe, repro.SchedulerOptions(
        slots=args.slots, max_len=args.max_len, buckets=policy))
    for i, p in enumerate(prompts):
        sched.submit(Request(uid=100_000 + i, prompt=p,
                             max_new_tokens=args.max_new))
    sched.run()
    sched.wait_warm()
    stalls0 = sched.summary()["runtime"]["compile_stalls"]
    for i, p in enumerate(prompts):
        sched.submit(Request(uid=i, prompt=p, max_new_tokens=args.max_new))
    # run() may re-report warm-wave completions; keep the steady uids
    steady = {c.uid: list(c.tokens) for c in sched.run()
              if c.uid < 100_000}
    bsummary = sched.summary()
    sched.shutdown()
    steady_stalls = bsummary["runtime"]["compile_stalls"] - stalls0

    return {
        "mesh": mesh_spec.describe(),
        "devices": mesh_spec.size,
        "tokens_identical": got == ref,
        "mismatched_uids": sorted(u for u in ref if got.get(u) != ref[u]),
        "bucketed_tokens_identical": steady == ref,
        "steady_state_stalls": steady_stalls,
        "faults": summary.get("faults", []) + bsummary.get("faults", []),
        "sharding": summary.get("sharding"),
        "wall_s_single": round(wall_1dev, 3),
        "wall_s_mesh": round(wall_mesh, 3),
        "tok_s_mesh": summary.get("tokens_per_s"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (implied by the defaults; kept "
                         "for symmetry with the other benches)")
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--mesh", default="data=2,model=2",
                    help="requested serve mesh; shrinks to 1x1 when the "
                         "device set is too small")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2,
                    help="batch size for the compile-section identity run")
    ap.add_argument("--out", default=None, help="JSON artifact path")
    ap.add_argument("--no-trajectory", action="store_true",
                    help="do not append this run to the perf trajectory")
    args = ap.parse_args(argv)

    from repro.api.cache import use_compilation_cache
    use_compilation_cache()

    import jax

    mesh_spec = _pick_mesh(args.mesh)
    print(f"[shard_bench] {len(jax.devices())} device(s) visible; "
          f"mesh {mesh_spec.describe()}", flush=True)

    report = {
        "bench": "shard_smoke",
        "requested_mesh": args.mesh,
        "mesh": mesh_spec.describe(),
        "devices_visible": len(jax.devices()),
        "compile": bench_compile(mesh_spec, args.batch),
        "serve": bench_serve(mesh_spec, args),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    if not args.no_trajectory:
        append_trajectory({"bench": "shard_smoke",
                           "mesh": report["mesh"],
                           "serve": {k: report["serve"][k]
                                     for k in ("tokens_identical",
                                               "wall_s_mesh",
                                               "tok_s_mesh")}})

    for name, row in report["compile"].items():
        print(f"[shard_bench] compile {name:<9} 1dev-bit-identical="
              f"{row['bit_identical_1dev']} mesh-allclose="
              f"{row['allclose_mesh']} "
              f"(max diff {row['max_abs_diff_mesh']:.2e}) collectives="
              f"{row['collectives']['counts'] or '{}'}", flush=True)
    srv = report["serve"]
    per = (srv["sharding"] or {}).get("collectives", {}).get("per_axis", {})
    per_str = {a: f"{v['count']}x/{v['bytes'] / 1e3:.1f}KB"
               for a, v in per.items()}
    print(f"[shard_bench] serve mesh {srv['mesh']}: tokens_identical="
          f"{srv['tokens_identical']} bucketed="
          f"{srv['bucketed_tokens_identical']} "
          f"steady_stalls={srv['steady_state_stalls']} "
          f"faults={len(srv['faults'])} "
          f"single {srv['wall_s_single']}s vs mesh {srv['wall_s_mesh']}s "
          f"per-axis {per_str or 'none'}", flush=True)

    failures = []
    for name, row in report["compile"].items():
        if not row["bit_identical_1dev"]:
            failures.append(f"compile {name}: 1-device mesh is not "
                            f"bit-identical to unsharded")
        if not row["allclose_mesh"]:
            failures.append(f"compile {name}: meshed output diverges "
                            f"beyond float reassociation "
                            f"(max {row['max_abs_diff_mesh']:.2e})")
    if not srv["tokens_identical"]:
        failures.append(f"serve: meshed tokens diverge for uids "
                        f"{srv['mismatched_uids']}")
    if not srv["bucketed_tokens_identical"]:
        failures.append("serve: bucketed meshed tokens diverge from the "
                        "single-device oracle")
    if srv["steady_state_stalls"]:
        failures.append(f"serve: {srv['steady_state_stalls']} compile "
                        f"stall(s) on the request path in steady state")
    if srv["faults"]:
        failures.append(f"serve: unexpected mesh faults {srv['faults']}")
    for msg in failures:
        print(f"[shard_bench] FAIL: {msg}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
