#!/usr/bin/env python3
"""Run both halves of the system once on a TPU, through the entry
points a user calls, and check what comes out.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips: the multi-chip phase only

One chip:

* the compiler path: every Table-1 graph through ``repro.compile`` on
  the ``jit`` and ``pallas`` targets at batch 1 and 8, against the
  ``interpret`` oracle; the int8 classifiers on ``pallas`` against the
  float32 oracle;
* the decode-attention kernel at qwen2.5-14b's head layout against its
  jnp reference (the served path does not run it);
* the serving path: qwen2.5-14b at its published widths, cut to
  ``LAYERS`` layers, compiled for the ``engine`` target and served 8
  seeded requests by ``repro.serve``.

Four chips: the same served model on a ``data=2,model=2`` mesh against
the same requests on one device, and one Table-1 graph compiled for
``data=4`` against its unsharded program.

Weights are random from fixed seeds.  The script exits nonzero, and
prints no result line, when JAX finds no TPU or any check fails; on
success its last line is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: qwen2.5-14b layers kept.  Each layer holds 275 M float32 parameters
#: (1.10 GB), the embedding and the untied head 1.56 B (6.23 GB).
#: Serving folds the norms into a copy of the QKV, gate/up and head
#: matrices (0.71 GB a layer plus 3.11 GB) while the executable keeps
#: the originals, so L layers hold 9.34 + 1.81·L GB, and the step
#: programs need up to 1.66 GB more by their compiled memory analysis.
#: Two layers peaked at 13.2 GB on a v5e (16.9 GB usable); a third would
#: bring that to about 15.0 GB, too little room to risk on one run.
LAYERS = 2
SLOTS = 4
MAX_LEN = 256
REQUESTS = 8
MAX_NEW = 16
SEED = 0

#: Table-1 outputs against the ``interpret`` oracle, which runs at
#: ``highest`` precision, as a fraction of the output's scale (its
#: largest magnitude, at least 1).  Every program is compiled twice:
#:
#: * under ``jax.default_matmul_precision("highest")`` it is held to the
#:   CPU smoke's 1e-4.  Matmuls, convolutions and the Pallas kernel's
#:   dot then run in float32; on a v5e the jit programs came within
#:   4.3e-6 of the oracle.  This is the check of the compiler itself: a
#:   wrong kernel, layout, fusion or padding is off by the order of the
#:   output.
#: * at the default precision, the one users run, a float32 matmul or
#:   convolution takes one bfloat16 pass (8 significant bits per
#:   operand), after the pipeline has folded batch norms into the
#:   weights.  The drift from the oracle measured on a v5e peaked at
#:   2.0% (MobileNetV2's 25 convolutions, VGG19's softmax), so the bound
#:   there is 5%.
TABLE1_RTOL = {"highest": 1e-4, "default": 5e-2}
#: A data-sharded program against the unsharded one: the same program
#: at the same precision, each device holding a quarter of the rows, so
#: only the order of float32 sums may differ.
SHARD_RTOL = 1e-3
#: int8 against the float32 oracle: the quantize pass's own error
#: budget (``DEFAULT_PRECISION_BUDGET``).
INT8_BUDGET = 0.05
INT8_GRAPHS = ("C-HTWK", "C-BH")
BATCHES = (1, 8)


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _say(*parts) -> None:
    print(*parts, flush=True)


# ---------------------------------------------------------------------------
# Compiler path
# ---------------------------------------------------------------------------
def _max_err(want: dict, got: dict):
    import numpy as np
    _check(sorted(want) == sorted(got),
           f"outputs differ: {sorted(want)} vs {sorted(got)}")
    err, scale = 0.0, 1.0
    for k in want:
        w, g = np.asarray(want[k], np.float64), np.asarray(got[k], np.float64)
        _check(w.shape == g.shape, f"{k}: shape {g.shape} != {w.shape}")
        _check(bool(np.isfinite(g).all()), f"{k}: non-finite output")
        err = max(err, float(np.max(np.abs(w - g))))
        scale = max(scale, float(np.max(np.abs(w))))
    return err, scale


def _matmul_precision(name: str):
    import contextlib
    import jax
    return (contextlib.nullcontext() if name == "default"
            else jax.default_matmul_precision(name))


def compiler_phase() -> None:
    """Table-1 suite on jit and pallas at batch 1 and 8, plus int8."""
    import jax
    import repro
    from repro.api.capture import seeded_inputs
    from benchmarks.table1_models import SUITE

    for name, build in SUITE.items():
        graph = build()
        oracle = repro.compile(graph, repro.CompileOptions(target="interpret"))
        n_dense = sum(1 for n in graph.nodes if n.op == "dense")
        inputs = {b: seeded_inputs(graph, b) for b in BATCHES}
        with jax.default_matmul_precision("highest"):
            wants = {b: oracle(**inputs[b]) for b in BATCHES}
        for target, precision in (
                (t, p) for t in ("jit", "pallas") for p in TABLE1_RTOL):
            with _matmul_precision(precision):
                exe = repro.compile(graph, repro.CompileOptions(
                    target=target, autotune="off"))
                outs = {b: exe(**inputs[b]) for b in BATCHES}
            for batch in BATCHES:
                err, scale = _max_err(wants[batch], outs[batch])
                tol = TABLE1_RTOL[precision] * scale
                line = (f"[table1] {name:<11} {target:<6} {precision:<7} "
                        f"batch={batch} max_abs_err={err!r} tol={tol!r}")
                if target == "pallas":
                    sel = exe.cost_summary().get("kernel_selection", {})
                    dense = [c for c in sel.get(batch, [])
                             if c["op"] == "dense"]
                    _check(len(dense) == n_dense and all(
                        c["kernel"] == "pallas.fused_matmul" for c in dense),
                        f"{name} batch {batch}: dense kernels {dense}")
                    custom = ("tpu_custom_call"
                              in exe.ensure_compiled(batch).as_text())
                    # Only dense nodes lower to a Pallas kernel (exact
                    # activations stay in XLA); an all-conv graph has none.
                    _check(custom == (n_dense > 0),
                           f"{name} batch {batch}: tpu_custom_call "
                           f"{custom} with {n_dense} dense nodes")
                    line += (f" dense={n_dense}x pallas.fused_matmul"
                             f" tpu_custom_call={custom}")
                _say(line)
                _check(err <= tol, f"{name} {target} {precision} batch "
                       f"{batch}: error {err} over {tol}")
        if name in INT8_GRAPHS:
            exe = repro.compile(graph, repro.CompileOptions(
                target="pallas", precision="int8", autotune="off"))
            err, _ = _max_err(wants[BATCHES[-1]], exe(**inputs[BATCHES[-1]]))
            kernels = sorted({c["kernel"] for c in exe.cost_summary()
                              ["kernel_selection"][BATCHES[-1]]
                              if c["op"] == "dense"})
            _say(f"[int8]   {name:<11} pallas batch={BATCHES[-1]} "
                 f"max_abs_err={err!r} budget={INT8_BUDGET} "
                 f"dense kernels={kernels}")
            _check(kernels == ["pallas.fused_matmul_q8"],
                   f"{name} int8: dense kernels {kernels}")
            _check(err <= INT8_BUDGET,
                   f"{name} int8: error {err} over {INT8_BUDGET}")


def decode_attention_phase() -> None:
    """``decode_attention_p`` compiled for the chip at qwen2.5-14b's
    head layout, over a cache that pads to two tiles, with ragged
    lengths (one row, mid-tile, a tile boundary, the full cache),
    against the jnp reference at ``highest`` precision.  The exact exp
    only: the Schraudolph body is not multiplicative, so its tiled
    online softmax departs from a one-pass reference by design."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention.kernel import (DEFAULT_BS,
                                                       decode_attention_p)
    from repro.kernels.decode_attention.ref import decode_attention_ref

    b, h, hkv, d, s = 4, 40, 8, 128, DEFAULT_BS + 300
    lengths = jnp.asarray([1, 97, DEFAULT_BS, s], jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(SEED), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.float32)
    k = jax.random.normal(kk, (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, s, hkv, d), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = decode_attention_ref(q, k, v, lengths)
        got = jax.jit(functools.partial(decode_attention_p, interpret=False))(
            q, k, v, lengths)
    err, scale = _max_err({"out": want}, {"out": got})
    tol = TABLE1_RTOL["highest"] * scale
    _say(f"[decode_attention] B={b} H={h} Hkv={hkv} D={d} S={s} "
         f"lengths={np.asarray(lengths).tolist()} "
         f"max_abs_err={err!r} tol={tol!r}")
    _check(err <= tol, f"decode_attention: error {err} over {tol}")


# ---------------------------------------------------------------------------
# Serving path
# ---------------------------------------------------------------------------
def _served_config():
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("qwen2.5-14b"), num_layers=LAYERS)
    published = dict(d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
                     d_ff=13824, vocab=152064, qkv_bias=True)
    got = {k: getattr(cfg, k) for k in published}
    _check(got == published, f"qwen2.5-14b widths {got} != {published}")
    return cfg


def _requests(cfg):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(16, 129)))
               .astype(np.int32) for _ in range(REQUESTS)]
    return prompts, [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW)
                     for i, p in enumerate(prompts)]


def _serve(cfg, requests, mesh=None):
    """Compile and serve as ``repro.launch.serve`` does; returns the
    executable, the completions by uid and the summary."""
    import repro
    exe = repro.compile(cfg, repro.CompileOptions(target="engine",
                                                  mesh=mesh))
    sched = repro.serve(exe, repro.SchedulerOptions(slots=SLOTS,
                                                    max_len=MAX_LEN))
    for r in requests:
        sched.submit(r)
    done = sched.run()
    summary = sched.summary()
    sched.shutdown()
    by_uid = {c.uid: c for c in done}
    _check(sorted(by_uid) == list(range(len(requests))),
           f"completed {sorted(by_uid)} of {len(requests)} requests")
    for c in done:
        _check(len(c.tokens) == MAX_NEW and c.finish_reason == "length",
               f"uid {c.uid}: {len(c.tokens)} tokens, {c.finish_reason}")
        _check(all(0 <= t < cfg.vocab for t in c.tokens),
               f"uid {c.uid}: token out of range {c.tokens}")
    _check(not summary.get("faults"), f"faults: {summary.get('faults')}")
    return exe, by_uid, summary


def _param_bytes(cfg) -> int:
    import jax
    from repro.models import get_model
    shapes = jax.eval_shape(
        lambda: get_model(cfg).init(jax.random.PRNGKey(0)))
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(shapes))


def serving_phase() -> None:
    """qwen2.5-14b at full width, LAYERS deep: 8 requests to the end,
    each first token the argmax of the executable's own forward."""
    import jax
    import numpy as np

    cfg = _served_config()
    _say(f"[serve] {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
         f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
         f"d_ff={cfg.d_ff} vocab={cfg.vocab} "
         f"param_bytes={_param_bytes(cfg)} ({cfg.param_dtype})")
    prompts, requests = _requests(cfg)
    t0 = time.perf_counter()
    exe, done, summary = _serve(cfg, requests)
    _say(f"[serve] {summary['completed']} completions, "
         f"{summary['total_new_tokens']} tokens, prompt lengths "
         f"{[len(p) for p in prompts]}, "
         f"set-up and run {time.perf_counter() - t0:.1f}s (not a speed result)")
    for uid, prompt in enumerate(prompts):
        logits = np.asarray(exe(tokens=prompt[None])["logits"][0, -1],
                            np.float32)
        _check(bool(np.isfinite(logits).all()), f"uid {uid}: NaN logits")
        want = int(np.argmax(logits))
        got = done[uid].tokens[0]
        top2 = np.sort(logits)[-2:]
        _say(f"[serve] uid={uid} first_token={got} forward_argmax={want} "
             f"top2_gap={float(top2[1] - top2[0])!r} "
             f"tokens={done[uid].tokens}")
        _check(got == want, f"uid {uid}: first token {got} != argmax {want}")
    stats = jax.devices()[0].memory_stats() or {}
    _say(f"[serve] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
         f"bytes_limit={stats.get('bytes_limit')}")


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------
def multichip_phase(n: int) -> None:
    """data×model serving against one device, and a data-sharded
    Table-1 program against the unsharded one."""
    import gc
    import jax
    import repro
    from repro.api.capture import seeded_inputs
    from benchmarks.table1_models import SUITE

    cfg = _served_config()
    _say(f"[mesh] {cfg.name} layers={cfg.num_layers} "
         f"param_bytes={_param_bytes(cfg)} ({cfg.param_dtype})")
    prompts, requests = _requests(cfg)
    exe, ref, _ = _serve(cfg, requests)
    del exe
    gc.collect()
    _say(f"[mesh] one-device run freed: device 0 bytes_in_use "
         f"{(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}")
    mesh = "data=2,model=2"
    exe, got, summary = _serve(cfg, _requests(cfg)[1], mesh=mesh)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()[:n]]
    sh = summary["sharding"]
    _say(f"[mesh] {mesh} bytes_in_use per device {in_use}")
    _say(f"[mesh] collectives {json.dumps(sh['collectives'])}")
    for uid in range(len(prompts)):
        a, b = ref[uid].tokens, got[uid].tokens
        if a != b:
            at = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
            raise SmokeFailure(f"uid {uid}: tokens diverge at {at}: "
                               f"one device {a} vs mesh {b}")
    _say(f"[mesh] tokens identical to one device for "
         f"{len(prompts)} requests x {MAX_NEW} tokens")
    _check(sh["devices"] == n and all(b > 0 for b in in_use),
           f"mesh devices {sh['devices']}, bytes_in_use {in_use}")

    name = "VGG19"
    graph = SUITE[name]()
    inputs = seeded_inputs(graph, 8)
    want = repro.compile(graph, repro.CompileOptions(autotune="off"))(**inputs)
    sharded = repro.compile(graph, repro.CompileOptions(
        mesh=f"data={n}", autotune="off"))
    out = sharded(**inputs)
    err, scale = _max_err(want, out)
    placed = sorted({d.id for v in out.values() for d in v.sharding.device_set})
    info = sharded.cost_summary()["sharding"]
    _say(f"[mesh] {name} data={n} batch=8 max_abs_err={err!r} "
         f"tol={SHARD_RTOL * scale!r} devices={placed} "
         f"collectives={json.dumps(info['collectives'])}")
    _check(err <= SHARD_RTOL * scale, f"{name} data={n}: error {err}")
    _check(len(placed) == n, f"{name} data={n}: output on {placed}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    """Entry point; returns the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip phase")
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path[:0] = [src, ROOT]
    # Built only from committed files: no executable cache, capture
    # bundle or IR dump that the environment points at.
    for var in ("REPRO_CACHE_DIR", "REPRO_CAPTURE_DIR", "REPRO_DUMP_IR"):
        os.environ.pop(var, None)
    # The TPU library reads this when it loads; unset, it writes its
    # logs to a fixed directory outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    from importlib.metadata import version
    from repro.api.cache import compilation_cache_events, use_compilation_cache
    cache_dir = use_compilation_cache()
    _say(f"[device] platform={dev.platform} kind={dev.device_kind} "
         f"count={len(jax.devices())} jax={jax.__version__} "
         f"libtpu={version('libtpu')} compilation_cache={cache_dir}")

    _check(len(jax.devices()) == args.chips,
           f"--chips {args.chips}: JAX sees {len(jax.devices())} devices")
    t0 = time.perf_counter()
    with compilation_cache_events() as hits:
        if args.chips == 1:
            compiler_phase()
            decode_attention_phase()
            serving_phase()
        else:
            multichip_phase(args.chips)
    _say(f"[cache] persistent compilation cache hits={hits['hits']} "
         f"misses={hits['misses']}; "
         f"wall {time.perf_counter() - t0:.1f}s (not a speed result)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
