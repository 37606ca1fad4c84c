"""Precision of the fast activations (paper §3.4) and of the whole
compiled pipeline vs the SimpleNN oracle — the paper's "approximating
activation functions … impacts the precision" quantified.

The quantization section extends the same question to the calibrated
low-precision modes: for every Table-1 config, the bf16 and int8
compiled outputs are diffed against the f32 oracle (max_abs and
max_rel), which is the accuracy half of the precision gate's contract
(the speed half lives in ``benchmarks/table1.py --precision``)."""

from __future__ import annotations

import argparse
import json
import platform
from typing import Dict, Optional, Sequence

import numpy as np

import repro
from repro.kernels.fast_act import ref as fa

from .table1_models import SUITE


def activation_errors() -> Dict[str, Dict[str, float]]:
    x = np.linspace(-8, 8, 100_001, dtype=np.float32)
    out = {}
    for fn in ("exp", "tanh", "sigmoid"):
        approx = np.asarray(fa.FAST[fn](x))
        exact = np.asarray(fa.EXACT[fn](x))
        denom = np.maximum(np.abs(exact), 1e-6)
        out[fn] = {
            "max_abs": float(np.max(np.abs(approx - exact))),
            "max_rel": float(np.max(np.abs(approx - exact) / denom)),
        }
    # softmax over a batch of logit-ish rows
    rng = np.random.default_rng(0)
    z = rng.standard_normal((256, 64)).astype(np.float32) * 4
    a = np.asarray(fa.fast_softmax(z))
    e = np.asarray(fa.EXACT["softmax"](z, axis=-1))
    out["softmax"] = {"max_abs": float(np.max(np.abs(a - e))),
                      "max_rel": float("nan")}
    return out


def end_to_end_errors() -> Dict[str, Dict[str, float]]:
    rng = np.random.default_rng(1)
    out = {}
    for name in ("C-HTWK", "C-BH", "Segmenter"):
        g = SUITE[name]()
        in_name = next(iter(g.inputs))
        x = rng.standard_normal((2,) + g.inputs[in_name].shape) \
            .astype(np.float32)
        out_name = g.outputs[0]
        oracle = repro.compile(g, repro.CompileOptions(target="interpret"))
        want = np.asarray(oracle(**{in_name: x})[out_name])
        exact = np.asarray(
            repro.compile(g, repro.CompileOptions())(**{in_name: x})[out_name])
        fast = np.asarray(
            repro.compile(g, repro.CompileOptions(precision="fast"))(
                **{in_name: x})[out_name])
        out[name] = {
            "exact_vs_oracle": float(np.max(np.abs(want - exact))),
            "fast_vs_oracle": float(np.max(np.abs(want - fast))),
        }
    return out


def quantization_errors(calibrate: Optional[int] = None
                        ) -> Dict[str, Dict[str, float]]:
    """bf16/int8 compiled outputs vs the f32 oracle, per Table-1
    config: max_abs and max_rel (relative to the oracle's magnitude,
    floored at 1e-6 so near-zero outputs don't blow the ratio up)."""
    rng = np.random.default_rng(2)
    out = {}
    for name in SUITE:
        g = SUITE[name]()
        in_name = next(iter(g.inputs))
        out_name = g.outputs[0]
        x = rng.standard_normal((2,) + g.inputs[in_name].shape) \
            .astype(np.float32)
        oracle = repro.compile(g, repro.CompileOptions(target="interpret"))
        want = np.asarray(oracle(**{in_name: x})[out_name])
        denom = np.maximum(np.abs(want), 1e-6)
        row: Dict[str, float] = {}
        for prec in ("bf16", "int8"):
            got = np.asarray(repro.compile(g, repro.CompileOptions(
                precision=prec, calibrate=calibrate))(
                    **{in_name: x})[out_name])
            row[f"{prec}_max_abs"] = float(np.max(np.abs(want - got)))
            row[f"{prec}_max_rel"] = float(np.max(np.abs(want - got) / denom))
        out[name] = row
    return out


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--calibrate", type=int, default=None, metavar="N",
                    help="calibration sample batches for the "
                         "quantization section (default: pass default, 4)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write every section as a PRECISION_*.json "
                         "artifact (what the CI precision gate consumes)")
    args = ap.parse_args(argv)

    from repro.api.cache import use_compilation_cache
    use_compilation_cache()

    act = activation_errors()
    print("fast-activation errors (paper §3.4):")
    for fn, e in act.items():
        print(f"  {fn:<8} max_abs={e['max_abs']:.3e} "
              f"max_rel={e['max_rel']:.3e}")
    e2e = end_to_end_errors()
    print("end-to-end compiled vs SimpleNN oracle:")
    for name, e in e2e.items():
        print(f"  {name:<10} exact={e['exact_vs_oracle']:.2e} "
              f"fast={e['fast_vs_oracle']:.2e}")
    quant = quantization_errors(calibrate=args.calibrate)
    print("quantized compiled vs f32 oracle (calibration-driven):")
    for name, e in quant.items():
        print(f"  {name:<12} bf16={e['bf16_max_abs']:.2e} "
              f"(rel {e['bf16_max_rel']:.2e})  "
              f"int8={e['int8_max_abs']:.2e} "
              f"(rel {e['int8_max_rel']:.2e})")
    if args.json:
        import jax
        doc = {
            "bench": "precision",
            "activations": act,
            "end_to_end": e2e,
            "quantization": quant,
            "env": {
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "python": platform.python_version(),
                "machine": platform.machine(),
            },
        }
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        print(f"[precision] wrote {args.json}")


if __name__ == "__main__":
    main()
