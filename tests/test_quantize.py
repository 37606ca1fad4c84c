"""Calibration-driven quantization: the quantize pass, the
dtype-specialized kernels, and the low-precision compile surface.

Covers the PR's acceptance contract: per-precision golden identity
across interpret/jit/pallas, ``precision="f32"`` bit-identity with the
exact pipeline, deterministic calibration under the fixed seed,
``quant.*`` attrs surviving the container round trip, and a subprocess
persistent-cache round trip with zero recompiles."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro
from repro.api import CompileOptions
from repro.core import ModelBuilder
from repro.core.passes import run_pipeline
from repro.kernels.tiles import block_vmem_bytes


def _mlp():
    mb = ModelBuilder().seed(7)
    x = mb.input((20,))
    h = mb.dense(x, 64, activation="tanh")
    h = mb.dense(h, 48, activation="relu")
    h = mb.dense(h, 32, activation="tanh")
    out = mb.dense(h, 9)
    return mb.build([out]), out


def _cnn():
    mb = ModelBuilder().seed(8)
    x = mb.input((10, 10, 3))
    h = mb.conv2d(x, 8, (3, 3), activation="relu")
    h = mb.batchnorm(h)
    h = mb.global_avg_pool(h)
    out = mb.dense(h, 5)
    return mb.build([out]), out


# ---------------------------------------------------------------------------
# Satellite: the VMEM model's accumulator itemsize
# ---------------------------------------------------------------------------
def test_block_vmem_bytes_itemsize_geometry():
    """Operand bytes scale with itemsize; acc/out bytes with
    acc_itemsize — f32 (4), bf16 (2), and int8 (1) tiles of the same
    block differ exactly by the operand-byte term."""
    bm, bk, bn = 128, 512, 128
    operands = bm * bk + bk * bn
    acc = 2 * bm * bn
    for itemsize in (1, 2, 4):
        got = block_vmem_bytes(bm, bk, bn, itemsize)
        assert got == itemsize * operands + 4 * acc
    # the int8 kernel budgets an i32 scratch + f32 out: acc_itemsize=4
    # is the default, but the parameter must be honored when it is not
    assert block_vmem_bytes(bm, bk, bn, 1, acc_itemsize=8) == \
        operands + 8 * acc


# ---------------------------------------------------------------------------
# Calibration determinism
# ---------------------------------------------------------------------------
def _quantized_graph(graph, mode="int8", calibrate=4):
    g = graph.copy()
    g.quant = {"mode": mode, "calibrate": calibrate, "measure": False}
    out, _ = run_pipeline(g, ("quantize",))
    return out


def test_calibration_ranges_deterministic():
    g, _ = _mlp()
    a = _quantized_graph(g)
    b = _quantized_graph(g)
    sites = [n for n in a.nodes if "quant.x_scale" in n.attrs]
    assert sites, "int8 mode must annotate dense sites"
    for na, nb in zip(a.nodes, b.nodes):
        assert na.attrs.get("quant.x_scale") == nb.attrs.get("quant.x_scale")
        assert na.attrs.get("quant.w_scale") == nb.attrs.get("quant.w_scale")
    assert a.structure_hash() == b.structure_hash()


def test_quant_attrs_flow_into_structure_hash():
    g, _ = _mlp()
    assert _quantized_graph(g).structure_hash() != \
        _quantized_graph(g, mode="bf16").structure_hash()


# ---------------------------------------------------------------------------
# Golden identity per precision
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("graph_fn", [_mlp, _cnn], ids=["mlp", "cnn"])
@pytest.mark.parametrize("prec", ["f32", "bf16", "int8"])
def test_golden_identity_across_targets(graph_fn, prec, rng):
    g, out = graph_fn()
    x = rng.standard_normal((4,) + next(iter(g.inputs.values())).shape) \
        .astype(np.float32)
    outs = {}
    for tgt in ("interpret", "jit", "pallas"):
        exe = repro.compile(g, CompileOptions(target=tgt, precision=prec))
        outs[tgt] = np.asarray(exe(input=x)[out])
    # jit and pallas trace the same annotated graph through the same
    # shared quant expressions.  int8's i32 accumulation is exact under
    # any blocking, so there the two are bit-identical.  A float dot's
    # f32 sum is not: the Pallas kernel contracts K padded to the
    # 128-lane tile in blocks, the jit program the bare K in one dot,
    # and XLA adds the products in a different order for each shape —
    # one f32 ulp of these activations apart.
    if prec == "int8":
        np.testing.assert_array_equal(outs["jit"], outs["pallas"])
    else:
        np.testing.assert_allclose(outs["jit"], outs["pallas"], atol=1e-6)
    # the eager oracle differs only by XLA's jit-side fma contraction
    # of the dequant/bias chain (~1 ulp of the activations)
    np.testing.assert_allclose(outs["interpret"], outs["jit"], atol=1e-5)


def test_f32_bit_identical_to_exact_pipeline(rng):
    """precision='f32' must be today's pipeline exactly — same graph,
    same kernels, bit-identical outputs on both compiled targets."""
    g, out = _cnn()
    x = rng.standard_normal((2, 10, 10, 3)).astype(np.float32)
    for tgt in ("jit", "pallas"):
        exact = repro.compile(g, CompileOptions(target=tgt))
        f32 = repro.compile(g, CompileOptions(target=tgt, precision="f32"))
        np.testing.assert_array_equal(
            np.asarray(exact(input=x)[out]), np.asarray(f32(input=x)[out]))
        assert f32.cost_summary().get("quant") is None


def test_int8_error_within_default_budget(rng):
    g, out = _mlp()
    x = rng.standard_normal((4, 20)).astype(np.float32)
    want = np.asarray(repro.compile(g, CompileOptions())(input=x)[out])
    got = np.asarray(repro.compile(
        g, CompileOptions(precision="int8"))(input=x)[out])
    assert float(np.abs(want - got).max()) <= 0.05


def test_backend_prior_conv_stays_f32_off_tpu(monkeypatch):
    """Off-TPU, int8 annotates dense sites only (XLA CPU int8 conv is a
    slowdown); bf16 annotates both."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    g, _ = _cnn()
    q8 = _quantized_graph(g, mode="int8")
    modes8 = {n.op: n.attrs.get("quant.mode") for n in q8.nodes
              if n.op in ("dense", "conv2d")}
    assert modes8["dense"] == "int8" and modes8["conv2d"] is None
    qb = _quantized_graph(g, mode="bf16")
    modesb = {n.op: n.attrs.get("quant.mode") for n in qb.nodes
              if n.op in ("dense", "conv2d")}
    assert modesb == {"dense": "bf16", "conv2d": "bf16"}


# ---------------------------------------------------------------------------
# Options surface
# ---------------------------------------------------------------------------
def test_quant_options_validation():
    with pytest.raises(ValueError):
        CompileOptions(calibrate=0)
    with pytest.raises(ValueError):
        CompileOptions(calibrate=-3)
    with pytest.raises(ValueError):
        CompileOptions(precision_budget=0.0)
    CompileOptions(precision="int8", calibrate=2, precision_budget=0.1)


def test_cost_summary_reports_decisions(rng):
    g, _ = _mlp()
    exe = repro.compile(g, CompileOptions(precision="int8"))
    q = exe.cost_summary()["quant"]
    assert q["mode"] == "int8"
    assert q["decisions"]["int8"] == 4


# ---------------------------------------------------------------------------
# Serialization + persistent cache
# ---------------------------------------------------------------------------
def test_scale_attrs_survive_container_roundtrip(tmp_path):
    from repro.frontends.container import load_model, save_model
    g, _ = _mlp()
    q = _quantized_graph(g)
    path = tmp_path / "quantized.npz"
    save_model(q, str(path))
    r = load_model(str(path))
    for a, b in zip(q.nodes, r.nodes):
        for key in ("quant.mode", "quant.x_scale", "quant.w_scale",
                    "quant.zp"):
            assert a.attrs.get(key) == b.attrs.get(key), (a.name, key)
    assert q.structure_hash() == r.structure_hash()


def test_serialized_executable_reproduces_quantized_outputs(rng):
    g, out = _mlp()
    x = rng.standard_normal((2, 20)).astype(np.float32)
    exe = repro.compile(g, CompileOptions(precision="int8"))
    want = np.asarray(exe(input=x)[out])
    clone = repro.deserialize(exe.serialize())
    np.testing.assert_array_equal(want, np.asarray(clone(input=x)[out]))


_SUBPROC = textwrap.dedent("""
    import json, sys
    import numpy as np
    import repro
    from repro.api import CompileOptions
    sys.path.insert(0, {test_dir!r})
    from test_quantize import _mlp
    g, out = _mlp()
    x = np.linspace(-1, 1, 40, dtype=np.float32).reshape(2, 20)
    exe = repro.compile(g, CompileOptions(precision="int8",
                                          calibrate=3,
                                          cache_dir={cache!r}))
    y = exe(input=x)[out]
    print(json.dumps({{"cache": exe.cache_info(),
                       "digest": float(np.asarray(y).sum())}}))
""")


def test_quant_cache_subprocess_zero_recompiles(tmp_path):
    """Two processes, same int8 compile, shared cache dir: the second
    must serve the executable from disk (0 recompiles) and produce the
    same output — deterministic calibration is what keeps the key
    stable across processes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = _SUBPROC.format(test_dir=os.path.dirname(__file__),
                             cache=str(tmp_path))
    reports = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr
        reports.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert reports[0]["cache"]["misses"] == 1
    assert reports[1]["cache"]["misses"] == 0, "second process recompiled"
    assert reports[1]["cache"]["hits"] == 1
    assert reports[0]["digest"] == reports[1]["digest"]


def test_precision_changes_cache_key(tmp_path, rng):
    g, _ = _mlp()
    x = rng.standard_normal((2, 20)).astype(np.float32)
    e1 = repro.compile(g, CompileOptions(cache_dir=str(tmp_path)))
    e1(input=x)
    e2 = repro.compile(g, CompileOptions(cache_dir=str(tmp_path),
                                         precision="int8"))
    e2(input=x)
    assert e2.cache_info()["misses"] == 1 and e2.cache_info()["hits"] == 0
    e3 = repro.compile(g, CompileOptions(cache_dir=str(tmp_path),
                                         precision="int8", calibrate=8))
    e3(input=x)
    assert e3.cache_info()["misses"] == 1, \
        "calibrate must be part of the compile cache key"


# ---------------------------------------------------------------------------
# Serving surface
# ---------------------------------------------------------------------------
def test_serve_summary_reports_precision():
    """The engine target serves weight-only bf16 (rejecting graph-routed
    int8), and the scheduler's summary() carries the precision audit
    record through from the compiled executable."""
    from repro.configs import get_config
    cfg = get_config("qwen2.5-14b", smoke=True)
    with pytest.raises(ValueError, match="engine"):
        repro.compile(cfg, CompileOptions(target="engine",
                                          precision="int8"))
    exe = repro.compile(cfg, CompileOptions(target="engine",
                                            precision="bf16"))
    q = exe.cost_summary()["quant"]
    assert q["mode"] == "bf16" and q["decisions"]["bf16"] > 0
    import repro as _r
    sched = _r.serve(exe, _r.SchedulerOptions(slots=2, max_len=32))
    try:
        prec = sched.summary()["precision"]
        assert prec["precision"] == "bf16"
        assert prec["decisions"] == q["decisions"]
    finally:
        sched.shutdown()


# ---------------------------------------------------------------------------
# Mixed mode
# ---------------------------------------------------------------------------
def test_mixed_mode_measures_and_respects_budget(tmp_path, rng):
    g, out = _mlp()
    x = rng.standard_normal((2, 20)).astype(np.float32)
    exe = repro.compile(g, CompileOptions(
        precision="mixed", precision_budget=1e-9, cache_dir=str(tmp_path)))
    q = exe.cost_summary()["quant"]
    assert q["mode"] == "mixed"
    # a budget this tight rejects every narrow candidate: all sites f32,
    # and the output is exactly the f32 program's
    assert q["decisions"]["f32"] == 4
    want = np.asarray(repro.compile(g, CompileOptions())(input=x)[out])
    np.testing.assert_array_equal(want, np.asarray(exe(input=x)[out]))
