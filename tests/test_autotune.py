"""Autotuner: tactic cache round-trips, off-mode bit-identity with the
heuristic selector, and corruption/staleness falling back instead of
crashing."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro
from repro.api.options import CompileOptions
from repro.autotune import (TacticCache, Tactic, candidates_for_node,
                            environment_fingerprint, open_tactic_cache,
                            tactic_key, tune_selection)
from repro.core import ModelBuilder, select_kernels
from repro.core import selection as selection_mod
from repro.core.graph import Graph
from repro.kernels.tiles import (LANE, STREAM_MAX_ROWS, block_vmem_bytes,
                                 ceil_to, enumerate_blocks, grid_steps,
                                 pick_block, sublane_for,
                                 VMEM_BUDGET_BYTES)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp():
    mb = ModelBuilder().seed(0)
    x = mb.input((32,))
    h = mb.dense(x, 64, activation="relu")
    out = mb.dense(h, 8)
    return mb.build([out])


def _compile_tuned(graph, cache_dir, mode="full", budget_ms=10_000):
    return repro.compile(graph, CompileOptions(
        target="pallas", autotune=mode, autotune_budget_ms=budget_ms,
        cache_dir=str(cache_dir)))


# ---------------------------------------------------------------------------
# tiles: dtype-parametrized geometry (satellite)
# ---------------------------------------------------------------------------
def test_pick_block_f32_unchanged():
    """Above STREAM_MAX_ROWS rows the tile geometry is the
    pre-autotuner one, bit for bit, for every dtype; few-row shapes
    stream their weights instead, on purpose (next test), and a weight
    that fits one lane tile is one block either way."""
    assert pick_block(1, 32, 2) == (8, 128, 128)
    assert pick_block(1000, 1000, 1000) == (256, 512, 256)
    assert pick_block(1000, 1000, 1000, itemsize=4) == (256, 512, 256)
    assert pick_block(1000, 1000, 1000, itemsize=2) == (256, 1024, 256)
    assert pick_block(1000, 1000, 1000, itemsize=1) == (256, 1024, 256)
    assert pick_block(1000, 4096, 1000, itemsize=1) == (256, 2048, 256)
    assert pick_block(256, 25088, 4096) == (256, 512, 256)
    rows = STREAM_MAX_ROWS + 1
    assert pick_block(rows, 25088, 4096) == (ceil_to(rows, 8), 512, 256)


# VGG19's dense layers as (K, N): fc1, fc2, and fc3 with its 1000
# outputs (the layout pass pads the weight to 1024 rows).
_VGG19_DENSE = {"fc1": (25088, 4096), "fc2": (4096, 4096),
                "fc3": (4096, 1000)}
#: Most grid steps each layer may take on the streaming geometry (the
#: tile geometry takes fc1 in 784 f32 steps of 512 KiB).
_MAX_STEPS = {"fc1": 128, "fc2": 16, "fc3": 4}


def _dense_graph(k: int, n: int, quant_mode=None) -> Graph:
    """One dense node of a (k, n) weight.  The weight is a broadcast
    zero, so the graph holds its shape and not its bytes."""
    g = Graph()
    g.add_input("input", (k,))
    kernel = g.add_param("fc/kernel", np.broadcast_to(np.float32(0), (k, n)))
    bias = g.add_param("fc/bias", np.zeros(n, np.float32))
    g.add_node("dense", "fc", ["input"],
               params={"kernel": kernel, "bias": bias})
    if quant_mode:
        g.nodes[0].attrs["quant.mode"] = quant_mode
    g.outputs = ["fc:out"]
    return g


@pytest.mark.parametrize("itemsize", [4, 2, 1], ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("m", [1, 32])
@pytest.mark.parametrize("layer", sorted(_VGG19_DENSE))
def test_pick_block_streams_few_row_weights(layer, m, itemsize, monkeypatch):
    """At VGG19's batch 1 and 32 each dense layer streams its weight in
    few large tiles that divide the lane-padded K and N (so no weight
    is padded per call), double-buffered within VMEM, and the selector
    records the streaming geometry and its grid steps."""
    k, n = _VGG19_DENSE[layer]
    bm, bk, bn = pick_block(m, k, n, itemsize)
    assert ceil_to(k, LANE) % bk == 0 and ceil_to(n, LANE) % bn == 0
    assert bm == ceil_to(m, sublane_for(itemsize))
    # both operand tiles and the output double-buffered, plus the
    # accumulator: at most twice the single-buffered working set
    assert 2 * block_vmem_bytes(bm, bk, bn, itemsize) <= VMEM_BUDGET_BYTES
    steps = grid_steps(bm, ceil_to(k, LANE), ceil_to(n, LANE), (bm, bk, bn))
    assert steps <= _MAX_STEPS[layer]

    # int8 only takes the Pallas kernel on a TPU; steer the selector's
    # backend probe so its record is the chip's.
    monkeypatch.setattr(selection_mod, "on_tpu", lambda: True)
    mode = {4: None, 2: "bf16", 1: "int8"}[itemsize]
    choice = select_kernels(_dense_graph(k, n, mode), batch_size=m,
                            target="pallas")["fc"]
    assert choice.kernel.startswith("pallas.fused_matmul")
    assert choice.geometry == "stream"
    assert choice.block == (bm, bk, bn)
    assert choice.grid_steps == steps
    assert choice.to_dict()["geometry"] == "stream"


def test_selection_records_tile_geometry_at_many_rows():
    choice = select_kernels(_dense_graph(4096, 4096), batch_size=256,
                            target="pallas")["fc"]
    assert choice.geometry == "tile"
    assert choice.block == (256, 512, 256)
    assert choice.grid_steps == 16 * 8


def test_pick_block_bf16_uses_freed_budget():
    # Half the itemsize: sublane granule doubles, K cap doubles — the
    # working set stays inside the same VMEM budget instead of idling.
    bm, bk, bn = pick_block(1000, 4096, 1000, itemsize=2)
    assert bk == 1024
    assert bm % sublane_for(2) == 0
    assert block_vmem_bytes(bm, bk, bn, 2) <= VMEM_BUDGET_BYTES
    f32 = pick_block(1000, 4096, 1000, itemsize=4)
    assert block_vmem_bytes(*f32, itemsize=4) <= VMEM_BUDGET_BYTES
    assert bk > f32[1]


def test_sublane_for_matches_tpu_granules():
    assert sublane_for(4) == 8     # f32
    assert sublane_for(2) == 16    # bf16
    assert sublane_for(1) == 32    # int8


def test_enumerate_blocks_prior_first_and_vmem_legal():
    blocks = enumerate_blocks(512, 1024, 512)
    assert blocks[0] == pick_block(512, 1024, 512)
    assert len(blocks) == len(set(blocks)) > 1
    assert all(block_vmem_bytes(*b) <= VMEM_BUDGET_BYTES for b in blocks)
    # clipped to the padded problem dims on tiny shapes
    for bm, bk, bn in enumerate_blocks(1, 32, 2):
        assert bm <= 8 and bk <= 128 and bn <= 128


# ---------------------------------------------------------------------------
# autotune="off": bit-identical to the heuristic selector (acceptance)
# ---------------------------------------------------------------------------
def test_off_mode_matches_heuristic_on_all_table1_configs(rng):
    sys.path.insert(0, REPO)
    from benchmarks.table1_models import SUITE

    for name, build in SUITE.items():
        g = build()
        exe = repro.compile(g, CompileOptions(target="pallas"))
        exe.ensure_compiled(batch_size=1)
        # the selector runs on the optimized graph — compare against
        # exactly what the heuristic says for it
        heuristic = select_kernels(exe.graph, batch_size=1, target="pallas")
        sel = exe._selections.get(1, {})
        assert set(sel) == set(heuristic), name
        for node, choice in sel.items():
            assert choice.source == "heuristic", (name, node)
            assert choice.kernel == heuristic[node].kernel, (name, node)
            assert choice.reason == heuristic[node].reason, (name, node)
        assert "autotune" not in exe.cost_summary(), name


def test_off_mode_outputs_identical_to_default(rng):
    g = _mlp()
    x = rng.standard_normal((2, 32)).astype(np.float32)
    out = g.outputs[0]
    y_default = np.asarray(
        repro.compile(g, CompileOptions(target="pallas"))(input=x)[out])
    y_off = np.asarray(
        repro.compile(g, CompileOptions(target="pallas",
                                        autotune="off"))(input=x)[out])
    np.testing.assert_array_equal(y_default, y_off)


def test_options_validate_autotune_fields():
    with pytest.raises(ValueError):
        CompileOptions(autotune="always")
    with pytest.raises(ValueError):
        CompileOptions(autotune_budget_ms=0)
    # autotune knobs never change the options cache token (the resolved
    # selection is keyed separately)
    assert (CompileOptions(autotune="full").cache_token()
            == CompileOptions().cache_token())


# ---------------------------------------------------------------------------
# full mode: measured winners, budget, and the persistent cache
# ---------------------------------------------------------------------------
def test_full_mode_measures_and_reports(tmp_path, rng):
    g = _mlp()
    exe = _compile_tuned(g, tmp_path)
    x = rng.standard_normal((2, 32)).astype(np.float32)
    y = exe(input=x)
    cost = exe.cost_summary()
    sel = cost["kernel_selection"][2]
    dense = [c for c in sel if c["op"] == "dense"]
    assert dense and all(c["source"] == "measured" for c in dense)
    assert all(c["measured_us"] for c in dense)
    # every measured choice must name a candidate that was benchmarked
    for c in dense:
        assert any(lbl.split("[")[0] == c["kernel"]
                   for lbl in c["measured_us"])
    rep = cost["autotune"][2]
    assert rep["mode"] == "full" and rep["measured_nodes"]
    assert rep["cache"]["stores"] == len(rep["measured_nodes"])
    # numerics unchanged vs the oracle
    oracle = repro.compile(g, CompileOptions(target="interpret"))(input=x)
    np.testing.assert_allclose(
        np.asarray(y[g.outputs[0]]),
        np.asarray(oracle[g.outputs[0]]), rtol=2e-5, atol=2e-6)


def test_exhausted_budget_falls_back_to_heuristic(tmp_path):
    g = _mlp()
    exe = _compile_tuned(g, tmp_path, budget_ms=1e-3)
    exe.ensure_compiled(batch_size=1)
    sel = exe._selections[1]
    heuristic = select_kernels(exe.graph, batch_size=1, target="pallas")
    assert all(c.source == "heuristic" for c in sel.values())
    assert {n: c.kernel for n, c in sel.items()} == \
           {n: c.kernel for n, c in heuristic.items()}
    rep = exe.cost_summary()["autotune"][1]
    assert rep["heuristic_nodes"] and not rep["measured_nodes"]


def test_tactic_cache_round_trip_across_processes(tmp_path):
    """Process 1 measures and stores; process 2 compiles the same model
    and gets every tactic from the cache without re-benchmarking."""
    prog = """
import json, sys
sys.path.insert(0, {src!r})
import repro
from repro.api.options import CompileOptions
from repro.core import ModelBuilder
mb = ModelBuilder().seed(0)
x = mb.input((32,))
h = mb.dense(x, 64, activation="relu")
out = mb.dense(h, 8)
g = mb.build([out])
exe = repro.compile(g, CompileOptions(target="pallas", autotune="full",
                                      autotune_budget_ms=20000,
                                      cache_dir={cache!r}))
exe.ensure_compiled(batch_size=1)
print(json.dumps(exe.cost_summary()["autotune"][1]))
"""
    src = os.path.join(REPO, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    reports = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c",
             prog.format(src=src, cache=str(tmp_path))],
            capture_output=True, text=True, env=env, check=True)
        reports.append(json.loads(out.stdout.strip().splitlines()[-1]))
    first, second = reports
    assert first["measured_nodes"] == ["dense_1", "dense_3"]
    assert second["measured_nodes"] == []          # no re-benchmarking
    assert set(second["cached_nodes"]) == {"dense_1", "dense_3"}
    assert second["cache"]["hits"] == 2


def test_cached_mode_never_measures(tmp_path):
    g = _mlp()
    exe = _compile_tuned(g, tmp_path, mode="cached")
    exe.ensure_compiled(batch_size=1)
    rep = exe.cost_summary()["autotune"][1]
    assert not rep["measured_nodes"] and rep["cache"]["stores"] == 0
    assert all(c.source == "heuristic"
               for c in exe._selections[1].values())


# ---------------------------------------------------------------------------
# corruption / staleness: heuristic fallback, never a crash (satellite)
# ---------------------------------------------------------------------------
def _populate_cache(tmp_path):
    g = _mlp()
    exe = _compile_tuned(g, tmp_path)
    exe.ensure_compiled(batch_size=1)
    tactics_dir = os.path.join(str(tmp_path), "tactics")
    files = [os.path.join(tactics_dir, f) for f in os.listdir(tactics_dir)]
    assert files
    return g, files


def test_corrupt_tactic_entries_fall_back(tmp_path):
    g, files = _populate_cache(tmp_path)
    for f in files:
        with open(f, "w") as fh:
            fh.write("{not json")
    exe = _compile_tuned(g, tmp_path, mode="cached")
    exe.ensure_compiled(batch_size=1)
    assert all(c.source == "heuristic"
               for c in exe._selections[1].values())
    # every corrupt entry the compile probed is dropped so it stops
    # costing a parse.  (Kernel-tactic entries measured under the tuned
    # graph's geometry are never probed once the corrupted graph-level
    # decisions fall back to the heuristic pipeline, so a strict "all
    # gone" doesn't hold — but the probed majority must be.)
    tactics_dir = os.path.dirname(files[0])
    remaining = [f for f in os.listdir(tactics_dir) if f.endswith(".json")]
    assert len(remaining) < len(files)


def test_stale_fingerprint_entries_ignored(tmp_path):
    g, files = _populate_cache(tmp_path)
    for f in files:
        with open(f) as fh:
            entry = json.load(fh)
        entry["fingerprint"] = "0" * 64   # measured in another world
        with open(f, "w") as fh:
            json.dump(entry, fh)
    exe = _compile_tuned(g, tmp_path, mode="cached")
    exe.ensure_compiled(batch_size=1)
    assert all(c.source == "heuristic"
               for c in exe._selections[1].values())
    # stale-but-parseable entries are kept (valid for their writer)
    assert os.path.exists(files[0])


def test_malformed_winner_entry_falls_back(tmp_path):
    g, files = _populate_cache(tmp_path)
    fp = environment_fingerprint()
    for f in files:
        with open(f, "w") as fh:
            json.dump({"winner": 42, "fingerprint": fp}, fh)
    exe = _compile_tuned(g, tmp_path, mode="cached")
    exe.ensure_compiled(batch_size=1)
    assert all(c.source == "heuristic"
               for c in exe._selections[1].values())


# ---------------------------------------------------------------------------
# plumbing details
# ---------------------------------------------------------------------------
def test_tactic_key_depends_on_desc_and_fingerprint():
    d1 = {"op": "dense", "m": 8, "k": 32, "n": 64}
    d2 = {"op": "dense", "m": 8, "k": 32, "n": 128}
    assert tactic_key(d1) == tactic_key(d1)
    assert tactic_key(d1) != tactic_key(d2)
    assert tactic_key(d1) != tactic_key(d1, fingerprint="f" * 64)


def test_candidates_shared_shapes_measured_once(tmp_path):
    # Two dense layers with identical geometry share one measurement.
    mb = ModelBuilder().seed(0)
    x = mb.input((64,))
    h = mb.dense(x, 64, activation="relu")
    h = mb.dense(h, 64, activation="relu")
    out = mb.dense(h, 64, activation="relu")
    g = mb.build([out])
    cache = open_tactic_cache(str(tmp_path))
    heuristic = select_kernels(g, batch_size=1, target="pallas")
    tuned, rep = tune_selection(g, heuristic, batch_size=1,
                                precision="exact", mode="full",
                                budget_ms=20_000, cache=cache)
    # all three dense layers are 64->64 relu — one measurement, two
    # memo hits (activations under exact precision have nothing to tune)
    assert len(rep["measured_nodes"]) == 1
    assert len(rep["cached_nodes"]) == 2
    assert all(tuned[n].source == "measured"
               for n, c in heuristic.items() if c.op == "dense")


def test_executable_cache_key_tracks_selection(tmp_path):
    g = _mlp()
    exe = repro.compile(g, CompileOptions(target="pallas"))
    heuristic_key = exe._key(1, select_kernels(g, batch_size=1,
                                               target="pallas"))
    measured = {
        n: repro.core.KernelChoice(c.node, c.op, "lax.dot", "measured",
                                   source="measured")
        for n, c in select_kernels(g, batch_size=1,
                                   target="pallas").items()}
    assert exe._key(1, measured) != heuristic_key
    # same resolved selection -> same key, regardless of autotune mode
    exe2 = repro.compile(g, CompileOptions(target="pallas",
                                           autotune="cached"))
    assert exe2._key(1, select_kernels(g, batch_size=1,
                                       target="pallas")) == heuristic_key
