"""Graph-level decision tuning (repro.autotune.decisions).

Covers the PR-7 tentpole invariants:
* the graph-region digest is invariant to node naming and insertion
  order, and changes on any shape/dtype/structure edit;
* the pass hooks (tune.fuse / tune.layout / pipeline variants) actually
  steer fuse_activation, optimize_layout and the pipeline;
* tuned decisions persist in the tactic cache and replay cross-process
  with autotune="cached" — bit-identical winners, zero measurement;
* autotune="off" never writes a decision attr (bit-identity guard).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import repro
from repro import CompileOptions
from repro.autotune import (enumerate_sites, extract_region, region_digest,
                            tune_graph_decisions)
from repro.autotune.cache import TacticCache
from repro.core import ModelBuilder
from repro.core.graph import Graph
from repro.core.passes import PassManager, run_pipeline
from repro.core.passes.fuse_activation import TUNE_FUSE_ATTR
from repro.core.passes.layout import TUNE_LAYOUT_ATTR
from repro.core.passes.manager import pipeline_candidates

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(in_dim=16, hidden=32, out=8):
    mb = ModelBuilder().seed(0)
    x = mb.input((in_dim,))
    h = mb.dense(x, hidden, activation="relu")
    o = mb.dense(h, out)
    return mb.build([o])


def _dense_act_graph(names=("d", "a"), tensors=("t0", "t1"), in_dim=16,
                     out_dim=32, dtype="float32", order="da"):
    """Hand-built dense→relu graph with controllable names/order."""
    rng = np.random.default_rng(0)
    g = Graph()
    g.add_input("x", (in_dim,), dtype)
    g.add_param("w", rng.standard_normal((in_dim, out_dim)))
    g.add_node("dense", names[0], ["x"], output=tensors[0],
               params={"kernel": "w"})
    g.add_node("activation", names[1], [tensors[0]], output=tensors[1],
               attrs={"fn": "relu"})
    g.set_outputs([tensors[1]])
    return g


# ---------------------------------------------------------------------------
# region digest
# ---------------------------------------------------------------------------
def test_digest_invariant_to_node_and_tensor_names():
    a = _dense_act_graph(names=("d", "a"), tensors=("t0", "t1"))
    b = _dense_act_graph(names=("layer7", "omega"), tensors=("u", "v"))
    assert (region_digest(a, [n.name for n in a.nodes])
            == region_digest(b, [n.name for n in b.nodes]))


def test_digest_invariant_to_insertion_order():
    """Two independent dense heads built in opposite order digest the
    same — the digest sorts per-node content hashes, it never sees
    list position."""
    rng = np.random.default_rng(0)

    def build(flip):
        g = Graph()
        g.add_input("x", (16,))
        g.add_param("w1", rng.standard_normal((16, 32)))
        g.add_param("w2", rng.standard_normal((16, 8)))
        heads = [("h1", "w1"), ("h2", "w2")]
        for name, w in (reversed(heads) if flip else heads):
            g.add_node("dense", name, ["x"], params={"kernel": w})
        g.set_outputs([n.output for n in g.nodes])
        return g

    a, b = build(False), build(True)
    assert (region_digest(a, ["h1", "h2"])
            == region_digest(b, ["h1", "h2"]))


@pytest.mark.parametrize("edit", ["shape", "dtype", "structure"])
def test_digest_changes_on_semantic_edits(edit):
    base = _dense_act_graph()
    if edit == "shape":
        other = _dense_act_graph(in_dim=24)
    elif edit == "dtype":
        other = _dense_act_graph(dtype="bfloat16")
    else:   # structure: different activation fn
        other = _dense_act_graph()
        other.nodes[1].attrs["fn"] = "tanh"
    assert (region_digest(base, [n.name for n in base.nodes])
            != region_digest(other, [n.name for n in other.nodes]))


def test_digest_ignores_tune_attrs():
    """Decision attrs must not feed back into the site identity, or a
    tuned graph would never hit the entries measured for it."""
    a = _dense_act_graph()
    b = _dense_act_graph()
    b.nodes[0].attrs[TUNE_LAYOUT_ATTR] = "oi"
    b.nodes[1].attrs[TUNE_FUSE_ATTR] = False
    assert (region_digest(a, [n.name for n in a.nodes])
            == region_digest(b, [n.name for n in b.nodes]))


def test_digest_unknown_node_raises():
    g = _dense_act_graph()
    with pytest.raises(KeyError):
        region_digest(g, ["nope"])


# ---------------------------------------------------------------------------
# site enumeration + region extraction
# ---------------------------------------------------------------------------
def test_enumerate_sites_shapes():
    g = _mlp()
    sites = enumerate_sites(g)
    kinds = [s.kind for s in sites]
    assert kinds.count("layout") == 2        # two dense nodes
    assert kinds.count("fusion") == 1        # one legal dense→relu site
    assert kinds.count("pipeline") == 1
    assert kinds[-1] == "pipeline"           # cheapest sites first
    pipeline_site = sites[-1]
    assert set(pipeline_site.choices) == set(pipeline_candidates())


def test_enumerate_sites_explicit_passes_pins_pipeline():
    g = _mlp()
    sites = enumerate_sites(g, passes=("canonicalize",))
    assert all(s.kind != "pipeline" for s in sites)


def test_extract_region_is_self_contained():
    g = _mlp()
    fusion = [s for s in enumerate_sites(g) if s.kind == "fusion"][0]
    mini = extract_region(g, fusion.region)
    assert len(mini.nodes) == 2
    mini.infer_shapes()          # validates
    # the mini-graph digest matches the site's: entries transfer
    assert (region_digest(mini, [n.name for n in mini.nodes])
            == fusion.digest)


# ---------------------------------------------------------------------------
# pass hooks
# ---------------------------------------------------------------------------
def test_tune_fuse_attr_blocks_fusion():
    g = _dense_act_graph()
    fused, _ = run_pipeline(g)
    assert len(fused.nodes) == 1             # heuristic fuses

    g2 = _dense_act_graph()
    g2.nodes[1].attrs[TUNE_FUSE_ATTR] = False
    unfused, _ = run_pipeline(g2)
    assert len(unfused.nodes) == 2           # hook keeps it unfused
    assert unfused.nodes[0].epilogue is None


def test_tune_layout_attr_overrides_heuristic():
    # rows=1 < SUBLANE_ALIGN → heuristic transposes to "oi"; the tuned
    # attr pins "io" and must win.
    g = _dense_act_graph()
    g.nodes[0].attrs[TUNE_LAYOUT_ATTR] = "io"
    out, _ = run_pipeline(g)
    dense = [n for n in out.nodes if n.op == "dense"][0]
    assert dense.attrs["kernel_layout"] == "io"

    g2 = _dense_act_graph()
    out2, _ = run_pipeline(g2)
    dense2 = [n for n in out2.nodes if n.op == "dense"][0]
    assert dense2.attrs["kernel_layout"] == "oi"    # heuristic baseline


def test_pipeline_candidates_contract():
    variants = pipeline_candidates()
    assert list(variants)[0] == "default"
    assert variants["default"] == PassManager.default().pipeline
    assert not any("fuse_activation" in p for p in variants["no_fusion"])
    assert "optimize_layout" not in variants["no_layout"]


# ---------------------------------------------------------------------------
# tuning + cross-process cached replay
# ---------------------------------------------------------------------------
def test_tune_graph_decisions_cached_replays(tmp_path):
    g = _mlp()
    cache = TacticCache(os.path.join(str(tmp_path), "tactics"))
    _, pipe1, rep1 = tune_graph_decisions(
        g, target="pallas", precision="exact", passes=None,
        mode="full", budget_ms=20_000, cache=cache, batch_size=1)
    assert all(r["source"] == "measured" for r in rep1["sites"])
    assert rep1["entries"]

    _, pipe2, rep2 = tune_graph_decisions(
        g, target="pallas", precision="exact", passes=None,
        mode="cached", budget_ms=None, cache=cache, batch_size=1)
    assert pipe1 == pipe2
    assert ([(r["kind"], r["node"], r["winner"]) for r in rep1["sites"]]
            == [(r["kind"], r["node"], r["winner"]) for r in rep2["sites"]])
    assert all(r["source"] == "cached" for r in rep2["sites"])
    assert rep2["spent_ms"] < 50            # cached mode never measures


def test_spent_ms_excludes_fingerprint(tmp_path, monkeypatch):
    """The fingerprint reads the backend, which a fresh process brings
    up on that first read: set-up, not measurement spend."""
    import time
    from repro.autotune import decisions
    real = decisions.environment_fingerprint

    def slow():
        time.sleep(0.2)
        return real()

    monkeypatch.setattr(decisions, "environment_fingerprint", slow)
    _, _, rep = tune_graph_decisions(
        _mlp(), target="pallas", precision="exact", passes=None,
        mode="cached", budget_ms=None,
        cache=TacticCache(os.path.join(str(tmp_path), "tactics")),
        batch_size=1)
    assert rep["spent_ms"] < 50


def test_cached_mode_without_entries_keeps_heuristics(tmp_path):
    g = _mlp()
    cache = TacticCache(os.path.join(str(tmp_path), "tactics"))
    decided, pipe, rep = tune_graph_decisions(
        g, target="pallas", precision="exact", passes=None,
        mode="cached", budget_ms=None, cache=cache, batch_size=1)
    assert pipe is None
    assert all(r["source"] == "heuristic" and r["winner"] is None
               for r in rep["sites"])
    # no decision attr was written: the decided graph is bit-identical
    assert decided.structure_hash() == g.structure_hash()


def test_autotune_off_writes_no_decision_attrs(tmp_path):
    g = _mlp()
    exe = repro.compile(g, CompileOptions(target="pallas", autotune="off",
                                          cache_dir=str(tmp_path)))
    exe.ensure_compiled(1)
    for node in exe.graph.nodes:
        assert not any(k.startswith("tune.") for k in node.attrs)
    assert exe.cost_summary().get("graph_decisions") is None


def test_decisions_replay_cross_process(tmp_path):
    """Process 1 measures graph decisions; process 2 (autotune="cached")
    resolves the same winners from the tactic cache — bit-identically,
    with zero measurement spend."""
    prog = """
import json, sys
sys.path.insert(0, {src!r})
import repro
from repro.api.options import CompileOptions
from repro.core import ModelBuilder
mb = ModelBuilder().seed(0)
x = mb.input((16,))
h = mb.dense(x, 32, activation="relu")
out = mb.dense(h, 8)
g = mb.build([out])
exe = repro.compile(g, CompileOptions(target="pallas", autotune={mode!r},
                                      autotune_budget_ms=20000,
                                      cache_dir={cache!r}))
exe.ensure_compiled(batch_size=1)
rep = exe.cost_summary()["graph_decisions"]
print(json.dumps({{"sites": [(r["kind"], r["node"], r["winner"])
                             for r in rep["sites"]],
                  "sources": sorted({{r["source"] for r in rep["sites"]}}),
                  "spent_ms": rep["spent_ms"]}}))
"""
    src = os.path.join(REPO, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = []
    for mode in ("full", "cached"):
        r = subprocess.run(
            [sys.executable, "-c",
             prog.format(src=src, cache=str(tmp_path), mode=mode)],
            capture_output=True, text=True, env=env, check=True)
        out.append(json.loads(r.stdout.strip().splitlines()[-1]))
    first, second = out
    assert first["sites"] == second["sites"]
    assert first["sources"] == ["measured"]
    assert second["sources"] == ["cached"]
    assert second["spent_ms"] < 50
