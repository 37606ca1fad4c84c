"""``BENCHMARK.json``: loading, validation, and resolving a cell to the
files that describe it.

Everything that belongs to one configuration, traffic mix or metric
lives in a file of its own, found by name:

* configuration ``<c>``: the JSON file that ``configs[].file`` names,
  and beside it ``<same stem>.py``, its plain reference and the code that builds it;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* metric ``<m>`` (end-to-end or per-layer): ``bench/metrics/<m>.py``,
  a module with ``read(ctx) -> float | None``.

So a cell, a configuration, a mix or a metric is added by adding files
and ``BENCHMARK.json`` entries, never by editing a file.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import re
from typing import Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRIC_SOURCES = ("device_trace", "program_span", "program_counter",
                  "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


class SpecError(ValueError):
    """``BENCHMARK.json`` breaks the benchmark's contract, or a cell
    names a file that is not there."""


def _line(text, what: str, errors: List[str]) -> None:
    if (not isinstance(text, str) or not 1 <= len(text) <= 200
            or "\n" in text or "\t" in text):
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def _name(name, what: str, errors: List[str]) -> None:
    if not isinstance(name, str) or not NAME_RE.match(name):
        errors.append(f"{what}: bad name {name!r}")


def validate(spec: dict) -> List[str]:
    """Every breach of the contract's shape rules, as messages (empty
    when the file is well formed)."""
    errors: List[str] = []
    if not isinstance(spec, dict) or set(spec) != TOP_KEYS:
        return [f"top-level keys must be exactly {sorted(TOP_KEYS)}"]
    cmd = spec["command"]
    if (not isinstance(cmd, list) or not 1 <= len(cmd) <= 32
            or not all(isinstance(w, str) for w in cmd)):
        errors.append("command: a list of 1 to 32 strings")
    else:
        for w in cmd:
            _line(w, f"command word {w!r}", errors)
            if w.startswith("/") or ".." in w.split("/"):
                errors.append(f"command word {w!r}: leaves the repository")
    paths = spec["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
        paths = []
    for p in paths:
        if (not isinstance(p, str) or not PATH_RE.match(p)
                or p.startswith("/") or ".." in p.split("/")):
            errors.append(f"paths: bad directory {p!r}")
    rs = spec["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        errors.append("run_seconds: a whole number from 1 to 51")

    configs = {}
    if not isinstance(spec["configs"], list) or not 1 <= len(
            spec["configs"]) <= 24:
        errors.append("configs: 1 to 24 entries")
    for c in spec["configs"] or []:
        if not isinstance(c, dict) or set(c) != CONFIG_KEYS:
            errors.append(f"config {c!r}: keys must be {sorted(CONFIG_KEYS)}")
            continue
        _name(c["name"], "config", errors)
        if c["name"] in configs:
            errors.append(f"config {c['name']}: duplicate name")
        configs[c["name"]] = c
        _line(c["source"], f"config {c['name']} source", errors)
        _line(c["why"], f"config {c['name']} why", errors)
        if not any(isinstance(c["file"], str)
                   and c["file"].startswith(p.rstrip("/") + "/")
                   for p in paths):
            errors.append(f"config {c['name']}: file not under paths")
        if not isinstance(c["reduced"], list) or len(c["reduced"]) > 16:
            errors.append(f"config {c['name']}: reduced is a list of <= 16")
        else:
            for k in c["reduced"]:
                _name(k, f"config {c['name']} reduced key", errors)
    files = [c["file"] for c in configs.values()]
    if len(set(files)) != len(files):
        errors.append("configs: two configurations share a file")

    e2e = {}
    for m in spec["end_to_end"] or []:
        if not isinstance(m, dict) or not E2E_KEYS <= set(m) or not set(
                m) <= E2E_KEYS | {"workloads"}:
            errors.append(f"end_to_end {m!r}: keys must be "
                          f"{sorted(E2E_KEYS)} (+ workloads)")
            continue
        e2e[m["name"]] = m
        if m["source"] not in E2E_SOURCES:
            errors.append(f"{m['name']}: end-to-end source must be one of "
                          f"{E2E_SOURCES}")
        b = m["bound"]
        if (not isinstance(b, (int, float)) or isinstance(b, bool)
                or not 0.01 <= b <= 0.25 or math.isnan(b)):
            errors.append(f"{m['name']}: bound must lie in [0.01, 0.25]")
    if not 1 <= len(e2e) <= 16:
        errors.append("end_to_end: 1 to 16 metrics")
    if "setup_s" not in e2e:
        errors.append("end_to_end: setup_s is required")
    layers = {}
    for m in spec["per_layer"] or []:
        if not isinstance(m, dict) or not LAYER_KEYS <= set(m) or not set(
                m) <= LAYER_KEYS | {"workloads"}:
            errors.append(f"per_layer {m!r}: keys must be "
                          f"{sorted(LAYER_KEYS)} (+ workloads)")
            continue
        layers[m["name"]] = m
        _line(m["layer"], f"{m['name']} layer", errors)
        if m["moves"] not in e2e:
            errors.append(f"{m['name']}: moves {m['moves']!r}, "
                          "not an end-to-end metric")
        if m["source"] not in METRIC_SOURCES:
            errors.append(f"{m['name']}: source must be one of "
                          f"{METRIC_SOURCES}")
    if not 1 <= len(layers) <= 128:
        errors.append("per_layer: 1 to 128 metrics")
    names = list(e2e) + list(layers)
    if len(set(names)) != len(names):
        errors.append("metrics: two metrics share a name")
    for m in list(e2e.values()) + list(layers.values()):
        _name(m["name"], "metric", errors)
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            errors.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            errors.append(f"{m['name']}: better is 'lower' or 'higher'")

    cells = {}
    wl = spec["workloads"] or []
    if not 1 <= len(wl) <= 24:
        errors.append("workloads: 1 to 24 cells")
    for w in wl:
        if not isinstance(w, dict) or set(w) != WORKLOAD_KEYS:
            errors.append(f"workload {w!r}: keys must be "
                          f"{sorted(WORKLOAD_KEYS)}")
            continue
        for k in ("name", "config", "traffic"):
            _name(w[k], f"workload {k}", errors)
        _line(w["why"], f"workload {w['name']} why", errors)
        if w["name"] in cells:
            errors.append(f"workload {w['name']}: duplicate name")
        cells[w["name"]] = w
        if w["config"] not in configs:
            errors.append(f"workload {w['name']}: unknown config")
        if w["chips"] not in (1, 4):
            errors.append(f"workload {w['name']}: chips is 1 or 4")
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    if len(set(pairs)) != len(pairs):
        errors.append("workloads: a configuration and traffic pair twice")
    if sum(w["chips"] == 4 for w in cells.values()) > max(
            1, len(cells) // 2):
        errors.append("workloads: too many four-chip cells")
    used = {w["config"] for w in cells.values()}
    for c in configs:
        if c not in used:
            errors.append(f"config {c}: used by no cell")
    for m in list(e2e.values()) + list(layers.values()):
        for w in m.get("workloads", []):
            if w not in cells:
                errors.append(f"{m['name']}: unknown workload {w!r}")
    if not errors:
        for w in cells:
            got = [m["name"] for m in e2e_for(spec, w)]
            if "setup_s" not in got or len(got) < 2:
                errors.append(f"workload {w}: needs setup_s and another "
                              "end-to-end metric")
            if not per_layer_for(spec, w):
                errors.append(f"workload {w}: needs a per-layer metric")
    return errors


def e2e_for(spec: dict, workload: str) -> List[dict]:
    """End-to-end metrics this cell reports (with ``--trace 0``)."""
    return [m for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_for(spec: dict, workload: str) -> List[dict]:
    """Per-layer metrics this cell reports (with ``--trace 1``): those
    that list it, and those without a list whose ``moves`` metric the
    cell reports."""
    moved = {m["name"] for m in e2e_for(spec, workload)}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def load(root: str) -> dict:
    """Read and validate ``<root>/BENCHMARK.json``."""
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path) as f:
            spec = json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e
    errors = validate(spec)
    if errors:
        raise SpecError("BENCHMARK.json: " + "; ".join(errors))
    return spec


def load_module(path: str, name: Optional[str] = None):
    """Import the Python file at ``path`` as a fresh module (file names
    may hold dots and dashes, so not through the package system)."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    mod_name = name or "bench_file_" + re.sub(r"\W", "_", os.path.relpath(
        path, BENCH_DIR))
    sp = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload with everything its files say."""

    name: str
    chips: int
    config: dict            # the configuration file as it is run
    config_path: str
    traffic: dict           # the traffic mix's parameters
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str

    def config_module(self):
        """The configuration's plain reference and the code that builds it, beside its
        JSON file."""
        return load_module(os.path.splitext(self.config_path)[0] + ".py")

    def metric_reader(self, name: str):
        """``read(ctx)`` of the metric's own file."""
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        name + ".py")).read


def resolve(spec: dict, root: str, workload: str,
            bench_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``workload``, with its configuration and traffic
    files read; raises :class:`SpecError` if a name or file is
    missing."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"unknown workload {workload!r}; have "
                        f"{sorted(cells)}")
    w = cells[workload]
    c = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config_path = os.path.join(root, c["file"])
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    cell = Cell(name=workload, chips=w["chips"],
                config=_read_json(config_path), config_path=config_path,
                traffic=traffic, end_to_end=e2e_for(spec, workload),
                per_layer=per_layer_for(spec, workload),
                bench_dir=bench_dir)
    for m in cell.end_to_end + cell.per_layer:
        path = os.path.join(bench_dir, "metrics", m["name"] + ".py")
        if not os.path.isfile(path):
            raise SpecError(f"metric {m['name']}: missing reader {path}")
    if not os.path.isfile(os.path.splitext(config_path)[0] + ".py"):
        raise SpecError(f"config {c['name']}: missing reference module")
    return cell


def metric_names(cell: Cell, trace: bool) -> Dict[str, dict]:
    """The metrics a run of ``cell`` prints, by name."""
    return {m["name"]: m for m in (cell.per_layer if trace
                                   else cell.end_to_end)}
