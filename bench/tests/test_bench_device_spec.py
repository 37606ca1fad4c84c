"""The device check, and the shape rules of ``BENCHMARK.json``."""

import copy
import json
import os
import shutil

import pytest

from bench import device, spec
from bench.tests import tiny


class Dev:
    def __init__(self, platform="tpu", kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, kind


def test_known_tpu_passes_and_gives_peaks():
    p = device.check([Dev()], 1, device.load_peaks())
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["int8_ops_per_s"] == 394e12 and p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("devs,chips", [
    ([Dev(platform="cpu", kind="cpu")], 1),
    ([Dev(platform="gpu", kind="H100")], 1),
    ([Dev(kind="TPU v9 imaginary")], 1),
    ([Dev()], 4),
    ([], 1),
])
def test_refuses_other_devices(devs, chips):
    with pytest.raises(device.DeviceError):
        device.check(devs, chips, device.load_peaks())


def test_committed_benchmark_is_valid():
    s = spec.load(tiny.ROOT)
    assert spec.validate(s) == []
    for w in s["workloads"]:
        cell = spec.resolve(s, tiny.ROOT, w["name"])
        assert cell.chips == 1
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert cell.per_layer


@pytest.mark.parametrize("edit,msg", [
    (lambda s: s["end_to_end"][0].update(name="bad name"), "bad name"),
    (lambda s: s["end_to_end"][0].update(unit="tokens per s"), "bad unit"),
    (lambda s: s["end_to_end"][0].update(unit="µs"), "bad unit"),
    (lambda s: s["end_to_end"][0].update(bound=0.3), "bound"),
    (lambda s: s["end_to_end"][0].update(source="program_span"), "source"),
    (lambda s: s["per_layer"][0].update(better="faster"), "better"),
    (lambda s: s["per_layer"][0].update(why="x"), "keys"),
    (lambda s: s["workloads"][0].update(name="a,b"), "bad name"),
    (lambda s: s["workloads"][0].update(chips=2), "chips"),
    (lambda s: s.update(run_seconds=52), "run_seconds"),
    (lambda s: s.update(command=["python3", "/abs/run.py"]), "command"),
    (lambda s: s["per_layer"][0].update(moves="nothing"), "moves"),
])
def test_refuses_malformed_benchmark(edit, msg):
    s = copy.deepcopy(spec.load(tiny.ROOT))
    edit(s)
    errors = spec.validate(s)
    assert errors and any(msg in e for e in errors), errors


def test_resolves_a_cell_added_as_new_files_only(tmp_path):
    """A configuration, a traffic mix, a metric and a cell that exist
    only as new files and new BENCHMARK.json entries are found by name,
    with no existing file edited."""
    root, bench = tmp_path, tmp_path / "bench"
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    s = spec.load(tiny.ROOT)
    (bench / "configs" / "mini.json").write_text(json.dumps(
        dict(tiny._read("bench/configs/vgg19.json"), name="mini")))
    shutil.copy(bench / "configs" / "vgg19.py", bench / "configs" / "mini.py")
    (bench / "traffic" / "closed_b2.json").write_text(json.dumps(
        {"loop": "closed", "callers": 1, "batch": 2, "pool": 2}))
    (bench / "metrics" / "call_ms_p50.py").write_text(
        "def read(run):\n    return 42.0\n")
    s["configs"].append({"name": "mini", "source": "arXiv:1409.1556",
                         "file": "bench/configs/mini.json", "reduced": [],
                         "why": "added by files alone"})
    s["workloads"].append({"name": "mini.b2", "config": "mini",
                           "traffic": "closed_b2", "chips": 1,
                           "why": "added by files alone"})
    s["end_to_end"].append({"name": "call_ms_p50", "unit": "ms",
                            "better": "lower", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["mini.b2"]})
    s["per_layer"][0]["workloads"] = s["per_layer"][0].get(
        "workloads", []) + ["mini.b2"]
    (root / "BENCHMARK.json").write_text(json.dumps(s))
    cell = spec.resolve(spec.load(str(root)), str(root), "mini.b2",
                        bench_dir=str(bench))
    assert cell.config["name"] == "mini" and cell.traffic["batch"] == 2
    assert {m["name"] for m in cell.end_to_end} >= {"call_ms_p50",
                                                    "setup_s"}
    assert cell.metric_reader("call_ms_p50")(None) == 42.0
    assert hasattr(cell.config_module(), "reference")


def test_missing_files_are_refused(tmp_path):
    s = spec.load(tiny.ROOT)
    s["workloads"][0]["traffic"] = "no_such_mix"
    with pytest.raises(spec.SpecError):
        spec.resolve(s, tiny.ROOT, s["workloads"][0]["name"])
