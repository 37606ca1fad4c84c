"""The cells ``BENCHMARK.json`` lists resolve to their files, and their
metrics read on a run of each listed cell at a CPU-sized configuration
chosen by its runner (the look for a chip skipped): every end-to-end metric and every reader that
needs no trace gives a finite number, and a reader that needs the trace
gives nothing without one."""

import math

import pytest

from bench import device, spec
from bench.tests import tiny

#: Sources a run without a profiler trace can read
TRACE_FREE = ("host_clock", "program_counter")
#: ``max_logit_gap`` of ``qwen14b.chat`` on a TPU v5e: the widest the
#: program read over its seeds, the narrowest the int8 control read
PROGRAM_WIDEST, CONTROL_NARROWEST = 0.0611, 0.2850


@pytest.fixture(scope="module")
def bench_spec():
    return spec.load(tiny.ROOT)


def test_serving_cell_resolves_to_its_files(bench_spec):
    assert spec.validate(bench_spec) == []
    cell = spec.resolve(bench_spec, tiny.ROOT, "qwen14b.chat")
    assert cell.chips == 1
    assert cell.config["name"] == "qwen2.5-14b"
    assert cell.config["runner"] == "serve"
    assert cell.traffic["loop"] == "open"
    assert {m["name"] for m in cell.end_to_end} >= {
        "itl_ms_p95", "ttft_ms_p50", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {
        "step_decode_ms.serve", "step_sample_ms.serve",
        "sample_idle_share.serve", "decode_roofline", "mfu.serve",
        "idle_share.serve", "queue_ms_p95.serve"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.metric_reader(m["name"]))
    ref = cell.config_module()
    assert callable(ref.gaps) and callable(ref.control_gaps)


def test_serving_limit_lies_between_program_and_control():
    """The compared number's limit lies above the program's widest gap
    and below the int8 control's narrowest, with more room above the
    program: readings of ``qwen14b.chat`` on a TPU v5e (PERF.md)."""
    chk = tiny._read("bench/configs/qwen2.5-14b.json")["check"]
    assert chk["number"] == "max_logit_gap"
    assert PROGRAM_WIDEST < chk["limit"] < CONTROL_NARROWEST
    assert chk["limit"] - PROGRAM_WIDEST > CONTROL_NARROWEST - chk["limit"]


@pytest.mark.parametrize("workload", sorted(
    w["name"] for w in spec.load(tiny.ROOT)["workloads"]))
def test_listed_metrics_read_on_a_cpu_run(bench_spec, workload):
    listed = spec.resolve(bench_spec, tiny.ROOT, workload)
    run = tiny.drive(tiny.shrink(listed), seed=2 ** 33 + 5, seconds=0.5)
    assert run.correct, run.checks
    run.peaks = device.peaks_for("TPU v5 lite", device.load_peaks())
    for m in listed.end_to_end:
        value = listed.metric_reader(m["name"])(run)
        assert value is not None and math.isfinite(value), m["name"]
        assert value > 0, m["name"]
    for m in listed.per_layer:
        value = listed.metric_reader(m["name"])(run)
        if m["source"] in TRACE_FREE:
            assert value is not None and math.isfinite(value), m["name"]
        else:
            assert value is None, m["name"]
