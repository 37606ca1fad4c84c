"""The readers of the program's own spans (``bench/program_spans.py``
and the six metric files that use it), on event lists built by hand,
on a profiler trace written on the CPU, and on a trace recorded on a
TPU v5e (``data/trace_vgg19_b1_spans.json``).

``trace_reduce.load`` keeps a host event that is not a ``bench.`` span
only if it lasts at least 20 us (``trace_reduce.HOST_MIN_NS``): a
shorter program span is invisible to these readers.
"""

import json
import os
import time

import jax
import pytest

from bench import device, program_spans, record, spec, trace_reduce as T
from bench.tests import tiny

MS = 1e6                                    # nanoseconds
HOST, DEV = "/host:CPU", "/device:TPU:0"
CALL = ("repro.call.bind", "repro.call.transfer", "repro.call.dispatch",
        "repro.call.outputs")
READERS = ("call_transfer_ms.cnn", "call_dispatch_ms.cnn",
           "call_idle_share.cnn", "step_decode_ms.serve",
           "step_sample_ms.serve", "sample_idle_share.serve")


def reader(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                         name + ".py")).read


def _call(o, lag=0.0):
    """One call at ``o`` ns: bind 0-0.05 ms, transfer -0.25, dispatch
    -0.55 (the program enqueued at 0.4 ms), outputs 0.95-0.96 ms; the
    device busy 0.4-0.95 ms, its events put ``lag`` ns early as the
    profiler puts a TPU's."""
    return [T.Event(HOST, "py", "bench.call", o, 1.0 * MS),
            T.Event(HOST, "py", CALL[0], o, 0.05 * MS),
            T.Event(HOST, "py", CALL[1], o + 0.05 * MS, 0.2 * MS),
            T.Event(HOST, "py", CALL[2], o + 0.25 * MS, 0.3 * MS),
            T.Event(HOST, "py", CALL[3], o + 0.95 * MS, 0.01 * MS),
            T.Event(HOST, "rt", program_spans.ENQUEUE, o + 0.4 * MS,
                    0.02 * MS),
            T.Event(HOST, "rt", "_ReadSyncFlag", o + 0.6 * MS, 0.35 * MS),
            T.Event(DEV, T.MODULES_LINE, "jit_program(1)",
                    o + 0.4 * MS - lag, 0.55 * MS),
            T.Event(DEV, T.OPS_LINE, "%fused_matmul.1 = f32[8,4096]",
                    o + 0.4 * MS - lag, 0.55 * MS)]


def _compile_run(events, calls=2):
    run = record.Run(config={}, traffic={})
    run.trace = T.reduce(events)
    run.counters["traced_calls"] = calls
    return run


def _two_calls(lag=0.0):
    return [T.Event(HOST, "py", "bench.window", 0, 2 * MS)] + \
        _call(0, lag) + _call(1 * MS, lag)


@pytest.mark.parametrize("lag", [0.0, 1.13 * MS])
def test_call_readers_on_two_calls(lag):
    """The readers give the same values whether or not the trace puts
    the device's events early: the idle share moves them back first."""
    run = _compile_run(_two_calls(lag))
    assert reader("call_transfer_ms.cnn")(run) == pytest.approx(0.2)
    assert reader("call_dispatch_ms.cnn")(run) == pytest.approx(0.3)
    assert program_spans.device_lag_ns(run.trace) == pytest.approx(lag)
    # idle 0-0.4 ms under bind/transfer/dispatch, 0.95-0.96 under
    # outputs; 0.96-1.0 ms is idle outside every program span
    share = reader("call_idle_share.cnn")(run)
    assert share == pytest.approx(100 * 2 * 0.41 / 2)
    assert share <= 100 * run.trace.idle_share() + 1e-9
    if not lag:
        assert run.trace.idle_share() == pytest.approx(0.45)


def test_device_lag_needs_two_pairs_and_ignores_stray_enqueues():
    """Without enqueues, or with one program paired, no lag is read;
    an enqueue dropped by the loader or one of another program leaves
    the lag that most programs share."""
    run = _compile_run([e for e in _two_calls(1.13 * MS)
                        if e.name != program_spans.ENQUEUE])
    assert program_spans.device_lag_ns(run.trace) is None
    assert reader("call_idle_share.cnn")(run) is None
    assert program_spans.device_lag_ns(_compile_run(
        [T.Event(HOST, "py", "bench.window", 0, 2 * MS)]
        + _call(0, 1.13 * MS)).trace) is None
    ev = [T.Event(HOST, "py", "bench.window", 0, 5 * MS)]
    for o in range(4):
        ev += _call(o * 1.2 * MS, 1.13 * MS)
    ev = [e for e in ev if not (e.name == program_spans.ENQUEUE
                                and e.start_ns == 1.6 * MS)]
    ev.append(T.Event(HOST, "rt", program_spans.ENQUEUE, 2.2 * MS,
                      0.02 * MS))
    lag = program_spans.device_lag_ns(_compile_run(ev, calls=4).trace)
    assert lag == pytest.approx(1.13 * MS)


def test_gaps_are_named_by_the_program_span_over_them():
    r = T.reduce(_two_calls())
    # each gap takes the name of the span that covers most of it
    names = {n for n, _ in r.breakdown()["idle_gaps"]}
    assert names == {"bench.call: repro.call.transfer",
                     "bench.call: repro.call.outputs"}
    assert r.breakdown()["device_ops"][0][0] == "fused_matmul f32[8,4096]"


def test_spans_are_clipped_to_the_window():
    ev = [T.Event(HOST, "py", "bench.window", 1 * MS, 1 * MS),
          T.Event(HOST, "py", CALL[1], 0.5 * MS, 1 * MS),
          T.Event(HOST, "py", CALL[1], 1.8 * MS, 0.4 * MS)]
    run = _compile_run(ev, calls=1)
    assert reader("call_transfer_ms.cnn")(run) == pytest.approx(0.7)


def _serve_run(events, steps):
    run = record.Run(config={}, traffic={})
    run.window_start, run.window_end = 0.0, 10.0
    run.steps = [record.Step(i, i + 0.5, ctx, 0.0, len(ctx))
                 for i, ctx in enumerate(steps)]
    run.trace = T.reduce(events)
    return run


def test_step_readers_divide_by_the_steps_that_decoded():
    ev = [T.Event(HOST, "py", "bench.window", 0, 10 * MS)]
    for s, d in ((1 * MS, 2 * MS), (4.5 * MS, 0.5 * MS)):
        ev += [T.Event(DEV, T.MODULES_LINE, "decode_body", s, d),
               T.Event(DEV, T.OPS_LINE, "%while", s, d),
               T.Event(HOST, "rt", program_spans.ENQUEUE, s, 0.02 * MS)]
    for o in (0, 4 * MS):
        ev += [T.Event(HOST, "py", "repro.step.decode", o, 1 * MS),
               T.Event(HOST, "py", "repro.step.sample", o + 1 * MS, 3 * MS)]
    # a step that only admitted, and two that decoded
    run = _serve_run(ev, [[], [5, 6], [7]])
    assert reader("step_decode_ms.serve")(run) == pytest.approx(1.0)
    assert reader("step_sample_ms.serve")(run) == pytest.approx(3.0)
    # sampling 1-4 and 5-8 ms; the device busy 1-3 and 4.5-5 ms
    assert reader("sample_idle_share.serve")(run) == pytest.approx(
        100 * (1 + 3) / 10)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_program_spans(name):
    """A program that records no spans (or a run with no trace) gives
    no value and raises nothing."""
    ev = [T.Event(HOST, "py", "bench.window", 0, 2 * MS),
          T.Event(HOST, "py", "bench.call", 0, 1 * MS),
          T.Event(DEV, T.OPS_LINE, "%fusion", 0.5 * MS, 0.1 * MS)]
    run = _serve_run(ev, [[5]])
    run.counters["traced_calls"] = 1
    assert reader(name)(run) is None
    run.trace = None
    assert reader(name)(run) is None


def test_idle_share_needs_a_device_plane():
    ev = [T.Event(HOST, "py", "bench.window", 0, 2 * MS),
          T.Event(HOST, "py", CALL[2], 0, 1 * MS)]
    assert reader("call_idle_share.cnn")(_compile_run(ev)) is None


def test_loader_drops_program_spans_under_20_us(tmp_path):
    """Written by the profiler on the CPU and read back by the loader:
    a 30 ms span is kept, an empty one (a few microseconds) is not, and
    the harness's span is kept whatever its length."""
    assert T.HOST_MIN_NS == 20e3
    T.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("repro.call.transfer"):
                time.sleep(0.03)
            with jax.profiler.TraceAnnotation("repro.call.outputs"):
                pass
            with jax.profiler.TraceAnnotation("bench.call"):
                pass
    finally:
        jax.profiler.stop_trace()
    events = T.load(T.find_xplane(str(tmp_path)))
    names = {e.name for e in events}
    assert {"bench.window", "bench.call", "repro.call.transfer"} <= names
    assert "repro.call.outputs" not in names
    run = _compile_run(events, calls=1)
    assert reader("call_transfer_ms.cnn")(run) >= 30.0
    assert program_spans.mean_ms(run.trace, "repro.call.outputs", 1) is None


def test_call_readers_on_a_traced_cpu_run(tmp_path, monkeypatch):
    """The compile path's runner, traced on the CPU at a tiny size:
    every call's spans are in the window (the loader's floor lifted, as
    spans take microseconds here), and the readers give their sums over
    the calls; no device plane, so no idle share."""
    monkeypatch.setattr(T, "HOST_MIN_NS", 0)
    cell = tiny.cell("vgg19.b1", tiny.TINY_CNN, {"pool": 2})
    run = tiny.drive(cell, seed=5, seconds=0.3, trace_dir=str(tmp_path))
    run.trace = T.reduce(T.load(T.find_xplane(str(tmp_path))))
    calls = run.counters["traced_calls"]
    assert calls == len(run.calls) > 0
    for name in CALL:
        assert len(program_spans.spans(run.trace, name)) == calls
    total = sum(e - s for s, e in program_spans.spans(
        run.trace, "repro.call.dispatch"))
    assert reader("call_dispatch_ms.cnn")(run) == pytest.approx(
        total / calls / 1e6)
    assert reader("call_idle_share.cnn")(run) is None


# --- a trace recorded on the chip: two whole calls of vgg19.b1 --------------
CHIP = os.path.join(os.path.dirname(__file__), "data",
                    "trace_vgg19_b1_spans.json")
CHIP_OLD = os.path.join(os.path.dirname(__file__), "data",
                        "trace_vgg19_b1.json")


def _chip_run(path):
    """A run of ``vgg19.b1`` over the recorded trace: its window holds
    exactly two calls, each with three dense kernels."""
    with open(os.path.join(tiny.ROOT, "bench", "configs", "vgg19.json")) as f:
        cfg = json.load(f)
    run = record.Run(config=cfg, traffic={}, batch=1)
    run.trace = T.reduce(T.load_json(path))
    run.calls = [(0.0, 1.0)] * 2
    run.counters = {"traced_calls": 2, "dense_kernels_per_call": 3}
    run.peaks = device.peaks_for("TPU v5 lite", device.load_peaks())
    return run


def test_chip_trace_call_readers():
    """Each call's transfer and dispatch last well over the loader's
    20 us and are kept; bind (10-25 us at batch 1) and outputs (a few
    us) mostly are not, and count as nothing."""
    run = _chip_run(CHIP)
    for name in CALL[1:3]:
        assert len(program_spans.spans(run.trace, name)) == 2
    assert len(program_spans.spans(run.trace, CALL[3])) == 0
    assert reader("call_transfer_ms.cnn")(run) == pytest.approx(
        (0.24509 + 0.44779) / 2)
    assert reader("call_dispatch_ms.cnn")(run) == pytest.approx(
        (0.20229 + 0.40221) / 2)
    share = reader("call_idle_share.cnn")(run)
    assert 0 <= share <= 100 * run.trace.idle_share()


def test_chip_trace_device_plane_leads_the_host_plane():
    """In the recorded trace the device's events stand about 1.1 ms
    earlier than the host's: the second call's program starts on the
    device before the harness's span of that call begins, and before
    the runtime enqueues it.  Taken as recorded, no idle gap meets a
    program span; moved by the lag read from the enqueues, the call
    path's spans hold 15.5% of the window, within its idle share."""
    ev = T.load_json(CHIP)
    calls = sorted(e.start_ns for e in ev if e.name == "bench.call")
    programs = sorted(e.start_ns for e in ev if e.line == T.MODULES_LINE)
    enqueued = sorted(e.start_ns for e in ev if e.name == "DoEnqueueProgram")
    assert programs[1] < calls[1] < enqueued[1]
    lead = [q - p for p, q in zip(programs, enqueued)]
    assert all(1.0 * MS < x < 1.3 * MS for x in lead)
    run = _chip_run(CHIP)
    lag = program_spans.device_lag_ns(run.trace)
    assert lag == pytest.approx(sum(lead) / 2)
    under = T.union(program_spans.spans(run.trace, program_spans.CALL),
                    run.trace.lo, run.trace.hi)
    as_recorded = program_spans._intersect(T.idle_gaps(
        run.trace.events, run.trace.lo, run.trace.hi), under)
    assert as_recorded / (run.trace.hi - run.trace.lo) < 1e-4
    share = reader("call_idle_share.cnn")(run)
    assert share == pytest.approx(15.469, abs=0.01)
    assert share < 100 * run.trace.idle_share()


def test_chip_trace_names_the_dense_kernel_and_keeps_its_roofline():
    """With ``pallas_call(name=...)`` the dense kernel's operations are
    ``%fused_matmul.N``, not the enclosing program's name; the
    roofline's count check still passes, and it reads as on the trace
    recorded before the kernels had names."""
    run, old = _chip_run(CHIP), _chip_run(CHIP_OLD)
    custom = [e.name for e in run.trace.events
              if 'custom_call_target="tpu_custom_call"' in e.name]
    assert len(custom) == 6
    assert all(n.startswith("%fused_matmul.") for n in custom)
    assert run.trace.breakdown()["device_ops"][0][0].startswith(
        "fused_matmul f32[8,4096]")
    now = reader("fused_matmul_roofline")(run)
    assert now is not None
    assert now == pytest.approx(reader("fused_matmul_roofline")(old),
                                abs=0.1)
