"""The program's spans, read back from a profiler trace on the CPU.

``Executable.__call__`` of a compiled graph records four leaf spans per
call (``repro.call.bind``, ``.transfer``, ``.dispatch``, ``.outputs``)
and ``Scheduler.step`` one per phase that runs (``repro.step.admit``,
``.prefill``, ``.decode``, ``.sample``).  They are
``jax.profiler.TraceAnnotation`` events on the host planes of the same
``.xplane.pb`` as the device planes, read here with the benchmark's
loader (``bench/trace_reduce.py``).  The loader keeps a runtime host
event only if it lasts at least ``HOST_MIN_NS`` (20 us); on the CPU
some spans take a few microseconds, so these tests lift that floor to
count every span.
"""

import os
import sys
from collections import Counter

import jax
import numpy as np
import pytest

import repro
from repro.configs import get_config
from repro.core import ModelBuilder
from repro.models import get_model
from repro.serve import Request, Scheduler, SchedulerOptions

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from bench import trace_reduce  # noqa: E402

CALL_SPANS = ("repro.call.bind", "repro.call.transfer",
              "repro.call.dispatch", "repro.call.outputs")
STEP_SPANS = ("repro.step.admit", "repro.step.prefill", "repro.step.decode",
              "repro.step.sample")


def _profile(tmp_path, monkeypatch, body):
    """Run ``body`` under the profiler; the host events the loader
    keeps, in start order, with no floor on their length."""
    monkeypatch.setattr(trace_reduce, "HOST_MIN_NS", 0)
    trace_reduce.start(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    events = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    return sorted(events, key=lambda e: e.start_ns)


def _named(events, prefix):
    return [e for e in events if e.name.startswith(prefix)]


def _assert_leaves(spans):
    """No program span encloses or overlaps another."""
    for a, b in zip(spans, spans[1:]):
        assert a.end_ns <= b.start_ns, (a, b)


@pytest.mark.parametrize("batch,buckets", [(2, ()), (3, (4,))],
                         ids=["exact", "padded"])
def test_each_call_records_four_leaf_spans(tmp_path, monkeypatch, batch,
                                           buckets):
    mb = ModelBuilder().seed(0)
    out = mb.dense(mb.input((32,)), 16, activation="relu")
    exe = repro.compile(mb.build([out]), repro.CompileOptions(
        target="pallas", autotune="off", batch_buckets=buckets))
    x = np.random.default_rng(0).standard_normal((batch, 32), np.float32)
    want = exe(x)                           # compiled outside the trace
    calls = 3

    def body():
        for _ in range(calls):
            got = jax.block_until_ready(exe(x))
            for k in want:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))

    spans = _named(_profile(tmp_path, monkeypatch, body), "repro.call.")
    assert Counter(e.name for e in spans) == {n: calls for n in CALL_SPANS}
    assert [e.name for e in spans[:4]] == list(CALL_SPANS)
    _assert_leaves(spans)


def test_each_phase_of_a_step_records_one_leaf_span(tmp_path, monkeypatch):
    cfg = get_config("qwen2.5-14b", smoke=True)
    model = get_model(cfg)
    sched = Scheduler(model, model.init(jax.random.PRNGKey(0)),
                      SchedulerOptions(slots=2, max_len=32, prefill_chunk=8,
                                       fold=False))
    prompt = np.arange(1, 6, dtype=np.int32)
    # compile every program outside the trace
    sched.submit(Request(uid=0, prompt=prompt, max_new_tokens=3))
    sched.run()
    sched.submit(Request(uid=1, prompt=prompt, max_new_tokens=3))

    def body():
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.step"):
                sched.step()

    events = _profile(tmp_path, monkeypatch, body)
    steps = [e for e in events if e.name == "bench.step"]
    spans = _named(events, "repro.step.")
    assert len(steps) == 2
    # step 1 admits, prefills its one chunk, decodes and samples; step 2
    # has nothing queued or prefilling, so only decodes and samples
    inside = [[e.name for e in spans
               if s.start_ns <= e.start_ns and e.end_ns <= s.end_ns]
              for s in steps]
    assert inside == [list(STEP_SPANS), ["repro.step.decode",
                                         "repro.step.sample"]]
    _assert_leaves(spans)
    assert sched.generated[1] == sched.generated[0][:len(sched.generated[1])]
