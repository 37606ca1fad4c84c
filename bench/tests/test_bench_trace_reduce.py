"""The trace reduction on a small trace recorded on a TPU v5e
(``data/trace_vgg19_b1.json``: 9 ms of ``vgg19.b1``)."""

import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_vgg19_b1.json")


@pytest.fixture(scope="module")
def events():
    return T.load_json(DATA)


def test_busy_counts_overlapping_ops_once():
    ev = [T.Event("/host:CPU", "py", "bench.window", 0, 100)] + [
        T.Event("/device:TPU:0", T.OPS_LINE, "a", s, d)
        for s, d in ((10, 20), (20, 20), (80, 40))]
    r = T.reduce(ev)
    assert r.busy_s == pytest.approx(50e-9)
    assert r.idle_share() == pytest.approx(0.5)


def test_union_merges_and_clips():
    assert T.union([(0, 2), (1, 3), (5, 6), (9, 20)], 1, 10) == [
        (1, 3), (5, 6), (9, 10)]


def test_busy_is_the_union_of_device_ops(events):
    r = T.reduce(events)
    ops = [(e.start_ns, e.end_ns) for e in events if e.line == T.OPS_LINE]
    lo, hi = r.lo, r.hi
    covered = sum(e - s for s, e in T.union(ops, lo, hi))
    assert r.busy_s == pytest.approx(covered / 1e9)
    assert r.window_s == pytest.approx(9e-3)
    assert r.idle_share() == pytest.approx(1 - r.busy_s / r.window_s)
    assert 0.2 < r.idle_share() < 0.5


def test_gaps_and_busy_fill_the_window(events):
    r = T.reduce(events)
    gaps = T.idle_gaps(events, r.lo, r.hi)
    idle = sum(b - a for a, b in gaps)
    assert idle / 1e9 + r.busy_s == pytest.approx(r.window_s)


def test_gap_attribution_names_the_covering_span():
    host = [T.Event("/host:CPU", "py", "bench.call", 0, 100),
            T.Event("/host:CPU", "rt", "H2D Dispatch", 40, 30),
            T.Event("/host:CPU", "py", "bench.step", 200, 100)]
    assert T.attribute((50, 60), host) == "bench.call: H2D Dispatch"
    assert T.attribute((210, 220), host) == "bench.step"
    assert T.attribute((150, 160), host) == "host"


def test_kernel_and_program_time(events):
    r = T.reduce(events)
    t, n = r.time_s(r'custom_call_target="tpu_custom_call"')
    assert n == 6                # three dense layers, two calls
    assert t > 0
    t_mod, n_mod = r.time_s("jit_program", line=T.MODULES_LINE)
    assert n_mod == 2 and t_mod > t


def test_breakdown_lists_at_most_ten_of_each(events):
    b = T.reduce(events).breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert 0 < len(b["idle_gaps"]) <= 10
    for name, secs in b["device_ops"] + b["idle_gaps"]:
        assert isinstance(name, str) and secs >= 0
    # the op family drops XLA's suffixes and keeps the result type
    assert T._op_family("%fusion.12 = f32[8,4]{1,0} fusion(x)") == \
        "fusion f32[8,4]{1,0}"
