"""The program's own spans in a trace: how long the host spent in each
step of a call or phase of a serving step, and how much of the device's
idle time falls inside them.

The program records them with ``jax.profiler.TraceAnnotation``, the
mechanism the harness uses for its ``bench.*`` spans, so they sit on
the host planes of the same ``.xplane.pb`` as the device planes:

* ``repro.call.bind``, ``.transfer``, ``.dispatch``, ``.outputs``: the
  four steps of ``Executable.__call__`` of a compiled graph;
* ``repro.step.admit``, ``.prefill``, ``.decode``, ``.sample``: the
  phases of ``Scheduler.step``, each present only when it runs.

Every one is a leaf: none encloses another within a call or step.
The profiler does not put the device planes on the host's clock to the
millisecond (a TPU v5e's events stand about 1.1 ms early), so the idle
share under the spans first moves the device's events by the lag read
from the trace itself: each program's start on the device against the
host's ``DoEnqueueProgram`` that launched it (:func:`device_lag_ns`).
``trace_reduce.load`` keeps a host event that is not a ``bench.`` span
only if it lasts at least ``trace_reduce.HOST_MIN_NS`` (20 us), so a
span shorter than that is invisible here and sums read low by its
time.  A program without these spans (before they were added) gives
no events, and every function below then returns None.
"""

from __future__ import annotations

import bisect
import statistics
from typing import List, Optional, Tuple

from bench import trace_reduce

CALL = "repro.call."
#: The runtime's host event that puts a program on the device's queue.
ENQUEUE = "DoEnqueueProgram"
#: Lags between a program and an enqueue further apart than this are
#: not considered; two lags this close count as one.
REACH_NS = 10e6
NEAR_NS = 50e3


def spans(trace, prefix: str) -> List[Tuple[float, float]]:
    """(start, end) of every host event of ``trace`` whose name starts
    with ``prefix`` (a full name selects one span), clipped to the
    window, in start order."""
    out = []
    for e in trace_reduce.host_events(trace.events, trace.lo, trace.hi):
        if e.name.startswith(prefix):
            out.append((max(e.start_ns, trace.lo), min(e.end_ns, trace.hi)))
    return sorted(out)


def mean_ms(trace, name: str, count: int) -> Optional[float]:
    """Summed time of the span ``name`` in the window over ``count``
    (the calls or steps it belongs to), in milliseconds."""
    found = spans(trace, name)
    if not found or count <= 0:
        return None
    return sum(e - s for s, e in found) / count / 1e6


def _intersect(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def device_lag_ns(trace) -> Optional[float]:
    """How much later the first device's events belong on the host's
    clock than the trace puts them: the lag from a program's start on
    the device (``XLA Modules``) to the host's ``DoEnqueueProgram``
    that launched it.  Every program is paired with every enqueue
    within ``REACH_NS``; the lag that the most pairs share to
    ``NEAR_NS`` is taken as the true pairing, and the median of the
    lags near it is returned.  So a program is placed where its enqueue
    began, to within the enqueue's duration (some 25 us on a v5e).
    None without at least two programs paired at one lag; the loader
    drops enqueues under 20 us, which leaves fewer pairs, not wrong
    ones."""
    planes = trace_reduce.devices(trace.events)
    if not planes:
        return None
    programs = [e.start_ns for e in trace.events
                if e.plane == planes[0] and e.line == trace_reduce.MODULES_LINE]
    enqueued = sorted(e.start_ns for e in trace.events
                      if e.plane.startswith("/host:") and e.name == ENQUEUE)
    lags = []
    for p in programs:
        i = bisect.bisect_left(enqueued, p - REACH_NS)
        j = bisect.bisect_right(enqueued, p + REACH_NS)
        lags.extend(q - p for q in enqueued[i:j])
    lags.sort()
    best, at, j = 0, 0, 0
    for i, lag in enumerate(lags):
        while lags[j] < lag - NEAR_NS:
            j += 1
        if i - j + 1 > best:
            best, at = i - j + 1, lag
    if best < 2:
        return None
    return statistics.median(x for x in lags if abs(x - at) <= NEAR_NS)


def idle_share_under(trace, prefix: str) -> Optional[float]:
    """Share of the window in which the first device ran no operation
    while the host was inside a span whose name starts with
    ``prefix``: the part of the device's idle share that those spans
    account for.  The device's idle gaps are moved onto the host's
    clock by :func:`device_lag_ns` first; None where it finds no lag."""
    if trace.window_s <= 0 or not trace_reduce.devices(trace.events):
        return None
    under = trace_reduce.union(spans(trace, prefix), trace.lo, trace.hi)
    lag = device_lag_ns(trace) if under else None
    if lag is None:
        return None
    gaps = [(s + lag, e + lag) for s, e in trace_reduce.idle_gaps(
        trace.events, trace.lo - lag, trace.hi - lag)]
    return _intersect(gaps, under) / (trace.hi - trace.lo)
