"""From a profiler trace to numbers: busy and idle time of the device,
time of named kernels and programs, and what the host was doing in
each idle gap.

A trace is reduced in two steps.  :func:`load` reads the ``.xplane.pb``
that ``jax.profiler`` wrote into a flat list of :class:`Event`; the
functions below work on such lists only, so they are checked on a
small recorded trace (``bench/tests/data``) without a chip.

* Device operations are the events of the ``XLA Ops`` line of every
  ``/device:TPU:n`` plane; programs are those of its ``XLA Modules``
  line.
* Host spans are the ``bench.*`` annotations the harness records with
  ``jax.profiler.TraceAnnotation`` around each call into a layer; the
  one named ``bench.window`` marks the measured window.  The runtime's
  own host events (transfers, dispatch, waits) of 20 us or more are
  kept too, to say what the host did in an idle gap.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
#: Runtime host events shorter than this are dropped when loading.
HOST_MIN_NS = 20e3


def start(log_dir: str) -> None:
    """Start the profiler without the Python tracer, whose per-call
    events would swamp the host (the harness's spans are kept)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event, times in nanoseconds on the trace's clock."""

    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> List[Event]:
    """The events a reduction uses: device ops and programs of every
    TPU plane, and the harness's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                out.extend(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns))
                           for e in line.events)
            elif plane.name.startswith("/host:"):
                out.extend(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns))
                           for e in line.events
                           if e.name.startswith(SPAN_PREFIX)
                           or (e.duration_ns >= HOST_MIN_NS
                               and not e.name.startswith("$")))
    return out


def load_json(path: str) -> List[Event]:
    """Events saved as a JSON list of ``[plane, line, name, start_ns,
    dur_ns]`` rows (the recorded test trace)."""
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


# ---------------------------------------------------------------------------
def window(events: Iterable[Event]) -> Tuple[float, float]:
    """Start and end of the ``bench.window`` span (the measured
    window)."""
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace holds no bench.window span")
    return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted
    disjoint intervals."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def devices(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def _ops(events: Iterable[Event], plane: str) -> List[Event]:
    return [e for e in events if e.plane == plane and e.line == OPS_LINE]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` in which an operation ran, averaged over
    the device planes of the trace."""
    planes = devices(events)
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        total += sum(e - s for s, e in union(
            ((o.start_ns, o.end_ns) for o in _ops(events, p)), lo, hi))
    return total / len(planes)


def idle_gaps(events: Sequence[Event], lo: float, hi: float,
              plane: Optional[str] = None) -> List[Tuple[float, float]]:
    """Intervals of ``[lo, hi]`` in which no operation ran on
    ``plane`` (the first device by default)."""
    planes = [plane] if plane else devices(events)[:1]
    if not planes:
        return []
    plane = planes[0]
    busy = union(((o.start_ns, o.end_ns) for o in _ops(events, plane)),
                 lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def named_time_ns(events: Iterable[Event], pattern: str, lo: float,
                  hi: float, line: str = OPS_LINE) -> Tuple[float, int]:
    """Summed device time and count of the events of ``line`` whose
    name matches ``pattern`` (a regular expression) and that start in
    ``[lo, hi)``, over every device plane."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for e in events:
        if (e.line == line and DEVICE_PLANE.match(e.plane)
                and lo <= e.start_ns < hi and rx.search(e.name)):
            total += e.dur_ns
            n += 1
    return total, n


def host_events(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    """Host events that overlap ``[lo, hi]``, the window span left
    out."""
    return [e for e in events if e.plane.startswith("/host:")
            and e.name != WINDOW_SPAN and e.end_ns > lo and e.start_ns < hi]


def _most(gap: Tuple[float, float], events: Iterable[Event]) -> str:
    best, cover = "", 0.0
    for e in events:
        c = min(gap[1], e.end_ns) - max(gap[0], e.start_ns)
        if c > cover:
            best, cover = e.name, c
    return best


def attribute(gap: Tuple[float, float], host: Sequence[Event]) -> str:
    """What the host was doing in ``gap``: the harness span that covers
    most of it (``host`` when none does: the harness between calls),
    and the runtime event that covers most of it, if any."""
    span = _most(gap, (e for e in host if e.name.startswith(SPAN_PREFIX)))
    rt = _most(gap, (e for e in host if not e.name.startswith(SPAN_PREFIX)))
    return (span or "host") + (f": {rt}" if rt else "")


def _op_family(name: str) -> str:
    """A short name for a device operation: the HLO instruction's name
    without XLA's numeric suffixes, and its result type, so that the
    instances of one operation add up."""
    m = re.match(r"^%?([\w.-]+?)(?:\.\d+)*(?:\.clone)?\s*=\s*(\S+)", name)
    if not m:
        return name[:120]
    return f"{m.group(1)} {m.group(2)}"[:120]


def breakdown(events: Sequence[Event], lo: float, hi: float,
              top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps named by what the host was doing, in seconds."""
    per: Dict[str, float] = defaultdict(float)
    planes = devices(events)
    for e in events:
        if e.line == OPS_LINE and e.plane in planes and lo <= e.start_ns < hi:
            per[_op_family(e.name)] += e.dur_ns / max(len(planes), 1)
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    host = host_events(events, lo, hi)
    gaps = sorted(idle_gaps(events, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[attribute(g, host), (g[1] - g[0]) / 1e9]
                          for g in gaps]}


@dataclasses.dataclass
class Reduction:
    """What a metric reader may take from the trace."""

    events: List[Event]
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return busy_ns(self.events, self.lo, self.hi) / 1e9

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not devices(self.events):
            return None
        return 1.0 - self.busy_s / self.window_s

    def time_s(self, pattern: str, line: str = OPS_LINE) -> Tuple[float, int]:
        """Device seconds and count of matching operations (or, with
        ``line=MODULES_LINE``, programs) in the window, summed over
        devices."""
        t, n = named_time_ns(self.events, pattern, self.lo, self.hi, line)
        return t / 1e9, n

    def breakdown(self) -> dict:
        return breakdown(self.events, self.lo, self.hi)


def reduce(events: List[Event]) -> Reduction:
    lo, hi = window(events)
    return Reduction(events, lo, hi)
