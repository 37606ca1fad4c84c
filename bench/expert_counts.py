"""The expert counters a serving program publishes, over a run's window.

A model with routed experts reports, for every decode step and every
prefill chunk, the token-choices that fell on the experts held here and
how many of those experts were hit (summed over its expert layers), as
``jax.monitoring`` scalars named
``/repro/serve/experts/<decode|chunk>/<held_choices|experts_hit>``.
:func:`listen` records them with the host's ``perf_counter`` time; the
scheduler publishes them inside ``Scheduler.step()``, so each falls in
the span of the runner's ``record.Step`` that made it (a chunk run in a
step that decoded nothing is published by the next step that does).
A program that publishes none (one before they were added) leaves the
record empty, and every reader of it then reads nothing.
"""

from __future__ import annotations

import bisect
import time
from typing import Dict, List, Optional, Tuple

PREFIX = "/repro/serve/experts/"
#: (time, counter, value), in the order published
EVENTS: List[Tuple[float, str, float]] = []
_listening = False


def _record(event: str, value, **_) -> None:
    if event.startswith(PREFIX):
        name = event[len(PREFIX):].split("/", 1)[1]
        EVENTS.append((time.perf_counter(), name, float(value)))


def listen() -> None:
    """Start recording (once per process)."""
    global _listening
    if not _listening:
        import jax.monitoring
        jax.monitoring.register_scalar_listener(_record)
        _listening = True


def window_totals(run) -> Optional[Dict[str, float]]:
    """The counters (``held_choices``, ``experts_hit``) of decode steps
    and chunks alike, summed over those published in the run's window
    steps; None when nothing was published there."""
    steps = run.window_steps()
    starts = [s.start for s in steps]
    out = {"held_choices": 0.0, "experts_hit": 0.0}
    found = False
    for t, name, value in EVENTS:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0 or t > steps[i].end:
            continue
        out[name] += value
        found = True
    return out if found else None
