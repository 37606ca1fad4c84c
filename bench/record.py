"""What one run recorded: the object every metric reader receives."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Request:
    """One served request, times on the host's ``perf_counter``."""

    uid: int
    due: float                       # when it was due to be sent
    submitted: float
    prompt_tokens: int
    max_new_tokens: int
    admitted: Optional[float] = None
    first_token: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    finished: bool = False

    def ttft(self) -> Optional[float]:
        return None if self.first_token is None else self.first_token - self.due

    def gaps(self) -> List[float]:
        t = self.token_times
        return [b - a for a, b in zip(t, t[1:])]


@dataclasses.dataclass
class Step:
    """One ``Scheduler.step()`` call."""

    start: float
    end: float
    decode_contexts: List[int]       # context of each slot it decoded
    prefill_flops: float             # prompts whose first token it gave
    tokens: int                      # tokens it produced


@dataclasses.dataclass
class Run:
    """A run's records.  Runners fill what their path has; readers
    return None where what they need is absent."""

    config: dict
    traffic: dict
    setup_s: float = 0.0
    window_start: float = 0.0
    window_end: float = 0.0
    attempted: int = 0
    failed: int = 0
    correct: bool = False
    checks: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    device_json: Dict[str, Any] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # compile path: (start, end) of every call in the window
    calls: List[tuple] = dataclasses.field(default_factory=list)
    batch: int = 0
    # serving path
    requests: List[Request] = dataclasses.field(default_factory=list)
    steps: List[Step] = dataclasses.field(default_factory=list)
    # the control's reading of the same number (bench/control.py)
    control_reading: Any = None
    # filled by bench/run.py
    trace: Any = None
    peaks: Dict[str, float] = dataclasses.field(default_factory=dict)
    device: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    def check(self, name: str, value: float, limit: float) -> bool:
        """Record a compared number beside its limit (at most the
        limit passes)."""
        ok = bool(value == value and value <= limit)
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": ok})
        return ok

    def ttfts(self) -> List[float]:
        """Time to first token of every request due in the window; one
        that never gave a token counts the time to the end of the
        run's last step."""
        end = max([self.window_end] + [s.end for s in self.steps])
        return [r.ttft() if r.ttft() is not None else end - r.due
                for r in self.requests]

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps
                if self.window_start <= s.start < self.window_end]
