"""repro.serve — continuous-batching serving over compiled executables.

    import repro
    from repro.serve import Request

    exe = repro.compile(cfg, repro.CompileOptions(target="engine"))
    sched = repro.serve(exe, repro.SchedulerOptions(slots=8))
    sched.submit(Request(uid=0, prompt=toks))
    completions = sched.run()
    print(sched.summary())

One scheduler (`Scheduler`), one options object (`SchedulerOptions`),
per-request metrics (`RequestMetrics`), and a slot/KV-cache manager
(`SlotManager`); the cache's placement on a mesh is owned by
:class:`repro.dist.placement.Placement`.

The module itself is callable — ``repro.serve(executable, options)``
delegates to :func:`repro.api.serve.serve` — so the package namespace
(``repro.serve.Scheduler``) and the API entry point share one name.
"""

import sys as _sys
import types as _types

from .metrics import RequestMetrics, SchedulerMetrics
from .options import ADMISSION_POLICIES, SchedulerOptions
from .prefix import PrefixCache
from .scheduler import (Completion, QueueFullError, Request, Scheduler,
                        sample_rows)
from .slots import SlotManager, SlotState

__all__ = [
    "ADMISSION_POLICIES",
    "Completion",
    "PrefixCache",
    "QueueFullError",
    "Request",
    "RequestMetrics",
    "Scheduler",
    "SchedulerMetrics",
    "SchedulerOptions",
    "SlotManager",
    "SlotState",
    "sample_rows",
]


class _CallableServeModule(_types.ModuleType):
    def __call__(self, executable, options=None, **kw):
        from ..api.serve import serve as _serve   # lazy: avoids a cycle
        return _serve(executable, options, **kw)


_sys.modules[__name__].__class__ = _CallableServeModule
