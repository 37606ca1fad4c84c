"""``repro.serve`` — the serving funnel over compiled executables.

    exe = repro.compile(cfg, repro.CompileOptions(target="engine"))
    sched = repro.serve(exe, repro.SchedulerOptions(slots=8))

Takes an :class:`Executable` produced by the ``"engine"`` target (or
anything exposing ``model`` + ``params``) and returns a
:class:`repro.serve.Scheduler` — the continuous-batching step loop,
slot/KV-cache manager and per-request metrics live in
:mod:`repro.serve`; this module is only the API seam that pairs the
compiled artifact with a scheduling policy.
"""

from __future__ import annotations

from typing import Callable, Optional

from ..serve.options import SchedulerOptions
from ..serve.scheduler import Scheduler

_SERVE_HINT = (
    "repro.serve() drives framework-scale executables: compile with "
    "CompileOptions(target='engine') first (graph-IR executables are "
    "single-shot programs with no KV cache to schedule over)"
)


def _precision_report(executable) -> Optional[dict]:
    """Audit record for ``Scheduler.summary()["precision"]``: the
    compiled precision, the calibration setting, and — when the
    executable reports one — the per-site f32/bf16/int8 decision counts
    from its quantization report."""
    opts = getattr(executable, "options", None)
    prec = getattr(opts, "precision", None)
    if prec is None:
        return None
    info = {"precision": prec}
    if getattr(opts, "calibrate", None) is not None:
        info["calibrate"] = opts.calibrate
    try:
        quant = executable.cost_summary().get("quant")
    except Exception:
        quant = None
    if quant and quant.get("decisions"):
        info["decisions"] = dict(quant["decisions"])
    return info


def serve(executable, options: Optional[SchedulerOptions] = None, *,
          sampler: Optional[Callable] = None,
          clock: Optional[Callable[[], float]] = None,
          engine_worker: Optional[str] = None,
          device_source: Optional[Callable] = None,
          **kw) -> Scheduler:
    """Build a continuous-batching :class:`Scheduler` over ``executable``.

    ``executable`` must expose ``model`` (a ``models.api.Model``) and
    ``params`` — i.e. come from ``repro.compile(cfg,
    CompileOptions(target="engine"))``.  Remaining keyword args override
    ``SchedulerOptions`` fields (``repro.serve(exe, slots=8)``);
    ``sampler`` and ``clock`` are injection points for tests
    (scripted token streams sampled on the host, one read per slot and
    step; fake time).

    When the executable was compiled with ``CompileOptions(mesh=...)``
    and the scheduler options leave ``mesh`` unset, the compile-time
    mesh carries over — the serving placement follows the compiled
    artifact unless explicitly overridden.

    With ``fold`` on (the default) the scheduler takes the executable's
    weights over: the norm fold replaces each matrix in place (the
    originals are deleted, for every holder), and ``executable.params``
    becomes the folded weights.
    """
    model = getattr(executable, "model", None)
    params = getattr(executable, "params", None)
    if model is None or params is None or not hasattr(model, "decode_step"):
        raise TypeError(
            f"cannot serve {type(executable).__name__}: {_SERVE_HINT}")
    if options is None:
        options = SchedulerOptions()
    if kw:
        options = options.replace(**kw)
    if options.mesh is None:
        compiled_mesh = getattr(getattr(executable, "options", None),
                                "mesh", None)
        if compiled_mesh is not None:
            options = options.replace(mesh=compiled_mesh)
    extra = {}
    if clock is not None:
        extra["clock"] = clock
    if engine_worker is not None:
        extra["engine_worker"] = engine_worker
    if device_source is not None:
        extra["device_source"] = device_source
    info = _precision_report(executable)
    if info is not None:
        extra["precision_info"] = info
    # The scheduler takes the executable's weights: the norm fold writes
    # each folded matrix into its original's buffer, so set-up never
    # holds two copies, and the executable keeps the folded weights
    # (the same function, up to rounding).
    sched = Scheduler(model, params, options, sampler=sampler,
                      donate_params=options.fold, **extra)
    if options.fold:
        executable.params = sched.params
    return sched
