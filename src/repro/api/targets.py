"""Target registry + the graph-IR lowering targets.

A *target* is a named factory ``(graph, options) -> Executable``.  The
three built-ins mirror the paper's cast:

    "interpret"  SimpleNN semantics — node-by-node eager oracle.
    "jit"        the optimized path: pass pipeline + one specialized
                 XLA program per batch size (CompiledNN's role).
    "pallas"     same front end, dense nodes routed through the fused
                 Pallas kernel (TPU; interpret-mode on CPU).

New backends register with::

    @register_target("my-backend")
    def build(graph, options):
        return MyExecutable(graph, options)
"""

from __future__ import annotations

import functools
import hashlib
import io
import json
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.graph import Graph
from ..frontends.container import save_model
from ..core.lowering import execute_graph, lowering_fingerprint
from ..core.passes import run_pipeline
from ..core.selection import KernelChoice, select_kernels
from ..core.simple import SimpleNN
from .cache import cache_key, open_cache
from .executable import Executable, pack
from .options import QUANT_PRECISIONS, CompileOptions


def _quant_request(options: CompileOptions, *, measure: bool) -> Optional[dict]:
    """The quantization request a target rides on ``graph.quant`` for
    the quantize pass (None when ``options.precision`` is not a
    quantizing mode).  ``measure`` gates mixed-mode micro-benchmarks —
    the eager interpret target never measures; it reuses cached
    decisions so a jit-compile with the same cache dir stays the
    source of truth."""
    if options.precision not in QUANT_PRECISIONS \
            or options.precision == "f32":
        return None
    req = {"mode": options.precision,
           "calibrate": options.calibrate,
           "budget": options.precision_budget,
           "budget_ms": options.autotune_budget_ms,
           "cache_dir": options.cache_dir}
    req = {k: v for k, v in req.items() if v is not None}
    req["measure"] = measure and options.autotune != "cached"
    return req

TargetFactory = Callable[[Graph, CompileOptions], Executable]

_TARGETS: Dict[str, TargetFactory] = {}


def register_target(name: str) -> Callable[[TargetFactory], TargetFactory]:
    """Decorator: register a factory under ``name`` (overwrites)."""

    def deco(factory: TargetFactory) -> TargetFactory:
        _TARGETS[name] = factory
        return factory

    return deco


def get_target(name: str) -> TargetFactory:
    """Look up a registered target factory; KeyError lists what exists."""
    try:
        return _TARGETS[name]
    except KeyError:
        raise KeyError(
            f"unknown target {name!r}; available: {available_targets()}"
        ) from None


def available_targets() -> Tuple[str, ...]:
    """Sorted names of every registered compile target."""
    return tuple(sorted(_TARGETS))


# ---------------------------------------------------------------------------
class GraphExecutable(Executable):
    """Shared surface for graph-IR executables (source kept for
    serialization; subclasses own the actual lowering)."""

    def __init__(self, graph: Graph, options: CompileOptions) -> None:
        self.source = graph
        self.options = options
        self.signature = graph.signature()
        self.compile_time: Optional[float] = None

    def serialize(self) -> bytes:
        """Pack the source graph + options + signature into the portable
        artifact container (recompiled, not unpickled, on load)."""
        buf = io.BytesIO()
        save_model(self.source, buf)
        return pack("graph", self.options, buf.getvalue(),
                    extra={"signature": self.signature.to_dict()})

    def ensure_compiled(self, batch_size: int = 1) -> Callable:
        """Callable taking inputs positionally in signature order, with
        any per-batch specialization done up front.  Base implementation
        (eager targets) just forwards; JitExecutable overrides it with
        the AOT-compiled program."""
        return lambda *args: self(*args)

    def cache_info(self) -> dict:
        """Disk-cache counters; zeros for targets without one."""
        return {"dir": None, "hits": 0, "misses": 0}

    def _gather_inputs(self, pos, inputs) -> List[jnp.ndarray]:
        """Bind positional-or-keyword call args against the signature;
        returns arrays ordered by the graph's declared inputs."""
        return self._transfer_inputs(self._bind_inputs(pos, inputs))

    def _bind_inputs(self, pos, inputs) -> dict:
        """Bind the call args against the signature and check each
        input's shape, on the host, before any transfer."""
        inputs = self.signature.bind(pos, inputs)
        missing = [n for n in self.source.inputs if n not in inputs]
        if missing:
            raise ValueError(f"missing inputs {missing}; expected "
                             f"{list(self.source.inputs)}")
        unknown = [k for k in inputs if k not in self.source.inputs]
        if unknown:
            raise TypeError(f"unexpected inputs {unknown}; expected "
                            f"{list(self.source.inputs)}")
        for n, spec in self.source.inputs.items():
            shape = np.shape(inputs[n])
            if shape[1:] != spec.shape:
                raise ValueError(
                    f"input {n!r}: expected (batch,)+{spec.shape}, "
                    f"got {shape}")
        return inputs

    def _transfer_inputs(self, inputs: dict) -> List[jnp.ndarray]:
        """Bound inputs as device arrays, in the graph's input order."""
        return [jnp.asarray(inputs[n]) for n in self.source.inputs]

    def _public_outputs(self, out) -> dict:
        """Re-key an output dict from graph tensor names to the
        signature's public output names."""
        return {pub: out[t]
                for pub, t in zip(self.source.output_names,
                                  self.source.outputs)}


@register_target("interpret")
class InterpretExecutable(GraphExecutable):
    """The oracle as an Executable: exact, unoptimized, eager."""

    def __init__(self, graph: Graph, options: CompileOptions) -> None:
        super().__init__(graph, options)
        t0 = time.perf_counter()
        # Low-precision modes change the *semantics*, not just the
        # compilation strategy, so even the oracle honors them: run the
        # quantize pass alone (no fusion/layout — this target stays the
        # unoptimized reference) and interpret the annotated graph.
        nn_graph = graph
        self._quant_report: Optional[dict] = None
        req = _quant_request(options, measure=False)
        if req is not None:
            qg = graph.copy()
            qg.quant = req
            nn_graph, self._quant_report = run_pipeline(qg, ("quantize",))
        self._nn = SimpleNN(nn_graph)
        self.compile_time = time.perf_counter() - t0

    def __call__(self, *pos, **inputs):
        args = self._gather_inputs(pos, inputs)
        return self._public_outputs(
            self._nn(**dict(zip(self.source.inputs, args))))

    def cost_summary(self):
        """Source-graph counts only — the interpreter runs no passes
        (plus the quantization decision record under low precision)."""
        out = {
            "target": self.options.target,
            "nodes": len(self.source.nodes),
            "params": len(self.source.params),
            "param_bytes": int(sum(v.nbytes
                                   for v in self.source.params.values())),
        }
        if self._nn.graph.quant:
            out["quant"] = dict(self._nn.graph.quant)
        return out


class JitExecutable(GraphExecutable):
    """Pass pipeline + AOT-compiled XLA program per batch size, with the
    persistent on-disk executable cache.

    ``lowering_target`` names the lowering-rule registry slice to
    compile with (``"jit"`` uses only the generic rules; ``"pallas"``
    activates the Pallas-kernel overrides, gated per node by the static
    kernel selector).  ``use_pallas=True`` is the legacy spelling of
    ``lowering_target="pallas"``.
    """

    def __init__(self, graph: Graph, options: CompileOptions,
                 *, lowering_target: Optional[str] = None,
                 use_pallas: bool = False) -> None:
        super().__init__(graph, options)
        self.lowering_target = (lowering_target
                                or ("pallas" if use_pallas else "jit"))
        t0 = time.perf_counter()
        # Capture bundle (CompileOptions.capture / $REPRO_CAPTURE_DIR):
        # records the *input* graph, then tees IR dumps and selection
        # reports below.  self.capture_path is the bundle dir or None.
        from .capture import CaptureSession, resolve_capture_dir
        self.capture_path = resolve_capture_dir(
            options.capture, graph, self.lowering_target)
        self._capture = (CaptureSession(self.capture_path, graph, options,
                                        lowering_target=self.lowering_target)
                         if self.capture_path else None)
        # Graph-level decision tuning (repro.autotune.decisions): winners
        # land as tune.* attrs on a copy — self.source stays the
        # untouched input graph — and may swap the pass pipeline.  With
        # autotune="off" nothing runs and the compile is bit-identical
        # to the heuristic pipeline.
        self._decisions_report: Optional[dict] = None
        effective_graph, effective_passes = graph, options.passes
        if options.autotune != "off":
            from ..autotune import open_tactic_cache, tune_graph_decisions
            effective_graph, effective_passes, self._decisions_report = (
                tune_graph_decisions(
                    graph,
                    target=self.lowering_target,
                    precision=options.precision,
                    passes=options.passes,
                    mode=options.autotune,
                    budget_ms=options.autotune_budget_ms,
                    cache=open_tactic_cache(options.cache_dir)))
        # Low-precision request for the quantize pass: attached to a
        # copy (self.source stays the untouched input graph); the pass
        # consumes the request and leaves only the semantic record —
        # mode + quant.* node attrs — which flow into structure_hash()
        # and therefore the executable cache key for free.
        req = _quant_request(options, measure=True)
        if req is not None:
            if effective_graph is graph:
                effective_graph = graph.copy()
            effective_graph.quant = req
        dump_ir = options.dump_ir
        if self._capture is not None:
            from ..core.passes.manager import _resolve_dump_ir
            # Tee the per-pass IR into the bundle alongside any
            # user-requested sink (including $REPRO_DUMP_IR).
            dump_ir = list(_resolve_dump_ir(dump_ir)) + [self._capture.ir_dir]
        self.graph, self.report = run_pipeline(
            effective_graph, effective_passes, dump_ir=dump_ir)
        if self._capture is not None:
            self._capture.record_pipeline(self.report,
                                          self._decisions_report)
        self._pass_time = time.perf_counter() - t0
        # ensure_compiled may be entered from a BucketedExecutable's
        # background-compile worker concurrently with the request path;
        # one lock keeps the memo/compile/stat updates coherent.
        self._compile_lock = threading.RLock()
        self._fns: Dict[int, Callable] = {}
        self._selections: Dict[int, Dict[str, KernelChoice]] = {}
        self._autotune_reports: Dict[int, dict] = {}
        self._disk = open_cache(options.cache_dir)
        self._xla_cost: Optional[dict] = None
        self._weights_digest_memo: Optional[str] = None

    @property
    def use_pallas(self) -> bool:
        """True when dense ops lower through hand-written Pallas kernels."""
        return self.lowering_target == "pallas"

    # -- cache key -----------------------------------------------------
    def _weights_digest(self) -> str:
        if self._weights_digest_memo is None:
            h = hashlib.sha256()
            for k in sorted(self.graph.params):
                v = np.ascontiguousarray(self.graph.params[k])
                h.update(k.encode())
                h.update(str(v.shape).encode())
                h.update(v.tobytes())
            self._weights_digest_memo = h.hexdigest()
        return self._weights_digest_memo

    @staticmethod
    def _selection_token(selection: Dict[str, KernelChoice]) -> str:
        """Stable digest of the *resolved* kernel selection (kernel +
        block geometry per node).  Mixing this into the executable-cache
        key — instead of the autotune mode — means two compiles that
        resolve to the same kernels share one cached executable, and a
        new tactic measurement (different winner or block) misses
        cleanly instead of serving the old program."""
        payload = json.dumps(
            sorted((name, c.kernel, list(c.block) if c.block else None)
                   for name, c in selection.items()))
        return hashlib.sha256(payload.encode()).hexdigest()

    def _key(self, batch_size: int,
             selection: Optional[Dict[str, KernelChoice]] = None) -> str:
        weights = self._weights_digest() if self.options.embed_weights else ""
        return cache_key(self.graph.structure_hash(), weights,
                         self.options.cache_token(), f"batch={batch_size}",
                         f"sig={self.signature.cache_token()}",
                         f"rules={lowering_fingerprint(self.lowering_target)}",
                         f"sel={self._selection_token(selection or {})}")

    # -- sharding hooks (overridden by repro.dist.ShardedExecutable) ---
    def _lowering_extras(self) -> dict:
        """Extra ``execute_graph`` kwargs (mesh + shardings for sharded
        compiles); the unsharded base adds nothing."""
        return {}

    def _input_sharding(self, name: str, batch_size: int):
        """Sharding for the AOT input spec of graph input ``name``
        (None = let XLA choose, the unsharded default)."""
        return None

    def _wrap_compiled(self, fn: Callable, batch_size: int) -> Callable:
        """Post-compile hook around the AOT entry point (sharded
        executables re-place call arguments here)."""
        return fn

    def _execution_devices(self):
        """Devices the program is compiled for, in assignment order; a
        cached copy is loaded onto these.  Unsharded: the default
        device."""
        dev = jax.config.jax_default_device
        if dev is None or isinstance(dev, str):
            dev = jax.devices(dev)[0]
        return [dev]

    # -- compilation ---------------------------------------------------
    def _resolve_selection(self, batch_size: int, *,
                           probe: bool = False):
        """Kernel selection for one batch specialization: the static
        heuristic prior, refined by the autotuner when enabled.  With
        ``probe=True`` the autotune mode is downgraded ``"full"`` →
        ``"cached"`` so probing a cache key never spends measurement
        budget (used by :meth:`disk_key` / bucket pre-warming)."""
        selection = select_kernels(
            self.graph, batch_size=batch_size,
            target=self.lowering_target,
            precision=self.options.precision)
        report = None
        if selection and self.options.autotune != "off":
            # Profile-guided refinement: measured tactics override the
            # heuristic prior; any failure leaves the prior untouched.
            from ..autotune import open_tactic_cache, tune_selection
            mode = ("cached" if probe and self.options.autotune == "full"
                    else self.options.autotune)
            # Graph-level decision tuning already spent part of the
            # budget at construction time; kernel tactics get the rest.
            budget = self.options.autotune_budget_ms
            if budget is not None and self._decisions_report is not None:
                budget = max(
                    0.0,
                    budget - self._decisions_report.get("spent_ms", 0.0))
            selection, report = tune_selection(
                self.graph, selection,
                batch_size=batch_size,
                precision=self.options.precision,
                mode=mode,
                budget_ms=budget,
                cache=open_tactic_cache(self.options.cache_dir))
        return selection, report

    def disk_key(self, batch_size: int) -> str:
        """The persistent-cache key this batch specialization resolves
        to today (autotune measurements are never triggered: in
        ``"full"`` mode the probe sees the cached tactics only)."""
        selection, _ = self._resolve_selection(batch_size, probe=True)
        return self._key(batch_size, selection)

    def has_disk_entry(self, batch_size: int) -> bool:
        """True if the persistent on-disk cache already holds the
        executable for this batch specialization."""
        if self._disk is None:
            return False
        import os
        return os.path.exists(self._disk._path(self.disk_key(batch_size)))

    def ensure_compiled(self, batch_size: int = 1) -> Callable:
        """Compile (or fetch) the program specialized to ``batch_size``;
        returns a callable taking inputs positionally in graph order."""
        if batch_size in self._fns:
            return self._fns[batch_size]
        with self._compile_lock:
            return self._compile_batch(batch_size)

    def _compile_batch(self, batch_size: int) -> Callable:
        if batch_size in self._fns:
            return self._fns[batch_size]
        t0 = time.perf_counter()
        input_names = list(self.graph.inputs)
        params = {k: jnp.asarray(v) for k, v in self.graph.params.items()}
        # Static kernel selection for this specialization: decided from
        # shapes before tracing, honored by the target lowering rules,
        # surfaced in cost_summary().
        selection, report = self._resolve_selection(batch_size)
        if report is not None:
            self._autotune_reports[batch_size] = report
        if selection:   # targets without kernel decisions stay silent
            self._selections[batch_size] = selection
        lower_kw = dict(precision=self.options.precision,
                        target=self.lowering_target,
                        batch_size=batch_size,
                        selection=selection,
                        **self._lowering_extras())
        in_specs = [
            jax.ShapeDtypeStruct((batch_size,) + self.graph.inputs[n].shape,
                                 self.graph.inputs[n].dtype,
                                 sharding=self._input_sharding(n, batch_size))
            for n in input_names
        ]

        if self.options.embed_weights:
            def program(*args):
                env = dict(zip(input_names, args))
                return execute_graph(self.graph, env, params, **lower_kw)

            donate = (tuple(range(len(input_names)))
                      if self.options.donate_inputs else ())
            specs = in_specs
            wrap = lambda exe: exe
        else:
            def program(param_arg, *args):
                env = dict(zip(input_names, args))
                return execute_graph(self.graph, env, param_arg, **lower_kw)

            donate = (tuple(range(1, len(input_names) + 1))
                      if self.options.donate_inputs else ())
            specs = [jax.eval_shape(lambda: params)] + in_specs
            wrap = lambda exe: functools.partial(exe, params)

        jitted = jax.jit(program, donate_argnums=donate)
        key = self._key(batch_size, selection)
        exe = (self._disk.load(key, self._execution_devices())
               if self._disk else None)
        if exe is None:
            exe = jitted.lower(*specs).compile()
            if self._disk:
                self._disk.store(key, exe)
        try:
            cost = exe.cost_analysis()
            self._xla_cost = cost[0] if isinstance(cost, list) else cost
        except Exception:
            pass
        fn = self._wrap_compiled(wrap(exe), batch_size)
        self._fns[batch_size] = fn
        if self._capture is not None:
            # Record this specialization: resolved selection, autotune
            # report, and one seeded forward pass replay can diff.
            from .capture import seeded_inputs
            ins = seeded_inputs(self.graph, batch_size)
            out = fn(*[jnp.asarray(v) for v in ins.values()])
            self._capture.record_batch(
                batch_size, selection or {}, report, ins,
                {k: np.asarray(v) for k, v in out.items()})
        # Total seconds spent compiling: pass pipeline once, plus every
        # per-batch-size XLA compile so far.
        base = (self.compile_time if self.compile_time is not None
                else self._pass_time)
        self.compile_time = base + (time.perf_counter() - t0)
        return fn

    # -- execution -----------------------------------------------------
    def _pick_bucket(self, batch: int) -> int:
        for b in self.options.batch_buckets:
            if b >= batch:
                return b
        return batch

    def __call__(self, *pos, **inputs):
        # Four leaf profiler spans (none encloses another), so a trace
        # shows which step of the call the host was in.
        with TraceAnnotation("repro.call.bind"):
            inputs = self._bind_inputs(pos, inputs)
        with TraceAnnotation("repro.call.transfer"):
            args = self._transfer_inputs(inputs)
        with TraceAnnotation("repro.call.dispatch"):
            batch = args[0].shape[0]
            bucket = self._pick_bucket(batch)
            fn = self.ensure_compiled(bucket)
            if bucket != batch:
                args = [
                    jnp.concatenate(
                        [a, jnp.zeros((bucket - batch,) + a.shape[1:],
                                      a.dtype)])
                    for a in args
                ]
            out = fn(*args)
        with TraceAnnotation("repro.call.outputs"):
            if bucket != batch:
                out = {k: v[:batch] for k, v in out.items()}
            # Passes may rename output tensors (e.g. a fused terminal
            # activation); the public contract keys outputs by the
            # signature's names, identically across targets.
            return {pub: out[opt] for pub, opt in
                    zip(self.source.output_names, self.graph.outputs)}

    # -- introspection -------------------------------------------------
    def cache_info(self) -> dict:
        """Executable disk-cache counters (zeros when caching is off)."""
        if self._disk is None:
            return super().cache_info()
        return self._disk.stats()

    def cost_summary(self):
        """Compile-time facts for this executable: pass reports, memory
        plan, per-batch kernel selections, and — when tuned — the
        autotune and graph-decision reports."""
        out = {
            "target": self.options.target,
            "nodes": len(self.graph.nodes),
            "params": len(self.graph.params),
            "param_bytes": int(sum(v.nbytes
                                   for v in self.graph.params.values())),
            "pipeline": self.report.get("pipeline"),
            "passes": self.report["passes"],
            "memory_plan": self.report["memory_plan"],
        }
        if self._selections:
            # Kernel-selector decisions, per compiled batch size; each
            # entry carries source ("heuristic"|"measured"), the block
            # geometry, and — for measured tactics — per-candidate µs.
            out["kernel_selection"] = {
                batch: [c.to_dict() for c in sel.values()]
                for batch, sel in sorted(self._selections.items())
            }
        if self._autotune_reports:
            # Raw cache "entries" are a capture-bundle implementation
            # detail; the human-facing report is everything else.
            out["autotune"] = {
                batch: {k: v for k, v in rep.items() if k != "entries"}
                for batch, rep in sorted(self._autotune_reports.items())
            }
        if self._decisions_report is not None:
            # Graph-level decisions (fusion/layout/pipeline winners with
            # per-candidate µs) — see repro.autotune.decisions.
            out["graph_decisions"] = {
                k: v for k, v in self._decisions_report.items()
                if k != "entries"}
        if self.graph.quant:
            # Quantization record: mode + per-precision site counts
            # (the quantize pass's decisions, measured or prior).
            out["quant"] = dict(self.graph.quant)
        if self._xla_cost:
            out["xla"] = {k: self._xla_cost[k]
                          for k in ("flops", "bytes accessed")
                          if k in self._xla_cost}
        return out


@register_target("jit")
def _build_jit(graph: Graph, options: CompileOptions) -> Executable:
    return JitExecutable(graph, options, lowering_target="jit")


@register_target("pallas")
def _build_pallas(graph: Graph, options: CompileOptions) -> Executable:
    return JitExecutable(graph, options, lowering_target="pallas")
