"""Continuous-batching request scheduler over a compiled model.

The step loop rebatches every decode step:

    1. admit — while capacity is free and requests wait, pick one (FCFS,
       shortest-prompt, or earliest-deadline), prefill it — whole-prompt
       at admission, or one ``prefill_chunk`` per step interleaved with
       decode so long prompts never block in-flight decodes — splice its
       cache row into the batched cache and sample its first token;
    2. decode — ONE batched decode step advances every active slot.
       Every per-bucket decode program takes the FULL batched cache with
       ``donate_argnums``: the KV write-back happens inside the compiled
       program on the donated buffer, so steady-state decode performs no
       new device allocations (the framework-scale version of the
       paper's in-place memory planning);
    3. sample + evict — the decode program samples every row's token
       itself (:func:`sample_rows`), so the host reads one ``(slots,)``
       vector a step; EOS / length retirement frees slots for the next
       iteration's admissions.

Requests whose prompts share a common head (the "system prompt"
scenario) prefill that head once: with ``prefix_cache`` enabled the
scheduler snapshots the head's KV rows at a chunk boundary and later
requests splice a copy, prefilling only their tail — bit-identical to
unshared prefill (see :mod:`repro.serve.prefix`).

``submit`` is thread-safe and non-blocking, so a producer can feed the
queue while another thread (or an asyncio executor) drives ``step`` /
``run`` — the scheduler itself never blocks waiting for requests.

Per-request metrics (TTFT, decode tok/s, queue depth at submit,
deadline/SLO outcome) and aggregate counters (batch occupancy, total
throughput, ``slo_violations``) come from an injected clock, so tests
assert exact numbers.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..dist.placement import Placement
from .metrics import RequestMetrics, SchedulerMetrics
from .options import SchedulerOptions
from .prefix import PrefixCache, common_prefix_len
from .slots import SlotManager, SlotState


@dataclasses.dataclass
class Request:
    """One generation request: prompt tokens, budget, sampling knobs,
    optional extra model inputs and an optional first-token SLO."""

    uid: int
    prompt: np.ndarray            # (s,) int32
    max_new_tokens: int = 32
    eos_id: int = -1              # -1 = never
    temperature: float = 0.0      # 0 = greedy
    #: Additional named model inputs consumed at prefill (the model
    #: signature's non-token inputs: ``frames`` for audio families,
    #: ``patches`` for VLMs).  Arrays may carry the leading batch dim
    #: (of 1) or omit it.  Missing extras are zero-filled; names the
    #: model does not declare are rejected at ``submit``.
    inputs: Optional[Dict[str, np.ndarray]] = None
    #: First-token SLO in milliseconds (relative to submit).  Sets the
    #: request's absolute deadline on the scheduler clock; the
    #: ``"deadline"`` admission policy schedules earliest-deadline-first
    #: and ``summary()`` counts ``slo_violations``.  None = no SLO.
    slo_ms: Optional[float] = None


@dataclasses.dataclass
class _PrefillTask:
    """One in-flight chunked prefill: a request whose prompt is being
    fed through the chunk program ``prefill_chunk`` tokens per step.
    Counts against slot capacity so a free slot is guaranteed when the
    final chunk lands and the task activates."""

    req: Request
    prompt: np.ndarray                    # (plen,) int32
    cache: Any                            # single-row cache, filled in place
    offset: int = 0                       # tokens prefilled so far
    logits: Any = None                    # last-token logits, latest chunk
    snapshot_at: Optional[int] = None     # chunk boundary to snapshot
    snapshot_key: Optional[bytes] = None  # pending PrefixCache key


@dataclasses.dataclass
class Completion:
    """Finished request: generated tokens and why generation stopped."""

    uid: int
    tokens: List[int]
    finish_reason: str = "length"   # "eos" | "length"


class QueueFullError(RuntimeError):
    """Raised by ``submit`` when ``SchedulerOptions.max_queue`` is hit."""


def sample_rows(logits: jnp.ndarray, temperature: jnp.ndarray,
                key: jax.Array) -> jnp.ndarray:
    """One token per row of ``logits`` (B, vocab), as (B,) int32: the
    argmax where the row's ``temperature`` (B,) is 0 or less, else a
    categorical draw from ``logits / temperature`` under the row's own
    key, split from ``key``.  Traced into the decode program; the draw
    runs only when some row has a temperature above 0, so an all-greedy
    step pays the argmax alone."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    hot = temperature > 0

    def draw():
        keys = jax.random.split(key, logits.shape[0])
        scale = jnp.where(hot, temperature, 1.0).astype(jnp.float32)
        drawn = jax.vmap(jax.random.categorical)(
            keys, logits.astype(jnp.float32) / scale[:, None])
        return jnp.where(hot, drawn.astype(jnp.int32), greedy)

    return jax.lax.cond(jnp.any(hot), draw, lambda: greedy)


class Scheduler:
    """Drive a compiled model under concurrent multi-request load.

    Tokens are sampled inside the decode program (:func:`sample_rows`,
    keyed by ``options.seed`` and a draw count).  ``sampler`` is a seam
    for tests: a host callable ``sampler(logits, temperature, *, uid,
    index) -> int``, with ``logits`` of shape (1, vocab) and ``index``
    the number of tokens the request already has, that scripts tokens
    per request.  Given one, the decode program returns its logits and
    the host calls the sampler once per active slot, each call a
    blocking read: slow by construction, and kept for tests only.
    """

    def __init__(self, model, params, options: SchedulerOptions, *,
                 sampler: Optional[Callable] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 engine_worker: str = "thread",
                 device_source: Optional[Callable] = None,
                 precision_info: Optional[dict] = None,
                 donate_params: bool = False) -> None:
        self.model = model
        self.cfg = model.cfg
        self.options = options
        # Audit record from the compiled executable (repro.serve fills
        # it in): active precision + per-site decision counts, surfaced
        # verbatim in summary()["precision"].
        self.precision_info = dict(precision_info) if precision_info \
            else None
        if options.fold:
            # ``donate_params``: the caller hands its weights over, and
            # each folded matrix takes its original's buffer.
            from ..inference.fold_norms import fold_norms
            params, self.fold_report = fold_norms(self.cfg, params,
                                                  donate=donate_params)
        else:
            self.fold_report = {"folds": 0}
        self.params = params
        self.sampler = sampler      # None: the programs sample
        self.clock = clock

        # data×model-parallel serving (repro.dist): the placement binds
        # the mesh before any program builds, so placements are committed
        # up front.  ``device_source`` is the fault-injection seam: tests
        # shrink the visible device set and the step loop raises a typed
        # MeshUnavailableError (recorded in ``summary()["faults"]``).
        self.placement = Placement.of(options.mesh,
                                      device_source or jax.devices)
        self.params = self.placement.put(self.params)
        self.slot_manager = SlotManager(model, options.slots,
                                        options.max_len,
                                        placement=self.placement)
        self._lock = threading.Lock()
        self._queue: List[Request] = []
        self.done: List[Completion] = []
        self._pending: List[Completion] = []  # finished, not yet popped
        self.generated: Dict[int, List[int]] = {}
        self.request_metrics: Dict[int, RequestMetrics] = {}
        self.metrics = SchedulerMetrics()
        self.last_token = np.zeros((options.slots, 1), np.int32)
        self.temperatures = np.zeros((options.slots,), np.float32)
        # sampling programs run so far: each one's key is the seed's
        # folded with its count, so no key makes a round trip
        self._draws = 0
        self._sampling = {"program_steps": 0, "host_steps": 0,
                          "token_reads": 0}

        # Expert counters (models with routed experts): the decode and
        # chunk programs return them as a small third output, read only
        # after the step's logits were (see _flush_counts).
        self._stats = {"stats": True} if getattr(
            model, "counts_experts", False) else {}
        self._pending_counts: List[tuple] = []
        self._expert_counts = {
            kind: {"calls": 0, "held_choices": 0, "experts_hit": 0}
            for kind in ("decode", "chunk")}

        # What every decode program is lowered against: the weights, the
        # batched cache and the per-step inputs (see _step_inputs), each
        # as the placement holds it.
        n = options.slots
        self._decode_specs = (
            self.placement.specs(self.params),
            self.placement.specs(self.slot_manager.cache, cache=True),
            *self.placement.specs((
                jax.ShapeDtypeStruct((n, 1), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.uint32))))
        # Padded (length-bucketed) and chunked prefill need a cache that
        # carries ``pos`` and holds ``max_len`` positions: a ring cache
        # (all-sliding-window models) holds the window, and padding
        # would roll real tokens out of it.
        self._row_cache = jax.eval_shape(
            lambda: model.init_cache(1, options.max_len))
        longer = jax.eval_shape(
            lambda: model.init_cache(1, options.max_len + 1))
        self._positional = (
            isinstance(self._row_cache, dict) and "pos" in self._row_cache
            and any(x.shape != y.shape for x, y in zip(
                jax.tree.leaves(self._row_cache), jax.tree.leaves(longer))))

        self._prefill = jax.jit(lambda p, b, c: model.prefill(p, b, c))
        self._sample_first = jax.jit(self._sample)

        # shape-polymorphic serving (repro.runtime): warm programs per
        # bucket, background compiles.  Without buckets, one decode
        # program over every slot, compiled here (set-up, not latency).
        self._decode = None
        self._decode_engine = None
        self._prefill_engine = None
        # chunked prefill + shared-prefix snapshots (both optional)
        self._chunk_engine = None
        self._prefix_cache: Optional[PrefixCache] = None
        self._prefilling: List[_PrefillTask] = []
        if options.prefill_chunk is not None:
            self._init_chunking(engine_worker)
        if options.buckets is not None:
            self._init_bucketing(engine_worker)
        else:
            self._decode = self._build_decode(options.slots)

    # -- sampling --------------------------------------------------------
    def _sample(self, logits, temps, n):
        """Traced: :func:`sample_rows` under the key of draw ``n``."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.options.seed), n)
        return sample_rows(logits, temps, key)

    def _head(self, logits, temps, n):
        """What a decode program returns for its rows' logits (B,
        vocab): their tokens, or the logits when a host sampler was
        handed in."""
        if self.sampler is not None:
            return logits
        return self._sample(logits, temps, n)

    def _next_draw(self) -> np.uint32:
        """The index of the next sampling program's draw."""
        n = self._draws
        self._draws += 1
        return np.uint32(n & 0xFFFFFFFF)

    # -- programs ------------------------------------------------------
    def _build_decode(self, rows: int):
        """Compile the decode program that advances the first ``rows``
        slots (all of them on the fixed path; a batch bucket's rows on
        the bucketed one).

        Every decode program takes (and donates) the FULL batched cache
        and returns it first: donation gives each donated leaf the first
        output of its shape, and a (slots,) int32 token vector ahead of
        the cache would take the position vector's buffer.  Below
        ``slots`` the row slice, the decode step and the KV write-back
        all happen inside the one program, so the donated buffer is
        updated in place: no per-step slice or write-back allocations
        (the pre-allocated step-buffer discipline of the paper's memory
        planner).  Every program is the XLA module ``decode_body``."""
        model, place, stats = self.model, self.placement, self._stats
        slots = self.options.slots

        def decode_body(p, c, t, temps, n):
            c = place.view(c)
            if rows == slots:
                logits, c, *counts = model.decode_step(p, c, t, **stats)
            else:
                sub = jax.tree.map(
                    lambda l: l[:rows] if l.ndim == 1 else l[:, :rows], c)
                logits, sub, *counts = model.decode_step(
                    p, sub, t[:rows], **stats)
                axis = lambda l: 0 if l.ndim == 1 else 1
                c = jax.tree.map(
                    lambda f, s: jax.lax.dynamic_update_slice_in_dim(
                        f, s, 0, axis=axis(f)), c, sub)
                temps = temps[:rows]
            return (place.constrain(c),
                    self._head(logits[:, 0], temps, n), *counts)

        return jax.jit(decode_body, donate_argnums=(1,)).lower(
            *self._decode_specs).compile()

    def _step_inputs(self) -> tuple:
        """The decode program's per-step inputs: the last-token batch,
        the slots' temperatures and the draw index, in one transfer,
        placed as the program's lowering committed to (replicated under
        a mesh)."""
        return self.placement.put(
            (self.last_token, self.temperatures, self._next_draw()))

    def _init_bucketing(self, worker: str) -> None:
        from ..runtime.buckets import Bucket, BucketPolicy
        from ..runtime.engine_cache import EngineCache
        opts = self.options
        policy = opts.buckets.clip(max_batch=opts.slots,
                                   max_len=opts.max_len)
        self._decode_engine = EngineCache(
            BucketPolicy(batch_buckets=policy.batch_buckets),
            lambda bucket: self._build_decode(min(bucket.batch, opts.slots)),
            worker=worker, clock=self.clock)
        # the full-slots program covers every batch, so compiling it
        # synchronously here (load time, not latency) guarantees the
        # decode path never stalls; smaller buckets fill in behind it
        self._decode_engine.warm_up([Bucket(opts.slots)], block=True)
        self._decode_engine.warm_up(block=False)

        # chunked prefill supersedes padded whole-prompt prefill: every
        # prompt runs through the (single-bucket) chunk program instead
        if (not (policy.len_buckets and self._positional)
                or self._chunk_engine is not None):
            return
        place, p_spec = self.placement, self._decode_specs[0]

        def build_prefill(bucket):
            from ..configs.base import extra_input_specs
            b_spec = {"tokens": jax.ShapeDtypeStruct((1, bucket.length),
                                                     jnp.int32)}
            for name, (shape, dt) in extra_input_specs(self.cfg).items():
                b_spec[name] = jax.ShapeDtypeStruct(shape, dt)
            fn = jax.jit(self._prefill_fixup)
            return fn.lower(p_spec, *place.specs((
                b_spec, self._row_cache,
                jax.ShapeDtypeStruct((), jnp.int32)))).compile()

        self._prefill_engine = EngineCache(
            BucketPolicy(batch_buckets=(1,),
                         len_buckets=policy.len_buckets),
            build_prefill, worker=worker, clock=self.clock)
        # largest length bucket first: it covers every admissible
        # prompt, so fallback coverage arrives as early as possible
        self._prefill_engine.warm_up(
            tuple(reversed(self._prefill_engine.policy.enumerate_buckets())))

    def _init_chunking(self, worker: str) -> None:
        """Build the chunk-prefill program — one PR-6 length bucket of
        exactly ``prefill_chunk`` tokens, compiled synchronously at
        construction so the request path never stalls on it — plus the
        optional shared-prefix snapshot cache.

        Families without incremental prefill keep whole-prompt prefill
        silently (vlm/audio extra inputs, ring caches whose capacity is
        the window, not ``max_len``) — surfaced in
        ``summary()["chunked_prefill"]["enabled"]``.
        """
        from ..runtime.buckets import BucketPolicy
        from ..runtime.engine_cache import EngineCache
        opts = self.options
        supports = getattr(self.model, "supports_chunked_prefill", None)
        if not (supports is not None and supports() and self._positional):
            return
        place, p_spec = self.placement, self._decode_specs[0]

        def build_chunk(bucket):
            t_spec, c_spec, s_spec = place.specs((
                jax.ShapeDtypeStruct((1, bucket.length), jnp.int32),
                self._row_cache, jax.ShapeDtypeStruct((), jnp.int32)))
            # the single-row cache is donated: each chunk fills it in
            # place (PrefixCache copies before/after, never aliases it)
            fn = jax.jit(
                lambda p, t, c, s, n: self.model.prefill_chunk(
                    p, t, c, s, n, **self._stats),
                donate_argnums=(2,))
            return fn.lower(p_spec, t_spec, c_spec, s_spec, s_spec).compile()

        self._chunk_engine = EngineCache(
            BucketPolicy(batch_buckets=(1,),
                         len_buckets=(opts.prefill_chunk,)),
            build_chunk, worker=worker, clock=self.clock)
        self._chunk_engine.warm_up(block=True)
        if opts.prefix_cache > 0:
            self._prefix_cache = PrefixCache(opts.prefix_cache)

    def _prefill_fixup(self, p, batch, cache, length):
        """Prefill padded to the bucket, then recover the exact-length
        result: the pad positions' K/V entries are causally downstream
        of the real tokens, so after rewinding ``pos`` to the last real
        token and re-decoding it, the logits and every cache position
        the model can still attend to are bit-identical to an
        exact-length prefill.  ``length`` is traced, so ONE compiled
        program serves every prompt length up to the bucket."""
        _, cache = self.model.prefill(p, batch, cache)
        cache = dict(cache)
        cache["pos"] = jnp.full_like(cache["pos"], length - 1)
        last = jax.lax.dynamic_slice_in_dim(batch["tokens"], length - 1, 1,
                                            axis=1)
        return self.model.decode_step(p, cache, last)

    def wait_warm(self, timeout: float = 120.0) -> bool:
        """Block until every scheduled background compile has landed
        (True) or the timeout expires.  No-op without bucketing."""
        ok = True
        for eng in (self._decode_engine, self._prefill_engine,
                    self._chunk_engine):
            if eng is not None:
                ok = eng.wait_warm(timeout) and ok
        return ok

    def shutdown(self) -> None:
        """Stop the background compile workers (daemon threads — safe
        to skip, but tests join them for determinism)."""
        for eng in (self._decode_engine, self._prefill_engine,
                    self._chunk_engine):
            if eng is not None:
                eng.shutdown()

    # -- queue ---------------------------------------------------------
    def submit(self, req: Request) -> RequestMetrics:
        """Enqueue a request (thread-safe, non-blocking).

        Raises :class:`QueueFullError` under admission control and
        ``ValueError`` if the prompt alone exceeds ``max_len``.
        """
        plen = int(np.asarray(req.prompt).shape[-1])
        if plen >= self.options.max_len:
            raise ValueError(
                f"prompt of {plen} tokens does not fit max_len="
                f"{self.options.max_len} (uid={req.uid})")
        if req.inputs:
            from ..configs.base import extra_input_specs
            allowed = extra_input_specs(self.cfg)
            unknown = sorted(set(req.inputs) - set(allowed))
            if unknown:
                raise ValueError(
                    f"unknown inputs {unknown} for {self.cfg.name!r} "
                    f"(family {self.cfg.family!r}); accepted extras: "
                    f"{sorted(allowed) or 'none'} (uid={req.uid})")
            # Shapes are rejected HERE, not at admission: by admission
            # time the request is out of the queue and a raise would
            # kill the step loop with other requests in flight.
            for name, a in req.inputs.items():
                shape = allowed[name][0]
                got = tuple(np.asarray(a).shape)
                if got not in (shape, shape[1:]):
                    raise ValueError(
                        f"input {name!r}: expected {shape} (or the "
                        f"batch-less {shape[1:]}), got {got} "
                        f"(uid={req.uid})")
        with self._lock:
            if (self.options.max_queue is not None
                    and len(self._queue) >= self.options.max_queue):
                self.metrics.rejected += 1
                raise QueueFullError(
                    f"queue full ({self.options.max_queue}); "
                    f"rejecting uid={req.uid}")
            if req.uid in self.request_metrics:
                raise ValueError(f"duplicate request uid={req.uid}")
            depth = len(self._queue)
            self._queue.append(req)
            self.metrics.submitted += 1
            self.metrics.peak_queue_depth = max(
                self.metrics.peak_queue_depth, len(self._queue))
            m = RequestMetrics(uid=req.uid, prompt_tokens=plen,
                               submitted_at=self.clock(),
                               queue_depth_at_submit=depth)
            if req.slo_ms is not None:
                m.deadline = m.submitted_at + req.slo_ms / 1e3
            self.request_metrics[req.uid] = m
            return m

    def queue_depth(self) -> int:
        """Requests waiting for admission (thread-safe snapshot)."""
        with self._lock:
            return len(self._queue)

    def num_active(self) -> int:
        """Slots currently generating."""
        return self.slot_manager.num_active()

    def _blocked(self, req: Request) -> bool:
        """True while an in-flight prefill is about to snapshot a head
        this request's prompt starts with: admitting it NOW would
        re-prefill the shared head; waiting the few steps until the
        snapshot lands turns it into a prefix hit."""
        if self._prefix_cache is None:
            return False
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        for t in self._prefilling:
            h = t.snapshot_at
            if (t.snapshot_key is not None and h is not None
                    and h < len(prompt)
                    and prompt[:h].tobytes() == t.snapshot_key):
                return True
        return False

    def _pop_next(self) -> Optional[Request]:
        with self._lock:
            if not self._queue:
                return None
            cand = range(len(self._queue))
            if self._prefix_cache is not None and self._prefilling:
                cand = [j for j in cand
                        if not self._blocked(self._queue[j])]
                if not cand:
                    return None    # every waiter gains by waiting
            if self.options.admission == "shortest":
                i = min(cand,
                        key=lambda j: (len(self._queue[j].prompt), j))
            elif self.options.admission == "deadline":
                # earliest deadline first; no-SLO requests after every
                # deadline, FCFS among themselves
                def urgency(j):
                    d = self.request_metrics[self._queue[j].uid].deadline
                    return (0, d, j) if d is not None else (1, 0.0, j)
                i = min(cand, key=urgency)
            else:                                   # fcfs
                i = next(iter(cand))
            return self._queue.pop(i)

    # -- admission -----------------------------------------------------
    def _prefill_batch(self, prompt: np.ndarray,
                       extras: Optional[Dict[str, np.ndarray]] = None
                       ) -> Dict[str, jnp.ndarray]:
        """The named multi-input prefill batch: tokens plus the model
        signature's extra inputs — request-supplied where given
        (batch dim added if omitted), zero-filled otherwise."""
        from ..configs.base import extra_input_specs
        batch = {"tokens": jnp.asarray(prompt)}
        extras = extras or {}
        for name, (shape, dtype) in extra_input_specs(self.cfg).items():
            if name in extras:
                a = jnp.asarray(extras[name], dtype)
                if a.ndim == len(shape) - 1:
                    a = a[None]
                if a.shape != shape:
                    raise ValueError(
                        f"input {name!r}: expected {shape} "
                        f"(or the batch-less {shape[1:]}), got {a.shape}")
                batch[name] = a
            else:
                batch[name] = jnp.zeros(shape, dtype)
        return batch

    def _admit_free_slots(self) -> None:
        if self._chunk_engine is not None:
            self._admit_chunked()
            return
        for slot in self.slot_manager.free_slots():
            req = self._pop_next()
            if req is None:
                return
            m = self.request_metrics[req.uid]
            m.admitted_at = self.clock()
            self.metrics.admitted += 1
            if self.metrics.started_at is None:
                self.metrics.started_at = m.admitted_at

            prompt = np.asarray(req.prompt, np.int32)[None, :]
            one = self.model.init_cache(1, self.options.max_len)
            if self._prefill_engine is not None:
                plen = prompt.shape[1]
                entry, bucket, _ = self._prefill_engine.get(1, plen)
                padded = np.zeros((1, bucket.length), np.int32)
                padded[:, :plen] = prompt
                logits, one = entry(
                    self.params, self._prefill_batch(padded, req.inputs),
                    one, jnp.int32(plen))
            else:
                logits, one = self._prefill(
                    self.params, self._prefill_batch(prompt, req.inputs),
                    one)
            self._activate(slot, req, logits[:, -1], one)

    def _admit_chunked(self) -> None:
        """Chunked admission: a popped request becomes a
        :class:`_PrefillTask` (advanced one chunk per step) instead of
        being prefilled inline.  Tasks count against slot capacity so a
        slot is free when each one completes; shared heads are taken
        from / planned into the prefix cache here."""
        opts = self.options
        while (len(self._prefilling) + self.slot_manager.num_active()
               < opts.slots):
            req = self._pop_next()
            if req is None:
                return
            m = self.request_metrics[req.uid]
            m.admitted_at = self.clock()
            self.metrics.admitted += 1
            if self.metrics.started_at is None:
                self.metrics.started_at = m.admitted_at
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            task = _PrefillTask(req=req, prompt=prompt, cache=None)
            if self._prefix_cache is not None:
                hit = self._prefix_cache.take(prompt)
                if hit is not None:
                    task.offset, task.cache = hit
            if task.cache is None:
                task.cache = self.model.init_cache(1, opts.max_len)
                if self._prefix_cache is not None:
                    self._plan_snapshot(task)
            self._prefilling.append(task)

    def _plan_snapshot(self, task: _PrefillTask) -> None:
        """On a prefix miss: if waiting prompts share a head with this
        one, mark the chunk boundary where this prefill should snapshot
        it (the head is then prefilled ONCE; sharers block in the queue
        until the snapshot lands and take a copy)."""
        chunk = self.options.prefill_chunk
        plen = len(task.prompt)
        with self._lock:
            queued = [np.asarray(r.prompt, np.int32).reshape(-1)
                      for r in self._queue]
        lcp = max((common_prefix_len(task.prompt, p) for p in queued),
                  default=0)
        head = (min(lcp, plen - 1) // chunk) * chunk
        if head < max(chunk, self.options.min_prefix):
            return
        key = PrefixCache.key_for(task.prompt[:head])
        if key in self._prefix_cache or any(
                t.snapshot_key == key for t in self._prefilling):
            return
        task.snapshot_at = head
        task.snapshot_key = key

    def _advance_prefills(self) -> None:
        """Advance every in-flight chunked prefill by ONE chunk — the
        interleaving that keeps long prompts from blocking decodes —
        then activate tasks whose prompt is complete."""
        if not self._prefilling:
            return
        chunk_len = self.options.prefill_chunk
        finished = []
        for task in self._prefilling:
            n = min(chunk_len, len(task.prompt) - task.offset)
            chunk = np.zeros((1, chunk_len), np.int32)
            chunk[0, :n] = task.prompt[task.offset:task.offset + n]
            entry, _, _ = self._chunk_engine.get(1, n)
            task.logits, task.cache, *counts = entry(
                self.params, jnp.asarray(chunk), task.cache,
                jnp.int32(task.offset), jnp.int32(n))
            self._note_counts("chunk", counts)
            task.offset += n
            self.metrics.prefill_chunks += 1
            if (task.snapshot_key is not None
                    and task.offset == task.snapshot_at):
                self._prefix_cache.insert(task.snapshot_key, task.offset,
                                          task.cache)
                task.snapshot_key = None
                task.snapshot_at = None
            if task.offset >= len(task.prompt):
                finished.append(task)
        for task in finished:
            self._prefilling.remove(task)
            slot = self.slot_manager.free_slots()[0]
            self._activate(slot, task.req, task.logits[:, 0], task.cache)

    def _activate(self, slot: int, req: Request, logits: jnp.ndarray,
                  one_cache: Any) -> None:
        """Prefill is done: sample the first token from its (1, vocab)
        logits (one program and one read; the host sampler on its
        path), splice the single-row cache into ``slot`` and record
        first-token metrics (including the SLO verdict)."""
        if self.sampler is None:
            tok = int(self._sample_first(
                logits, np.full((1,), req.temperature, np.float32),
                self._next_draw())[0])
        else:
            tok = self.sampler(logits, req.temperature, uid=req.uid,
                               index=0)
        # clamp so prompt + generated tokens can never outrun the
        # per-slot cache capacity
        plen = int(np.asarray(req.prompt).shape[-1])
        budget = self.options.max_len - plen
        self.slot_manager.admit(slot, SlotState(
            uid=req.uid,
            remaining=min(req.max_new_tokens, budget) - 1,
            eos_id=req.eos_id,
            temperature=req.temperature), one_cache)
        self.last_token[slot, 0] = tok
        self.temperatures[slot] = req.temperature
        self.generated[req.uid] = [tok]
        m = self.request_metrics[req.uid]
        m.first_token_at = self.clock()
        m.new_tokens = 1
        self.metrics.total_new_tokens += 1
        if m.deadline is not None:
            m.slo_violated = bool(m.first_token_at > m.deadline)
            if m.slo_violated:
                self.metrics.slo_violations += 1
        if tok == req.eos_id or min(req.max_new_tokens, budget) <= 1:
            self._retire(slot, "eos" if tok == req.eos_id else "length")

    # -- retirement ----------------------------------------------------
    def _retire(self, slot: int, reason: str) -> None:
        st = self.slot_manager.evict(slot)
        self.temperatures[slot] = 0.0    # a free row draws nothing
        m = self.request_metrics[st.uid]
        m.finished_at = self.clock()
        m.finish_reason = reason
        self.metrics.completed += 1
        c = Completion(st.uid, self.generated[st.uid],
                       finish_reason=reason)
        self.done.append(c)
        self._pending.append(c)

    # -- bucketed decode -----------------------------------------------
    def _bucket_program(self, k: int):
        """The best warm batch bucket's decode program for ``k`` active
        slots, and the active slots after compacting them into rows
        ``[0, k)``: the program slices those rows out of the FULL
        donated cache and writes them back in place (bit-identical per
        row to decoding at the full slot count, minus the work for the
        empty rows; the returned tokens, or logits, cover the bucket's
        rows)."""
        for src, dst in self.slot_manager.compact():
            self.last_token[dst, 0] = self.last_token[src, 0]
            self.temperatures[dst] = self.temperatures[src]
            self.temperatures[src] = 0.0
        entry, _, _ = self._decode_engine.get(k)
        return entry, self.slot_manager.active_slots()

    # -- expert counters -----------------------------------------------
    def _note_counts(self, kind: str, counts: List[Any]) -> None:
        """Keep a program's expert counters (none for a model without
        experts), their copy to the host started."""
        for c in counts:
            c.copy_to_host_async()
            self._pending_counts.append((kind, c))

    def _flush_counts(self) -> None:
        """Add the kept counters to the totals and publish each call's
        as ``jax.monitoring`` scalars
        (``/repro/serve/experts/<decode|chunk>/<held_choices|experts_hit>``).
        Called once the step's tokens were read, when the programs that
        made the counters have finished and their copies landed."""
        for kind, c in self._pending_counts:
            held, hit = (int(v) for v in np.asarray(c))
            tot = self._expert_counts[kind]
            tot["calls"] += 1
            tot["held_choices"] += held
            tot["experts_hit"] += hit
            jax.monitoring.record_scalar(
                f"/repro/serve/experts/{kind}/held_choices", held)
            jax.monitoring.record_scalar(
                f"/repro/serve/experts/{kind}/experts_hit", hit)
        self._pending_counts = []

    def _read_tokens(self, out: jnp.ndarray, active: List[int]) -> List[int]:
        """The step's token for each active slot, in ``active``'s order.
        ``out`` is the decode program's (B,) tokens, read in one
        transfer; on the host-sampler path its (B, vocab) logits, one
        sampler call (and read) per slot."""
        if self.sampler is None:
            self._sampling["program_steps"] += 1
            self._sampling["token_reads"] += 1
            toks = np.asarray(out)
            return [int(toks[slot]) for slot in active]
        self._sampling["host_steps"] += 1
        self._sampling["token_reads"] += len(active)
        toks = []
        for slot in active:
            st = self.slot_manager.state(slot)
            toks.append(self.sampler(
                out[slot:slot + 1], st.temperature, uid=st.uid,
                index=self.request_metrics[st.uid].new_tokens))
        return toks

    # -- the step loop -------------------------------------------------
    def step(self) -> int:
        """One scheduler iteration: admit into free slots, one batched
        decode step, sample + evict.  Returns the number of slots still
        active afterwards."""
        # Each phase that runs is one leaf profiler span (none encloses
        # another), so a trace shows which phase the host was in.
        self.placement.check(self.clock)   # no-op on one device
        with (TraceAnnotation("repro.step.admit") if self._queue
              else contextlib.nullcontext()):
            self._admit_free_slots()
        if self._prefilling:        # only with chunked prefill
            with TraceAnnotation("repro.step.prefill"):
                self._advance_prefills()
        active = self.slot_manager.active_slots()
        if not active:
            return 0
        with TraceAnnotation("repro.step.decode"):
            decode = self._decode
            if decode is None:
                decode, active = self._bucket_program(len(active))
            self.slot_manager.cache, out, *counts = decode(
                self.params, self.slot_manager.cache, *self._step_inputs())
            self._note_counts("decode", counts)
            if self.sampler is None:
                out.copy_to_host_async()
        self.metrics.decode_steps += 1
        self.metrics.decode_slot_steps += len(active)
        with TraceAnnotation("repro.step.sample"):
            for slot, tok in zip(active, self._read_tokens(out, active)):
                st = self.slot_manager.state(slot)
                m = self.request_metrics[st.uid]
                self.generated[st.uid].append(tok)
                self.last_token[slot, 0] = tok
                m.new_tokens += 1
                self.metrics.total_new_tokens += 1
                st.remaining -= 1
                if tok == st.eos_id:
                    self._retire(slot, "eos")
                elif st.remaining <= 0:
                    self._retire(slot, "length")
        self._flush_counts()
        self.metrics.last_step_at = self.clock()
        return self.slot_manager.num_active()

    def run(self, max_steps: int = 10_000) -> List[Completion]:
        """Drain the queue; returns all completions in finish order."""
        steps = 0
        while ((self.queue_depth() or self.slot_manager.num_active()
                or self._prefilling)
               and steps < max_steps):
            self.step()
            steps += 1
        return self.done

    def pop_completions(self, *, purge: bool = False) -> List[Completion]:
        """Completions finished since the last pop (streaming drain).

        With ``purge=True`` the scheduler also forgets the popped
        requests entirely — their ``done`` entries, token lists,
        per-request metrics — and their uids become reusable.  A
        long-running server MUST drain with ``purge=True`` or
        per-request state grows without bound (aggregate
        ``SchedulerMetrics`` counters are unaffected; purged requests
        simply drop out of ``summary()``'s mean-TTFT)."""
        out, self._pending = self._pending, []
        if purge and out:
            drop = {c.uid for c in out}
            self.done = [c for c in self.done if c.uid not in drop]
            for uid in drop:
                self.generated.pop(uid, None)
                self.request_metrics.pop(uid, None)
        return out

    # -- reporting -----------------------------------------------------
    def summary(self) -> dict:
        """Aggregate metrics: counters and TTFT/queue percentiles, plus
        runtime engine stats, the active-precision audit record, and
        chunked-prefill / prefix-cache sections when those features are
        active; under a mesh, the placement's faults and collective
        counts (:meth:`repro.dist.placement.Placement.summary`)."""
        engines = {}
        if self._decode_engine is not None:
            engines["decode"] = self._decode_engine.stats()
        if self._prefill_engine is not None:
            engines["prefill"] = self._prefill_engine.stats()
        if self._chunk_engine is not None:
            engines["chunk"] = self._chunk_engine.stats()
        out = self.metrics.summary(self.request_metrics)
        if engines:
            rt = {k: sum(e[k] for e in engines.values())
                  for k in ("bucket_hits", "bucket_misses",
                            "fallback_serves", "background_compiles",
                            "compile_stalls")}
            pad = sum(e["pad_elems"] for e in engines.values())
            total = sum(e["total_elems"] for e in engines.values())
            rt["pad_waste_frac"] = (pad / total) if total else 0.0
            rt.update(engines)
            out["runtime"] = rt
        if self.precision_info is not None:
            out["precision"] = dict(self.precision_info)
        if self.options.prefill_chunk is not None:
            out["chunked_prefill"] = {
                "enabled": self._chunk_engine is not None,
                "chunk_len": self.options.prefill_chunk,
            }
        if self._prefix_cache is not None:
            out["prefix_cache"] = self._prefix_cache.stats()
        # which path sampled the decode steps, and the token reads each
        # step made (1 when the program samples, one a slot otherwise)
        smp = self._sampling
        steps = smp["program_steps"] + smp["host_steps"]
        out["sampling"] = {
            "program_steps": smp["program_steps"],
            "host_steps": smp["host_steps"],
            "reads_per_step": smp["token_reads"] / steps if steps else None}
        if self._stats:
            # routed token-choices that fell on the experts held here,
            # and held experts hit, summed over the expert layers and
            # over the decode steps / prefill chunks run so far
            self._flush_counts()
            out["experts"] = {k: dict(v)
                              for k, v in self._expert_counts.items()}
        programs = [self._decode] if self._decode is not None else [
            self._decode_engine.peek(b)
            for b in self._decode_engine.warm_buckets()]
        out.update(self.placement.summary(programs))
        return out
