"""SchedulerOptions — the one options surface for ``repro.serve``.

The serving twin of ``CompileOptions``: a frozen, hashable dataclass
holding every scheduling choice, so a serving configuration can be
logged, compared and embedded in benchmark artifacts.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..dist.mesh import MeshSpec
from ..runtime.buckets import BucketPolicy

ADMISSION_POLICIES = ("fcfs", "shortest", "deadline")


@dataclasses.dataclass(frozen=True)
class SchedulerOptions:
    """Every serving-time choice, in one place.

    slots:        number of concurrent decode slots (the fixed batch the
                  decode program is specialized for; continuous batching
                  rebatches at slot granularity every step).
    max_len:      KV-cache capacity per slot.  A request whose prompt
                  alone exceeds it is rejected at submit; ``max_new_tokens``
                  is clamped so the cache can never overflow.
    admission:    queue discipline used when a slot frees up —
                  ``"fcfs"`` (arrival order), ``"shortest"`` (shortest
                  prompt first, minimizes mean TTFT under bursty load)
                  or ``"deadline"`` (earliest-deadline-first over each
                  request's ``slo_ms``; requests without an SLO sort
                  after every deadline, FCFS among themselves — the
                  policy that minimizes ``slo_violations`` under a
                  mixed interactive/batch trace).
    max_queue:    admission control: ``submit`` raises
                  :class:`QueueFullError` once this many requests are
                  waiting.  ``None`` = unbounded.
    fold:         run ``fold_norms`` on the params at scheduler build
                  (compile-time weight rewriting, paper §3.5).
    seed:         PRNG seed of the sampling the programs do (requests
                  with a temperature above 0).
    buckets:      a :class:`repro.runtime.BucketPolicy` enabling
                  shape-polymorphic serving: each step decodes at the
                  smallest warm batch bucket covering the active slots
                  (cache rows sliced, outputs written back) and prefill
                  runs one program per length bucket instead of one per
                  prompt length; cold buckets compile on a background
                  worker.  Buckets are clipped to ``slots``/``max_len``.
                  ``None`` (default) = fixed-shape serving, bit-identical
                  to the pre-bucketing scheduler.
    prefill_chunk: chunk size (tokens) for incremental prefill.  Long
                  prompts are prefilled ``prefill_chunk`` tokens per
                  scheduler step, interleaved with decode steps, so a
                  long prompt never blocks in-flight decodes (tokens
                  stay bit-identical — see ``models.prefill_chunk``).
                  Must divide ``max_len``.  ``None`` (default) =
                  whole-prompt prefill at admission.  Auto-disabled
                  (surfaced in ``summary()["chunked_prefill"]``) for
                  model families without incremental prefill: MLA
                  latent caches, vlm/audio extra inputs, ring caches.
    prefix_cache: capacity (entries) of the shared-prompt-head KV
                  cache.  When > 0, requests whose prompts share a
                  common head (the "system prompt" scenario) prefill
                  that head ONCE: the head's KV rows are snapshotted at
                  a chunk boundary and later requests splice a copy and
                  prefill only their tail (copy-on-write — the shared
                  snapshot is never mutated).  Requires
                  ``prefill_chunk``.  ``0`` (default) = off.
    min_prefix:   minimum shared-head length (tokens) worth caching;
                  the effective floor is ``max(min_prefix,
                  prefill_chunk)`` since snapshots land on chunk
                  boundaries.
    mesh:         a :class:`repro.MeshSpec` (or any spelling
                  ``MeshSpec.coerce`` accepts, e.g. ``"data=2,model=2"``)
                  enabling data×model-parallel serving: the batched KV
                  cache shards its slot (batch) dim over the ``data``
                  axes and its sequence dim over the ``model`` axes (the
                  ``kv_seq`` rule), params are replicated, and the
                  decode program is AOT-compiled against those placings
                  so steady-state decode never stalls on a compile.
                  The step loop re-checks device availability every
                  iteration and surfaces shrink faults as typed
                  :class:`repro.MeshUnavailableError` entries in
                  ``summary()["faults"]``.  ``repro.serve`` defaults
                  this from the executable's own
                  ``CompileOptions.mesh``.  ``None`` = single-device
                  serving (bit-identical tokens to a 1×1 mesh).
    """

    slots: int = 4
    max_len: int = 256
    admission: str = "fcfs"
    max_queue: Optional[int] = None
    fold: bool = True
    seed: int = 0
    buckets: Optional[BucketPolicy] = None
    prefill_chunk: Optional[int] = None
    prefix_cache: int = 0
    min_prefix: int = 0
    mesh: Optional[MeshSpec] = None

    def __post_init__(self) -> None:
        if self.slots <= 0:
            raise ValueError(f"slots must be positive, got {self.slots}")
        if self.max_len <= 1:
            raise ValueError(f"max_len must be > 1, got {self.max_len}")
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(f"admission must be one of "
                             f"{ADMISSION_POLICIES}, got {self.admission!r}")
        if self.max_queue is not None and self.max_queue <= 0:
            raise ValueError(f"max_queue must be positive or None, "
                             f"got {self.max_queue}")
        if isinstance(self.buckets, dict):      # to_dict round-trip
            object.__setattr__(self, "buckets",
                               BucketPolicy.from_dict(self.buckets))
        if self.buckets is not None and not isinstance(self.buckets,
                                                       BucketPolicy):
            raise ValueError(
                f"buckets must be a repro.runtime.BucketPolicy or None, "
                f"got {type(self.buckets).__name__}")
        if self.prefill_chunk is not None:
            if self.prefill_chunk <= 0:
                raise ValueError(f"prefill_chunk must be positive or "
                                 f"None, got {self.prefill_chunk}")
            if self.max_len % self.prefill_chunk != 0:
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must divide "
                    f"max_len ({self.max_len})")
        if self.prefix_cache < 0:
            raise ValueError(f"prefix_cache must be >= 0, "
                             f"got {self.prefix_cache}")
        if self.prefix_cache > 0 and self.prefill_chunk is None:
            raise ValueError(
                "prefix_cache requires prefill_chunk: shared heads are "
                "snapshotted at chunk boundaries and tails are "
                "prefilled incrementally")
        if self.min_prefix < 0:
            raise ValueError(f"min_prefix must be >= 0, "
                             f"got {self.min_prefix}")
        if self.mesh is not None and not isinstance(self.mesh, MeshSpec):
            object.__setattr__(self, "mesh", MeshSpec.coerce(self.mesh))

    def replace(self, **kw) -> "SchedulerOptions":
        """Copy with the given fields replaced (re-validates)."""
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict:
        """Plain-dict view of every option field."""
        return dataclasses.asdict(self)
