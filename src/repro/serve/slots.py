"""Slot / KV-cache manager: the memory half of continuous batching.

Owns the batched cache pytree (the paper: "input and output tensors are
owned by CompiledNN because it needs control over the actual memory
layout") and the per-slot host bookkeeping.  Admission splices a
freshly prefilled single-row cache into a free slot; eviction just
marks the slot free — the row is overwritten by the next admission, so
no memory moves on retire.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax

from ..dist.placement import Placement


@dataclasses.dataclass
class SlotState:
    """Host-side record for one occupied decode slot."""

    uid: int
    remaining: int           # decode steps left before forced retire
    eos_id: int              # -1 = never
    temperature: float


class SlotManager:
    """Owns the batched KV cache and the per-slot request states:
    admission writes one request's rows in, retirement frees them."""

    def __init__(self, model, slots: int, max_len: int, *,
                 placement: Optional[Placement] = None) -> None:
        self.slots = slots
        self.max_len = max_len
        # The cache is placed once here and every cache-mutating program
        # re-constrains its output, so the batched cache never drifts off
        # its placement: the compiled decode programs commit to it.
        place = placement or Placement()
        self.cache = place.put(model.init_cache(slots, max_len), cache=True)
        self._states: List[Optional[SlotState]] = [None] * slots

        # donate the batched cache: splice writes one row in place
        self._splice = jax.jit(
            lambda c, o, slot: place.constrain(
                self._splice_impl(c, o, slot)),
            donate_argnums=(0,), static_argnums=(2,))
        # row move for compaction; src/dst are traced, so one program
        # serves every (src, dst) pair
        self._move = jax.jit(
            lambda c, s, d: place.constrain(self._move_impl(c, s, d)),
            donate_argnums=(0,))

    # ------------------------------------------------------------------
    @staticmethod
    def _splice_impl(cache, one_cache, slot: int):
        """Copy the single-row cache ``one_cache`` into row ``slot`` of
        every batch-indexed leaf.  Leaves are (L, B, ...) except the
        position vector (B,)."""
        def put(dst, src):
            if dst.ndim == 1:                      # pos (B,)
                return dst.at[slot].set(src[0])
            return dst.at[:, slot].set(src[:, 0].astype(dst.dtype))
        return jax.tree.map(put, cache, one_cache)

    @staticmethod
    def _move_impl(cache, src, dst):
        """Copy slot row ``src`` over row ``dst`` in every leaf."""
        def mv(l):
            if l.ndim == 1:                        # pos (B,)
                return l.at[dst].set(l[src])
            return l.at[:, dst].set(l[:, src])
        return jax.tree.map(mv, cache)

    # ------------------------------------------------------------------
    def buffer_pointers(self) -> Tuple[int, ...]:
        """The device buffer address of every cache leaf — the handle
        zero-allocation tests use: across steady-state decode steps the
        donated step program must leave every pointer unchanged (the
        cache is updated in place, never reallocated)."""
        return tuple(l.unsafe_buffer_pointer()
                     for l in jax.tree.leaves(self.cache))

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        """Indices of empty slots."""
        return [s for s, st in enumerate(self._states) if st is None]

    def active_slots(self) -> List[int]:
        """Indices of occupied slots."""
        return [s for s, st in enumerate(self._states) if st is not None]

    def num_active(self) -> int:
        """Number of occupied slots."""
        return sum(st is not None for st in self._states)

    def state(self, slot: int) -> Optional[SlotState]:
        """The request state in ``slot`` (None when free)."""
        return self._states[slot]

    # ------------------------------------------------------------------
    def admit(self, slot: int, state: SlotState, one_cache: Any) -> None:
        """Occupy ``slot`` with ``state``, splicing its prefilled
        single-row cache into the batched cache."""
        if self._states[slot] is not None:
            raise RuntimeError(f"slot {slot} is occupied "
                               f"(uid={self._states[slot].uid})")
        self.cache = self._splice(self.cache, one_cache, slot)
        self._states[slot] = state

    def evict(self, slot: int) -> SlotState:
        """Free ``slot``; the cache row is left in place and simply
        overwritten by the next admission."""
        st = self._states[slot]
        if st is None:
            raise RuntimeError(f"slot {slot} is already free")
        self._states[slot] = None
        return st

    # ------------------------------------------------------------------
    def compact(self) -> List[Tuple[int, int]]:
        """Move active rows down so they occupy the prefix ``[0, k)`` —
        the invariant bucketed decode needs to slice the first ``k``
        cache rows.  Each hole below ``k`` is filled by the *highest*
        active row (one move per hole, no cascades).  Returns the
        ``(src, dst)`` moves so the caller can mirror them in host-side
        per-slot state (``last_token``)."""
        moves: List[Tuple[int, int]] = []
        while True:
            active = self.active_slots()
            k = len(active)
            hole = next((s for s in range(k)
                         if self._states[s] is None), None)
            if hole is None:
                return moves
            src = active[-1]
            self.cache = self._move(self.cache, src, hole)
            self._states[hole], self._states[src] = self._states[src], None
            moves.append((src, hole))
