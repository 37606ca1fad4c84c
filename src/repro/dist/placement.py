"""Placement of the serving state: one owner for the KV cache's sharding
rule.

The batched cache's leaves are (L, B, S, ...) except the position
vector (B,).  Under a mesh the slot (batch) dim shards over the
``batch`` rule's axes and the sequence dim over the ``kv_seq`` rule's
("model": the flash-decoding storage layout); a dim the axes do not
divide stays replicated, so any slots/max_len runs on any mesh.
Everything else the scheduler places (weights, per-step inputs, a
single-row cache) is replicated.

Sharding here is placement, never math: the decode-time view keeps the
batch sharding only, so row parallelism over ``data`` leaves each row's
reduction order exactly the single-device order (bit-identical tokens),
while GSPMD gathers the model-axis sequence shards whole each step (the
all-gather the summary reports).  The model axis still divides
per-device cache memory by its size between steps.

:class:`Placement` is the single-device form, where every method is the
identity (``put`` a plain transfer); :class:`MeshPlacement` the mesh
form.  ``Placement.of(spec, devices)`` picks one, so no caller branches
on whether a mesh is set.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Iterable, List, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import MeshSpec, MeshUnavailableError, ensure_mesh_available
from .propagate import merged_rules


class Placement:
    """One device: nothing is sharded, nothing is checked."""

    @staticmethod
    def of(spec: Optional[MeshSpec],
           devices: Callable[[], List[Any]] = jax.devices) -> "Placement":
        """The placement for ``spec`` over ``devices()`` (the
        single-device form when ``spec`` is None)."""
        return Placement() if spec is None else MeshPlacement(spec, devices)

    def put(self, tree, *, cache: bool = False):
        """Place a host tree: the batched cache (``cache=True``) by the
        rule, anything else replicated.  One transfer."""
        return jax.device_put(tree)

    def specs(self, tree, *, cache: bool = False):
        """ShapeDtypeStructs for AOT lowering, each carrying the
        placement ``put`` gives its leaf: a compiled program rejects
        committed arguments placed otherwise."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def constrain(self, cache):
        """Pin a traced batched cache to its storage placement, so a
        donated program's output keeps the placement its input had."""
        return cache

    def view(self, cache):
        """The traced decode-time view of the stored batched cache."""
        return cache

    def check(self, clock: Callable[[], float]) -> None:
        """Raise :class:`MeshUnavailableError` (recording the fault at
        ``clock()``) when the visible devices no longer realize the
        mesh."""

    def summary(self, programs: Iterable[Any]) -> dict:
        """The placement's sections of ``Scheduler.summary()``, read
        from the compiled decode ``programs`` (none on one device)."""
        return {}


class MeshPlacement(Placement):
    """A data×model mesh: the cache sharded by the rule (module
    docstring), everything else replicated, the device set checked each
    step."""

    def __init__(self, spec: MeshSpec,
                 devices: Callable[[], List[Any]] = jax.devices) -> None:
        self.spec = spec
        self._devices = devices
        self.mesh = spec.build(devices())
        self.faults: List[dict] = []
        names = set(self.mesh.axis_names)
        rules = merged_rules()
        self._axes = {k: tuple(a for a in rules.get(k, ()) if a in names)
                      for k in ("batch", "kv_seq")}

    def _sharding(self, leaf, *, cache: bool = False,
                 compute: bool = False) -> NamedSharding:
        """One leaf's NamedSharding: replicated unless ``cache``; a
        batched-cache leaf's slot dim over the ``batch`` axes and,
        unless ``compute`` (the decode-time view), its sequence dim over
        the ``kv_seq`` axes."""
        if not cache:
            return NamedSharding(self.mesh, PartitionSpec())
        sizes = dict(self.mesh.shape)

        def fit(dim, axes):
            axes = [a for a in axes if sizes.get(a, 1) > 1]
            k = math.prod(sizes[a] for a in axes) if axes else 1
            if k <= 1 or dim % k:
                return None
            return axes[0] if len(axes) == 1 else tuple(axes)

        parts = [None] * leaf.ndim
        b_dim = 0 if leaf.ndim == 1 else 1
        parts[b_dim] = fit(leaf.shape[b_dim], self._axes["batch"])
        if leaf.ndim >= 3 and not compute:
            parts[2] = fit(leaf.shape[2], self._axes["kv_seq"])
        return NamedSharding(self.mesh, PartitionSpec(*parts))

    def put(self, tree, *, cache: bool = False):
        """One ``device_put`` of the tree onto its leaves' shardings."""
        return jax.device_put(tree, jax.tree.map(
            lambda l: self._sharding(l, cache=cache), tree))

    def specs(self, tree, *, cache: bool = False):
        """ShapeDtypeStructs carrying the leaves' shardings."""
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=self._sharding(a, cache=cache)), tree)

    def _pin(self, cache, compute: bool):
        return jax.tree.map(lambda l: jax.lax.with_sharding_constraint(
            l, self._sharding(l, cache=True, compute=compute)), cache)

    def constrain(self, cache):
        """Slot and sequence dims sharded, as the cache is stored."""
        return self._pin(cache, compute=False)

    def view(self, cache):
        """Slot dim sharded only: GSPMD gathers the sequence shards."""
        return self._pin(cache, compute=True)

    def check(self, clock: Callable[[], float]) -> None:
        """Raise and record a fault when too few devices are visible."""
        try:
            ensure_mesh_available(self.spec, self._devices())
        except MeshUnavailableError as e:
            self.faults.append({
                "at": clock(), "mesh": e.spec.describe(),
                "needed": e.needed, "available": e.available,
                "missing_axes": list(e.missing_axes)})
            raise

    def summary(self, programs: Iterable[Any]) -> dict:
        """``faults`` (each step-loop check that raised) and
        ``sharding``: the mesh plus per-axis collective counts and
        bytes-moved estimates from the programs' post-optimization HLO
        (:func:`repro.launch.hlo_analysis.collectives_by_axis`)."""
        from ..launch.hlo_analysis import collectives_by_axis
        texts = []
        for prog in programs:
            try:
                texts.append(prog.as_text())
            except Exception:
                continue               # text unavailable: skip, not fail
        return {"faults": [dict(f) for f in self.faults],
                "sharding": {
                    "mesh": self.spec.describe(), "devices": self.spec.size,
                    "decode_programs": len(texts),
                    "collectives": collectives_by_axis(texts,
                                                       self.spec.axes)}}
