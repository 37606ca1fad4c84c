"""ShardedExecutable — a JitExecutable whose mesh is a compile input.

Built by ``repro.compile`` whenever ``CompileOptions(mesh=...)`` is set
on the ``"jit"``/``"pallas"`` targets.  It rides the entire existing
machinery — pass pipeline, kernel selection, persistent executable
cache, capture bundles — and adds exactly three things through the
:class:`~repro.api.targets.JitExecutable` sharding hooks:

* the ``propagate_sharding`` pass input: ``graph.dist`` carries the
  mesh spec + rules in, and the resolved per-tensor specs + collective
  edit log out;
* sharded lowering: AOT input specs get ``NamedSharding``s, every
  traced tensor its propagated constraint (``execute_graph``), and call
  arguments are re-placed with ``device_put`` so the AOT program's
  committed input shardings are always satisfied;
* a manifest in the executable cache grouping the per-batch artifacts,
  so ``repro.prune`` evicts a sharded executable atomically.

Mesh + shardings are part of both the persistent cache key (via
``graph.dist`` in ``structure_hash`` and ``mesh``/``sharding_rules`` in
``CompileOptions.cache_token``) and the ``serialize()`` manifest — a
second process deserializing the artifact replays the placement with
zero re-propagation and hits the warm cache with zero recompiles.
"""

from __future__ import annotations

import io
import json
import os
from typing import Callable, Optional

import jax

from ..api.executable import pack
from ..api.targets import JitExecutable
from ..frontends.container import save_model
from .mesh import MeshSpec, ensure_mesh_available
from .propagate import collective_summary


class ShardedExecutable(JitExecutable):
    """Mesh-aware compiled artifact: per-tensor PartitionSpecs, explicit
    collectives, and a device mesh bound at call time.

    A single-device mesh is bit-identical to the unsharded
    ``JitExecutable`` path: every collective lowers to the identity and
    every constraint is trivial.
    """

    def __init__(self, graph, options, *,
                 lowering_target: Optional[str] = None,
                 resolved: Optional[dict] = None) -> None:
        if options.mesh is None:
            raise ValueError("ShardedExecutable needs CompileOptions(mesh=...)")
        spec: MeshSpec = options.mesh
        # Fail before compiling, with the unfillable axes named —
        # never an opaque XLA device error.
        ensure_mesh_available(spec)
        self._mesh_spec = spec
        self._mesh = None
        annotated = graph.copy()
        annotated.dist = {"mesh": spec.to_dict(),
                          "rules": [list(p) for p in
                                    (options.sharding_rules or ())]}
        if resolved is not None:
            # Manifest round-trip: replay the recorded placement
            # instead of re-propagating (see dist.propagate._replay).
            annotated.dist["resolved"] = resolved
        super().__init__(annotated, options,
                         lowering_target=lowering_target
                         or ("pallas" if options.target == "pallas"
                             else "jit"))

    # -- mesh ----------------------------------------------------------
    @property
    def mesh_spec(self) -> MeshSpec:
        """The static mesh description this executable was compiled for."""
        return self._mesh_spec

    @property
    def mesh(self):
        """The live ``jax.sharding.Mesh`` (built lazily; raises
        ``MeshUnavailableError`` if the device set shrank)."""
        if self._mesh is None:
            self._mesh = self._mesh_spec.build()
        return self._mesh

    def partition_spec(self, name: str):
        """The resolved (batch-inclusive) ``PartitionSpec`` of a graph
        tensor — or of a public output name."""
        from jax.sharding import PartitionSpec
        shardings = self.graph.dist["shardings"]
        if name not in shardings:
            public = dict(zip(self.source.output_names, self.graph.outputs))
            if name in public:
                name = public[name]
        entry = shardings.get(name)
        if entry is None:
            raise KeyError(f"no resolved sharding for tensor {name!r}; "
                           f"known: {sorted(shardings)[:8]}...")
        return PartitionSpec(*(
            None if not axes else (axes[0] if len(axes) == 1
                                   else tuple(axes))
            for axes in entry))

    # -- sharding hooks (consumed by JitExecutable._compile_batch) -----
    def _lowering_extras(self) -> dict:
        return {"mesh": self.mesh,
                "shardings": self.graph.dist["shardings"]}

    def _input_sharding(self, name: str, batch_size: int):
        from jax.sharding import NamedSharding, PartitionSpec
        entry = self.graph.dist["shardings"].get(name) or []
        sizes = dict(self.mesh.shape)
        shape = (batch_size,) + self.graph.inputs[name].shape
        parts = []
        for dim, axes in zip(shape, entry):
            axes = [a for a in (axes or ()) if a in sizes]
            k = 1
            for a in axes:
                k *= sizes[a]
            if k <= 1 or dim % k:
                parts.append(None)
            else:
                parts.append(axes[0] if len(axes) == 1 else tuple(axes))
        return NamedSharding(self.mesh, PartitionSpec(*parts))

    def _wrap_compiled(self, fn: Callable, batch_size: int) -> Callable:
        # An AOT-compiled program rejects committed arguments whose
        # placement disagrees with its input shardings; re-placing with
        # device_put is a no-op when they already agree.
        shardings = [self._input_sharding(n, batch_size)
                     for n in self.graph.inputs]
        self._record_manifest(batch_size)

        def call(*args):
            placed = [jax.device_put(a, s) for a, s in zip(args, shardings)]
            return fn(*placed)

        return call

    def _execution_devices(self):
        return list(self.mesh.devices.flat)

    # -- cache manifest (repro.prune atomic groups) --------------------
    def manifest_key(self) -> str:
        """Identity of this executable's cache-manifest group (all batch
        specializations of one sharded compile)."""
        from ..api.cache import cache_key
        return cache_key("shard-manifest", self.graph.structure_hash(),
                         self.options.cache_token())

    def _record_manifest(self, batch_size: int) -> None:
        """Append this batch's artifact key to the on-disk manifest, so
        ``repro.prune`` treats the per-batch entries + manifest as one
        atomic LRU group (best-effort, like the cache itself)."""
        if self._disk is None:
            return
        path = os.path.join(self._disk.root,
                            f"{self.manifest_key()}.manifest.json")
        try:
            doc = {"mesh": self._mesh_spec.to_dict(), "members": []}
            if os.path.exists(path):
                with open(path) as f:
                    doc = json.load(f)
            key = self._key(batch_size,
                            self._selections.get(batch_size) or {})
            if key not in doc["members"]:
                doc["members"].append(key)
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except (OSError, ValueError):
            pass

    # -- introspection / serialization ---------------------------------
    def cost_summary(self):
        """Compile-time facts plus a ``"sharding"`` block: mesh, device
        count, per-axis collective counts / bytes-moved estimates, and
        the number of tensors with resolved specs."""
        out = super().cost_summary()
        out["sharding"] = {
            "mesh": self._mesh_spec.describe(),
            "devices": self._mesh_spec.size,
            "collectives": collective_summary(self.graph, self._mesh_spec),
            "tensors": len(self.graph.dist.get("shardings", {})),
        }
        return out

    def serialize(self) -> bytes:
        """Artifact container of kind ``"sharded"``: the source graph
        plus the resolved placement (specs + collective edit log), so
        ``repro.deserialize`` reconstructs it with zero
        re-propagation."""
        buf = io.BytesIO()
        save_model(self.source, buf)
        dist = self.graph.dist
        return pack("sharded", self.options, buf.getvalue(),
                    extra={"signature": self.signature.to_dict(),
                           "dist": {"shardings": dist["shardings"],
                                    "edits": dist["edits"]}})
