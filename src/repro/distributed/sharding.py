"""Logical-axis sharding rules (MaxText-style) for the LM framework.

Model code annotates tensors with *logical* axis names; the rules table
maps them to mesh axes of whatever mesh is active.  With no mesh (unit
tests on 1 CPU device) every annotation is a no-op, so the same model
code runs everywhere — the compile-time-specialization philosophy of the
paper extended to distribution: the sharding is part of the compiled
artifact, not of the model definition.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]

#: logical axis -> mesh axis (or tuple of mesh axes).  "batch" composes
#: pod×data so a multi-pod mesh is pure DP across pods by default.
DEFAULT_RULES: Dict[str, AxisVal] = {
    "batch": ("pod", "data"),
    "seq": None,             # sequence kept replicated by default ...
    "seq_shard": "data",     # ... except where context parallelism is on
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_capacity": None,
    "vocab": "model",
    # Decode KV caches shard the SEQUENCE dim over "model"
    # (flash-decoding layout): context lengths are always 16-divisible,
    # unlike kv-head counts (8, 1, ...), and the only collective the
    # layout needs is a tiny psum of the (B,H,dv) attention output.
    "kv_seq": "model",
    "conv": None,
    "state": None,
    "frames": None,
    # Parameter-only axes.  "fsdp" shards the weight fan-in dim over the
    # data axis (ZeRO-3-style: GSPMD all-gathers each layer's params at
    # use inside the scan); "layers" is the scan-stack dim.
    "fsdp": "data",
    "layers": None,
}

_local = threading.local()


def current_rules() -> Dict[str, AxisVal]:
    return getattr(_local, "rules", DEFAULT_RULES)


def current_mesh() -> Optional[Mesh]:
    """The mesh activated by :func:`use_mesh` on this thread, if any."""
    return getattr(_local, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, AxisVal]] = None):
    """Activate a mesh + rules for model-code annotations."""
    prev_mesh = getattr(_local, "mesh", None)
    prev_rules = getattr(_local, "rules", None)
    _local.mesh = mesh
    _local.rules = {**DEFAULT_RULES, **(rules or {})}
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _local.mesh = prev_mesh
        if prev_rules is None:
            if hasattr(_local, "rules"):
                del _local.rules
        else:
            _local.rules = prev_rules


def spec_for(*logical_axes: Optional[str]) -> P:
    """PartitionSpec for a tuple of logical axis names, deduplicating
    mesh axes (a mesh axis may appear at most once in a spec)."""
    rules = current_rules()
    used = set()
    parts = []
    for ax in logical_axes:
        val = rules.get(ax) if ax else None
        if val is None:
            parts.append(None)
            continue
        axes = (val,) if isinstance(val, str) else tuple(val)
        axes = tuple(a for a in axes if a not in used)
        if not axes:
            parts.append(None)
        elif len(axes) == 1:
            parts.append(axes[0])
            used.add(axes[0])
        else:
            parts.append(axes)
            used.update(axes)
    return P(*parts)


def logical(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint by logical axis names (no-op if no mesh
    is active or the mesh axes don't exist on the current mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = spec_for(*logical_axes)
    # Drop mesh axes that this mesh doesn't have (e.g. "pod" on 2D mesh).
    names = set(mesh.axis_names)

    def keep(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        kept = tuple(a for a in v if a in names)
        return kept if kept else None

    spec = P(*(keep(v) for v in spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, *logical_axes: Optional[str]) -> NamedSharding:
    with use_mesh(None):  # rules only; don't re-enter mesh ctx
        pass
    spec = spec_for(*logical_axes)
    names = set(mesh.axis_names)

    def keep(v):
        if v is None:
            return None
        if isinstance(v, str):
            return v if v in names else None
        kept = tuple(a for a in v if a in names)
        return kept if kept else None

    return NamedSharding(mesh, P(*(keep(v) for v in spec)))
