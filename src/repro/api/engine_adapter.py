"""The "engine" target: framework-scale models behind the same API.

``repro.compile(cfg_or_model, CompileOptions(target="engine"))`` wraps
``models.api.Model`` + ``repro.serve`` in the Executable protocol,
so the LLM stack and the paper's CNN compiler are driven identically:

    exe = repro.compile(get_config("qwen2.5-14b", smoke=True),
                        CompileOptions(target="engine"), params=params)
    exe(tokens=toks)["logits"]          # jitted forward
    sched = exe.serve(slots=4)          # continuous-batching scheduler
"""

from __future__ import annotations

import dataclasses
import io
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.graph import Signature
from .executable import Executable, pack
from .options import CompileOptions


class ModelExecutable(Executable):
    """Executable over a registered model architecture (the ``"engine"``
    target): wraps ``model.forward`` in one jitted program with params
    closed over, tracking compile time per unseen input signature."""

    def __init__(self, model_or_cfg, options: CompileOptions, *,
                 params=None, init_seed: int = 0) -> None:
        from ..models.api import Model, get_model
        if isinstance(model_or_cfg, Model):
            self.model = model_or_cfg
            self.cfg = model_or_cfg.cfg
        else:
            self.cfg = model_or_cfg
            self.model = get_model(self.cfg)
        self.options = options
        if params is None:
            params = self.model.init(jax.random.PRNGKey(init_seed))
        # Low-precision serving: the graph quantize pass does not route
        # through framework-scale models, so the engine target supports
        # the storage-level subset — a weight-only bf16 cast (matmuls
        # upcast per JAX promotion, activations and KV stay f32).
        # Calibrated int8 needs the graph pipeline and is rejected here
        # rather than silently served at full precision.
        self.quant_report: Optional[dict] = None
        if options.precision in ("int8", "mixed"):
            raise ValueError(
                f"precision={options.precision!r} is not supported by "
                "the 'engine' target: calibrated int8 routes through "
                "the graph quantize pass, which framework-scale models "
                "bypass — use precision='bf16' (weight-only storage "
                "cast) for served models")
        if options.precision == "bf16":
            leaves, treedef = jax.tree_util.tree_flatten(params)
            cast = [l.astype(jnp.bfloat16)
                    if getattr(l, "dtype", None) == jnp.float32 else l
                    for l in leaves]
            n_bf16 = sum(1 for l in cast
                         if getattr(l, "dtype", None) == jnp.bfloat16)
            params = jax.tree_util.tree_unflatten(treedef, cast)
            self.quant_report = {
                "mode": "bf16",
                "decisions": {"bf16": n_bf16,
                              "f32": len(leaves) - n_bf16}}
        self.params = params
        self.compile_time: Optional[float] = None
        self._fwd = jax.jit(lambda p, b: self.model.forward(p, b)[0])
        self._seen_shapes = set()
        # Shapes are dynamic at this scale (prefill length, batch), so
        # the signature carries names + order but no static specs.
        from ..configs.base import extra_input_specs
        self.signature = Signature(
            inputs=(("tokens", None),) + tuple(
                (n, None) for n in extra_input_specs(self.cfg)),
            outputs=(("logits", None),),
        )

    # ------------------------------------------------------------------
    def __call__(self, *pos, **batch) -> Dict[str, Any]:
        batch = self.signature.bind(pos, batch)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        sig = tuple(sorted((k, v.shape, str(v.dtype))
                           for k, v in batch.items()))
        if sig not in self._seen_shapes:
            t0 = time.perf_counter()
            logits = jax.block_until_ready(self._fwd(self.params, batch))
            self._seen_shapes.add(sig)
            self.compile_time = ((self.compile_time or 0.0)
                                 + time.perf_counter() - t0)
        else:
            logits = self._fwd(self.params, batch)
        return {"logits": logits}

    def serve(self, options=None, **kw):
        """Build the continuous-batching scheduler over this executable
        (shorthand for ``repro.serve(exe, options, **kw)``)."""
        from .serve import serve as api_serve
        return api_serve(self, options, **kw)

    # ------------------------------------------------------------------
    def cost_summary(self):
        """Model-level cost facts: parameter count and byte footprint
        (engine executables have no pass pipeline to report)."""
        leaves = jax.tree_util.tree_leaves(self.params)
        out = {
            "target": "engine",
            "arch": self.cfg.name,
            "family": self.cfg.family,
            "params": int(sum(l.size for l in leaves)),
            "param_bytes": int(sum(l.size * l.dtype.itemsize
                                   for l in leaves)),
        }
        if self.quant_report is not None:
            out["quant"] = dict(self.quant_report)
        return out

    def serialize(self) -> bytes:
        """Pack cfg + param leaves into the portable artifact format."""
        # The param pytree structure is NOT stored: it is rederived from
        # the cfg at load time (no pickle — repro.deserialize must be
        # safe on untrusted bytes).  Only leaves travel, in
        # tree_flatten order.
        leaves, _ = jax.tree_util.tree_flatten(self.params)
        arrays = {}
        dtypes = []
        for i, leaf in enumerate(leaves):
            a = np.asarray(leaf)
            dtypes.append(str(a.dtype))
            if a.dtype not in (np.float32, np.float64, np.int32, np.int64,
                               np.uint8, np.bool_):
                a = a.astype(np.float32)  # bf16 etc: widen losslessly
            arrays[f"leaf::{i}"] = a
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        extra = {"cfg": dataclasses.asdict(self.cfg), "leaf_dtypes": dtypes}
        return pack("engine", self.options, buf.getvalue(), extra=extra)


def deserialize_engine(meta: dict, body: bytes,
                       options: CompileOptions) -> ModelExecutable:
    """Rebuild a ``ModelExecutable`` from a packed artifact: cfg from
    metadata, param leaves from the npz body (no pickle)."""
    from ..configs.base import ArchConfig
    from ..core.keras_like import _tuplify
    from ..models.api import get_model
    data = np.load(io.BytesIO(body), allow_pickle=False)
    cfg_dict = {k: _tuplify(v) if isinstance(v, list) else v
                for k, v in meta["cfg"].items()}
    cfg = ArchConfig(**cfg_dict)
    # Rebuild the pytree structure from the cfg (abstract init — no
    # allocation), then pour the stored leaves back in.
    model = get_model(cfg)
    template = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0)))
    treedef = jax.tree_util.tree_structure(template)
    n = treedef.num_leaves
    if len(meta["leaf_dtypes"]) != n:
        raise ValueError(
            f"param leaf count mismatch: container has "
            f"{len(meta['leaf_dtypes'])}, cfg {cfg.name!r} expects {n}")
    leaves = [jnp.asarray(data[f"leaf::{i}"]).astype(dt)
              for i, dt in enumerate(meta["leaf_dtypes"])]
    params = jax.tree_util.tree_unflatten(treedef, leaves)
    return ModelExecutable(cfg, options, params=params)
