"""Model API — one uniform surface over all architecture families.

``get_model(cfg)`` returns a ``Model`` whose methods dispatch to the
family implementation.  The launcher, trainer, serving engine, tests and
dry-run all speak only this protocol:

    init(key)                 -> params pytree
    param_axes()              -> logical-axis pytree (same structure)
    forward(params, batch)    -> (logits, extras)          [train]
    init_cache(batch, max_len)-> cache pytree              [serve]
    cache_axes()              -> logical axes for the cache
    prefill(params, batch, cache) -> (logits, cache)
    decode_step(params, cache, tokens) -> (logits, cache)
    prefill_chunk(params, tokens, cache, start, length) -> (logits, cache)
    loss(params, batch)       -> scalar loss               [train]
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import common as C
from . import rglru, ssm, transformer, whisper

_FAMILY_MODULES = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": ssm,
    "hybrid": rglru,
    "audio": whisper,
}


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    mod: Any

    # -- params --------------------------------------------------------
    def init(self, key) -> Any:
        return self.mod.init_params(self.cfg, key)

    def param_axes(self) -> Any:
        return self.mod.param_axes(self.cfg)

    # -- training ------------------------------------------------------
    def forward(self, params, batch: Dict[str, jnp.ndarray]):
        extra = {}
        if self.cfg.family == "vlm":
            extra["patches"] = batch.get("patches")
        if self.cfg.family == "audio":
            return self.mod.forward(self.cfg, params, batch["tokens"],
                                    frames=batch.get("frames"))
        return self.mod.forward(self.cfg, params, batch["tokens"], **extra)

    def loss(self, params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        logits, extras = self.forward(params, batch)
        loss = C.cross_entropy(logits, batch["labels"])
        loss = loss + extras.get("aux_loss", 0.0)
        if self.cfg.mtp and "mtp_hidden" in extras:
            mtp = self.mod.mtp_logits(self.cfg, params,
                                      extras["mtp_hidden"], batch["tokens"])
            mtp_labels = jnp.roll(batch["labels"], -1, axis=1)
            loss = loss + 0.1 * C.cross_entropy(mtp, mtp_labels)
        return loss

    # -- serving -------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        return self.mod.init_cache(self.cfg, batch, max_len)

    def cache_axes(self):
        return self.mod.cache_axes(self.cfg)

    def prefill(self, params, batch: Dict[str, jnp.ndarray], cache):
        kw = {}
        if self.cfg.family == "vlm":
            kw["patches"] = batch.get("patches")
        if self.cfg.family == "audio":
            kw["frames"] = batch.get("frames")
        return self.mod.prefill(self.cfg, params, batch["tokens"], cache,
                                **kw)

    def decode_step(self, params, cache, tokens, **kw):
        """One decode step; ``stats=True`` (models with
        :attr:`counts_experts`) appends the expert counters."""
        return self.mod.decode_step(self.cfg, params, cache, tokens, **kw)

    @property
    def counts_experts(self) -> bool:
        """True when ``decode_step``/``prefill_chunk`` can return expert
        counters (``stats=True``): transformer-family models with
        routed experts."""
        return bool(self.cfg.n_experts) and self.mod is transformer

    def supports_chunked_prefill(self) -> bool:
        """True when the family implements incremental ``prefill_chunk``.

        Transformer-family models (dense/moe, MLA latent caches too)
        qualify unless they need extra prefill inputs (vlm patches,
        audio frames).  Callers must additionally check that the cache
        is full-context (not a ring) — see ``repro.serve``.
        """
        if not hasattr(self.mod, "prefill_chunk"):
            return False
        if self.cfg.family in ("vlm", "audio"):
            return False
        return True

    def prefill_chunk(self, params, tokens, cache, start, length, **kw):
        """Prefill one fixed-size chunk of a prompt at absolute offset
        ``start`` (see ``transformer.prefill_chunk``)."""
        return self.mod.prefill_chunk(self.cfg, params, tokens, cache,
                                      start, length, **kw)


def get_model(cfg: ArchConfig) -> Model:
    if cfg.family not in _FAMILY_MODULES:
        raise KeyError(f"unknown family {cfg.family!r}")
    return Model(cfg, _FAMILY_MODULES[cfg.family])
