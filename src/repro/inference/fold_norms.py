"""Compile-time RMSNorm folding for the transformer zoo (paper §3.5).

The paper folds batch-norm's affine into the adjacent conv/dense weights
("adjusting the weights and biases … so that they already include the
factors of the normalization").  The modern-transformer twin: RMSNorm's
learned diagonal scale ``diag(1+γ)`` commutes into the *following*
projection:

    proj(rms(x) * (1+γ))  ==  rms(x) @ (diag(1+γ) W)

so at model-load time we set γ' = 0 and W' = diag(1+γ)·W.  One
multiplication per feature per layer disappears from every forward pass
— exactly the paper's trade: arithmetic moved from run time to compile
time because the weights are compile-time constants.  Inference-only
(the fold would corrupt gradients w.r.t. the original parametrization).

The fold leaves the *normalization* (rsqrt of the mean square) in place
— only the diagonal scale moves.  Numerics change by float-associativity
only; tests bound the drift against the unfolded oracle the same way the
paper uses SimpleNN.

Each matrix is folded by one compiled elementwise program, so no float32
copy of it is ever materialized.  With ``donate=True`` the program takes
the original's buffer for its result: the folded weights take the place
of the originals, and set-up never holds two copies of a large matrix
(the originals are deleted, for every holder of them).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


def _scale_rows_impl(w: jnp.ndarray, scale: jnp.ndarray,
                     axis: int) -> jnp.ndarray:
    shape = [1] * w.ndim
    shape[axis] = w.shape[axis]
    if scale.ndim == 2:
        shape[0] = w.shape[0]
    return (w.astype(jnp.float32)
            * scale.reshape(shape).astype(jnp.float32)).astype(w.dtype)


_SCALE = {donate: jax.jit(_scale_rows_impl, static_argnums=2,
                          donate_argnums=(0,) if donate else ())
          for donate in (False, True)}


def _scale_rows(w: jnp.ndarray, scale: jnp.ndarray, axis: int,
                donate: bool = False) -> jnp.ndarray:
    """w scaled by `scale` along `axis` (the fan-in dim).  2-D scales are
    (L, D) for layer-stacked weights (L on dim 0, D on `axis`).  With
    ``donate`` the result takes ``w``'s buffer and ``w`` is deleted."""
    return _SCALE[donate](w, scale, axis)


def _fold_layer(cfg, lp: Dict[str, Any], donate: bool = False
                ) -> Tuple[Dict[str, Any], int]:
    """Fold ln1 into the attention input projections and ln2 into the
    FFN input projections of one (stacked) layer pytree."""
    scale = functools.partial(_scale_rows, donate=donate)
    lp = dict(lp)
    folds = 0
    s1 = 1.0 + lp["ln1"].astype(jnp.float32)          # (L, D)
    s2 = 1.0 + lp["ln2"].astype(jnp.float32)

    attn = dict(lp["attn"])
    for k in (("q_down", "kv_down") if cfg.mla else ("wq", "wk", "wv")):
        attn[k] = scale(attn[k], s1, 1)
        folds += 1
    lp["attn"] = attn
    lp["ln1"] = jnp.zeros_like(lp["ln1"])

    ffn = dict(lp["ffn"])
    if "router" in ffn:
        ffn["router"] = scale(ffn["router"], s2, 1)
        ffn["wi_gate"] = scale(ffn["wi_gate"], s2, 2)
        ffn["wi_up"] = scale(ffn["wi_up"], s2, 2)
        folds += 3
        if "shared" in ffn:
            sh = dict(ffn["shared"])
            sh["wi_gate"] = scale(sh["wi_gate"], s2, 1)
            sh["wi_up"] = scale(sh["wi_up"], s2, 1)
            ffn["shared"] = sh
            folds += 2
    else:
        ffn["wi_gate"] = scale(ffn["wi_gate"], s2, 1)
        ffn["wi_up"] = scale(ffn["wi_up"], s2, 1)
        folds += 2
    lp["ffn"] = ffn
    lp["ln2"] = jnp.zeros_like(lp["ln2"])
    return lp, folds


def fold_norms(cfg, params: Dict[str, Any], *, donate: bool = False
               ) -> Tuple[Dict[str, Any], Dict]:
    """Inference-time norm fold for transformer-family params.
    Returns (new_params, report).  No-op for families without RMSNorm
    scales adjacent to projections (whisper's LayerNorm has a bias —
    foldable in principle, left as-is; ssm/hybrid handled partially).
    With ``donate`` every folded matrix replaces its original in place
    (see the module docstring)."""
    if cfg.family not in ("dense", "moe", "vlm"):
        return params, {"folds": 0, "note": f"family {cfg.family}: skipped"}
    params = dict(params)
    folds = 0
    for stack in ("dense_layers", "layers"):
        if stack in params:
            params[stack], n = _fold_layer(cfg, params[stack], donate)
            folds += n
    # Final norm -> unembedding (untied heads only: with tied embeddings
    # the matrix is shared with the input lookup, which must stay raw).
    if not cfg.tie_embeddings and "head" in params:
        sf = 1.0 + params["ln_f"].astype(jnp.float32)
        params["head"] = _scale_rows(params["head"], sf, 0, donate)
        params["ln_f"] = jnp.zeros_like(params["ln_f"])
        folds += 1
    return params, {"folds": folds, "donated": donate}
