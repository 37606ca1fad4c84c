"""Pure-jnp oracle for the grouped matmul."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def gmm_ref(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
            rhs_up: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``lhs`` (M, K) holds its rows sorted by group, ``group_sizes[g]``
    of them for group ``g`` in order; row ``r`` of group ``g`` gives
    ``lhs[r] @ rhs[g]`` (``rhs`` is (G, K, N)).  With ``rhs_up`` the row
    gives ``silu(lhs[r] @ rhs[g]) * (lhs[r] @ rhs_up[g])``.  Rows past
    the last group are zero.  Computed in float32."""
    m = lhs.shape[0]
    ends = jnp.cumsum(group_sizes)
    group = jnp.searchsorted(ends, jnp.arange(m), side="right")  # (M,)
    x = lhs.astype(jnp.float32)
    out = jnp.zeros((m, rhs.shape[-1]), jnp.float32)
    for g in range(rhs.shape[0]):
        y = x @ rhs[g].astype(jnp.float32)
        if rhs_up is not None:
            y = jax.nn.silu(y) * (x @ rhs_up[g].astype(jnp.float32))
        out = jnp.where((group == g)[:, None], y, out)
    return out.astype(lhs.dtype)
