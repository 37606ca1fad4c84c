"""Public wrappers of the grouped matmul: the tiled row layout, and
``gmm`` over rows sorted by group.

A mixture-of-experts layer sorts the token-choices that fall on the
experts held here by expert and multiplies each expert's rows by that
expert's weights, with no capacity and nothing dropped.  The kernel
wants each group's rows to start on a row tile, so :func:`layout` turns
the group sizes into that layout: where each group's rows start, which
group each tile belongs to and how many tiles hold rows.  The number of
tiles is static (the most any routing can need), the number in use is
not; the kernel skips the rest and reads no weights for a group
without rows.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..platform import interpret_mode
from ..tiles import LANE, STREAM_TILE_BYTES, ceil_to
from .kernel import gmm_p


class Layout(NamedTuple):
    """Where the rows of each group go in the tiled layout."""

    row_start: jnp.ndarray     # (G,) first row of each group
    tile_group: jnp.ndarray    # (n_tiles,) group of each row tile
    n_active: jnp.ndarray      # (1,) tiles that hold rows
    tm: int                    # rows per tile
    rows: int                  # n_tiles * tm


def row_tile(max_rows: int) -> int:
    """Rows per tile: 16 (the bf16 sublane granule) for the few rows of
    a decode step, 128 (the MXU's depth) for a prefill chunk's."""
    return 16 if max_rows <= 512 else 128


def layout(group_sizes: jnp.ndarray, max_rows: int,
           tm: Optional[int] = None) -> Layout:
    """The tiled layout of ``group_sizes`` (G,) whose sum is at most
    ``max_rows``: group ``g`` starts at ``row_start[g]``, a multiple of
    ``tm``, and takes ``ceil(size / tm)`` tiles."""
    g = group_sizes.shape[0]
    tm = tm or row_tile(max_rows)
    n_tiles = -(-max_rows // tm) + g
    tiles = -(-group_sizes.astype(jnp.int32) // tm)            # (G,)
    ends = jnp.cumsum(tiles)
    row_start = (ends - tiles) * tm
    tile_group = jnp.searchsorted(ends, jnp.arange(n_tiles, dtype=jnp.int32),
                                  side="right")
    tile_group = jnp.minimum(tile_group, g - 1).astype(jnp.int32)
    return Layout(row_start.astype(jnp.int32), tile_group,
                  ends[-1:].astype(jnp.int32), tm, n_tiles * tm)


def k_block(k: int, n: int, itemsize: int, weights: int) -> int:
    """K rows of a weight block: full (bk, N) rows of the weight, about
    :data:`STREAM_TILE_BYTES` over the ``weights`` streamed together,
    dividing K so no block is padded; all of K when K is not a multiple
    of the lane width."""
    if k % LANE:
        return k
    cap = max(LANE, STREAM_TILE_BYTES // weights // (n * itemsize))
    best = LANE
    for d in range(LANE, min(k, cap) + 1, LANE):
        if k % d == 0:
            best = d
    return best


def gmm_tiles(x: jnp.ndarray, w: jnp.ndarray, lay: Layout,
              w_up: Optional[jnp.ndarray] = None,
              layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``x`` (lay.rows, K) in the tiled layout times each tile's group's
    ``w`` (G, K, N); with ``w_up``, ``silu(x @ w) * (x @ w_up)``.  Rows
    of tiles past ``lay.n_active`` are left unwritten.  With ``layer``
    the weights are a stack (L, G, K, N) and layer ``layer`` is used,
    read in place."""
    if layer is None:
        w, w_up, layer = w[None], None if w_up is None else w_up[None], 0
    k, n = w.shape[2], w.shape[3]
    bk = k_block(k, n, w.dtype.itemsize, 1 if w_up is None else 2)
    return gmm_p(x, w.astype(x.dtype), lay.tile_group, lay.n_active,
                 jnp.asarray(layer, jnp.int32).reshape(1),
                 None if w_up is None else w_up.astype(x.dtype),
                 tm=lay.tm, bk=bk, interpret=interpret_mode())


def gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
        rhs_up: Optional[jnp.ndarray] = None,
        tm: Optional[int] = None) -> jnp.ndarray:
    """The grouped matmul of ``ref.gmm_ref`` on the kernel: ``lhs`` (M,
    K) with its rows sorted by group, ``group_sizes[g]`` rows each;
    rows past the last group come back zero."""
    m = lhs.shape[0]
    lay = layout(group_sizes, m, tm)
    sizes = group_sizes.astype(jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    r = jnp.arange(m, dtype=jnp.int32)
    group = jnp.searchsorted(jnp.cumsum(sizes), r, side="right")
    held = group < sizes.shape[0]
    g = jnp.minimum(group, sizes.shape[0] - 1)
    dest = jnp.where(held, lay.row_start[g] + r - starts[g], lay.rows)
    x = jnp.zeros((lay.rows, lhs.shape[1]), lhs.dtype).at[dest].set(
        lhs, mode="drop")
    y = gmm_tiles(x, rhs, lay, rhs_up)
    return jnp.where(held[:, None], y[jnp.minimum(dest, lay.rows - 1)], 0)
