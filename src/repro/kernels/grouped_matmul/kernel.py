"""Pallas TPU kernel: a grouped matmul that reads only the weights of
groups with rows.

The rows arrive in a tiled layout (``ops.layout``): each group's rows
start on a ``tm``-row tile boundary, so every row tile belongs to one
group, named by ``tile_group``, and only the first ``n_active`` tiles
hold rows.  The grid walks (row tile, K block); a step fetches its
group's (bk, N) weight block, full rows of the (G, K, N) weight, so
each fetch is one contiguous stretch of HBM.  Steps past the active
tiles map every block to the last active step's blocks, which the
pipeline does not fetch again, and skip their compute: a group with no
rows costs no weight bytes, and the output tiles past the active ones
are never written.

The weights come as a stack over layers, (L, G, K, N), with the layer
to use as a scalar: a layer scan hands the kernel the whole stack, so no
layer's experts are ever copied out of it.

With a second weight the kernel is the gated half of an expert MLP:
``silu(x @ w) * (x @ w_up)``, both accumulated in float32 in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Scoped VMEM the kernel may use: two double-buffered weight tiles of
#: up to ``ops.TILE_BYTES`` each, the row tiles and the accumulators.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _kernel(tile_group_ref, n_active_ref, layer_ref, x_ref, *refs, nk: int,
            glu: bool):
    del tile_group_ref, layer_ref
    if glu:
        w_ref, wu_ref, o_ref, acc_ref, accu_ref = refs
    else:
        w_ref, o_ref, acc_ref = refs
    i, k = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_active_ref[0])
    def _tile():
        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            if glu:
                accu_ref[...] = jnp.zeros_like(accu_ref)

        x = x_ref[...]
        acc_ref[...] += jnp.dot(x, w_ref[...],
                                preferred_element_type=jnp.float32)
        if glu:
            accu_ref[...] += jnp.dot(x, wu_ref[...],
                                     preferred_element_type=jnp.float32)

        @pl.when(k == nk - 1)
        def _store():
            y = acc_ref[...]
            if glu:
                y = jax.nn.silu(y) * accu_ref[...]
            o_ref[...] = y.astype(o_ref.dtype)


def gmm_p(x: jnp.ndarray, w: jnp.ndarray, tile_group: jnp.ndarray,
          n_active: jnp.ndarray, layer: jnp.ndarray, w_up=None, *, tm: int,
          bk: int, interpret: bool = False) -> jnp.ndarray:
    """x: (n_tiles·tm, K) in the tiled layout; w (and w_up): (L, G, K,
    N); tile_group: (n_tiles,) int32; n_active, layer: (1,) int32.
    Returns the (n_tiles·tm, N) rows, written for the active tiles
    only."""
    mp, kdim = x.shape
    n = w.shape[-1]
    n_tiles, nk = mp // tm, kdim // bk
    glu = w_up is not None

    def step(i, k, n_active):
        """The step whose blocks step (i, k) uses: itself, or past the
        active tiles the last active step."""
        last = jnp.maximum(n_active[0] - 1, 0)
        past = i > last
        return jnp.where(past, last, i), jnp.where(past, nk - 1, k)

    def x_map(i, k, tg, na, ly):
        i, k = step(i, k, na)
        return i, k

    def w_map(i, k, tg, na, ly):
        i, k = step(i, k, na)
        return ly[0], tg[i], k, 0

    def o_map(i, k, tg, na, ly):
        return step(i, k, na)[0], 0

    w_spec = pl.BlockSpec((None, None, bk, n), w_map)
    scratch = [pltpu.VMEM((tm, n), jnp.float32)] * (2 if glu else 1)
    call = pl.pallas_call(
        functools.partial(_kernel, nk=nk, glu=glu),
        out_shape=jax.ShapeDtypeStruct((mp, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, nk),
            in_specs=[pl.BlockSpec((tm, bk), x_map)]
            + [w_spec] * (2 if glu else 1),
            out_specs=pl.BlockSpec((tm, n), o_map),
            scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="moe_gmm",
    )
    args = (tile_group, n_active, layer, x, w) + ((w_up,) if glu else ())
    return call(*args)
