"""The grouped matmul (``kernels/grouped_matmul``, interpret mode on the
CPU) against its ``ref.py``: ragged groups, empty groups, every row in
one group, rows past the last group, the gated form, several K blocks."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.grouped_matmul import gmm, gmm_ref, gmm_tiles, layout


def _operands(rng, m, k, n, g, dtype):
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((g, k, n)) / np.sqrt(k), dtype)
    wu = jnp.asarray(rng.standard_normal((g, k, n)) / np.sqrt(k), dtype)
    return lhs, w, wu


@pytest.mark.parametrize("sizes", [
    [3, 0, 5, 1], [0, 0, 9, 0], [9, 0, 0, 0], [0, 0, 0, 9], [0, 0, 0, 0],
    [17, 1, 2, 20]], ids=["ragged", "one-middle", "one-first", "one-last",
                          "none", "over-a-tile"])
@pytest.mark.parametrize("glu", [False, True], ids=["plain", "glu"])
def test_gmm_matches_ref(rng, sizes, glu):
    m = max(40, sum(sizes))
    lhs, w, wu = _operands(rng, m, 256, 128, 4, jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    up = wu if glu else None
    got = np.asarray(gmm(lhs, w, gs, up))
    want = np.asarray(gmm_ref(lhs, w, gs, up))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[sum(sizes):].any()          # rows past the groups


def test_gmm_bf16(rng):
    lhs, w, wu = _operands(rng, 48, 256, 128, 3, jnp.bfloat16)
    gs = jnp.asarray([20, 0, 28], jnp.int32)
    got = np.asarray(gmm(lhs, w, gs, wu), np.float32)
    want = np.asarray(gmm_ref(lhs, w, gs, wu), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_gmm_tiles_several_k_blocks(rng, monkeypatch):
    """K split over blocks accumulates in order; the weight block is
    sized from ``STREAM_TILE_BYTES``, shrunk here to force four."""
    from repro.kernels.grouped_matmul import ops
    monkeypatch.setattr(ops, "STREAM_TILE_BYTES", 128 * 128 * 4)
    lhs, w, wu = _operands(rng, 24, 512, 128, 3, jnp.float32)
    gs = jnp.asarray([5, 0, 19], jnp.int32)
    assert ops.k_block(512, 128, 4, 1) == 128
    np.testing.assert_allclose(np.asarray(gmm(lhs, w, gs)),
                               np.asarray(gmm_ref(lhs, w, gs)),
                               rtol=1e-5, atol=1e-5)


def test_layout_skips_empty_groups():
    lay = layout(jnp.asarray([5, 0, 19, 0], jnp.int32), 24, tm=16)
    assert lay.tm == 16 and lay.rows == (2 + 4) * 16
    np.testing.assert_array_equal(np.asarray(lay.row_start), [0, 16, 16, 48])
    assert int(lay.n_active[0]) == 3                 # 1 + 2 tiles
    np.testing.assert_array_equal(np.asarray(lay.tile_group)[:3], [0, 2, 2])


def test_gmm_tiles_leaves_inactive_tiles_alone(rng):
    """Only the active tiles are computed: a group without rows costs
    nothing, whatever its weights hold."""
    lhs, w, _ = _operands(rng, 16, 128, 128, 2, jnp.float32)
    w = w.at[1].set(jnp.nan)                         # never read
    lay = layout(jnp.asarray([16, 0], jnp.int32), 16, tm=16)
    x = jnp.zeros((lay.rows, 128), jnp.float32).at[:16].set(lhs)
    out = np.asarray(gmm_tiles(x, w, lay))[:16]
    np.testing.assert_allclose(out, np.asarray(lhs @ w[0]), rtol=1e-5,
                               atol=1e-5)
