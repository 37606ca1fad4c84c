"""Pallas kernels: shape/dtype sweeps, interpret=True vs the pure-jnp
oracles (ref.py), per the assignment's per-kernel requirement."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fast_act import ref as fa_ref
from repro.kernels.fast_act.ops import fast_act, fast_softmax
from repro.kernels.fused_matmul import ref as fm_ref
from repro.kernels.fused_matmul.ops import fused_matmul
from repro.kernels.tiles import LANE, ceil_to, pick_block, streams_weights
from repro.kernels.decode_attention import ref as da_ref
from repro.kernels.decode_attention.ops import decode_attention


# ---------------------------------------------------------------------------
# fused matmul + epilogue
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (32, 64, 48), (1, 128, 256),
                                   (100, 30, 17)])
@pytest.mark.parametrize("fn", [None, "relu", "tanh"])
@pytest.mark.parametrize("w_layout", ["io", "oi"])
def test_fused_matmul_sweep(m, k, n, fn, w_layout, rng):
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = rng.standard_normal((k, n) if w_layout == "io" else (n, k)) \
        .astype(np.float32) * 0.1
    b = rng.standard_normal(n).astype(np.float32) * 0.1
    want = fm_ref.fused_matmul_ref(x, w, b, None, None, fn=fn, fast=False,
                                   w_layout=w_layout, attrs={})
    got = fused_matmul(x, w, b, fn=fn, w_layout=w_layout, use_pallas=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


def test_fused_matmul_affine_epilogue(rng):
    """Folded-BN scale/offset applied in the kernel epilogue (paper P2+P3)."""
    x = rng.standard_normal((16, 32)).astype(np.float32)
    w = rng.standard_normal((32, 24)).astype(np.float32) * 0.1
    b = rng.standard_normal(24).astype(np.float32)
    s = rng.uniform(0.5, 1.5, 24).astype(np.float32)
    o = rng.standard_normal(24).astype(np.float32)
    want = fm_ref.fused_matmul_ref(x, w, b, s, o, fn="relu", fast=False,
                                   w_layout="io", attrs={})
    got = fused_matmul(x, w, b, s, o, fn="relu", use_pallas=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


def test_fused_matmul_higher_rank(rng):
    x = rng.standard_normal((2, 3, 8)).astype(np.float32)
    w = rng.standard_normal((8, 4)).astype(np.float32)
    got = fused_matmul(x, w, None, use_pallas=True)
    want = np.einsum("abk,kn->abn", x, w)
    np.testing.assert_allclose(want, np.asarray(got), rtol=2e-5, atol=2e-5)


def test_fused_matmul_bf16_operands_match_upcast(rng):
    """bf16 operands stay bf16 in the kernel (half the VMEM bytes, as
    tiles.block_vmem_bytes models) and match the f32-upcast reference
    to f32 summation order.

    Each bf16 product is exact in f32, but XLA's CPU backend runs a
    bf16×bf16→f32 dot through a different routine than the reference's
    f32×f32 dot, and the two add the K products in different orders —
    plain ``jnp.dot`` on the same operands differs the same way, with
    no Pallas involved.  So the bound is the one for reordering a sum
    of K terms in f32: K·eps·Σ|x·w| per element."""
    import jax.numpy as jnp

    k = 64
    x = jnp.asarray(rng.standard_normal((16, k)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((k, 32)) * 0.1, jnp.bfloat16)
    b = rng.standard_normal(32).astype(np.float32)
    got = fused_matmul(x, w, b, fn="relu", use_pallas=True)
    x32, w32 = np.asarray(x, np.float32), np.asarray(w, np.float32)
    want = fm_ref.fused_matmul_ref(
        x32, w32, b, None, None,
        fn="relu", fast=False, w_layout="io", attrs={})
    assert got.dtype == jnp.float32
    bound = k * np.finfo(np.float32).eps * (np.abs(x32) @ np.abs(w32))
    assert np.all(np.abs(np.asarray(want) - np.asarray(got)) <= bound)


@pytest.mark.parametrize("m,k,n,nk", [(1, 16384, 256, 4), (5, 2048, 384, 1)],
                         ids=["nk4", "nk1"])
@pytest.mark.parametrize("w_layout", ["io", "oi"])
def test_fused_matmul_streaming_geometry(m, k, n, nk, w_layout, rng):
    """Few rows take the weight-streaming block of ``pick_block``: one
    row block and weight tiles of megabytes, over several K chunks or
    one; the result is the reference's to f32 summation order."""
    assert streams_weights(m)
    bm, bk, bn = pick_block(m, k, n)
    assert (bm, ceil_to(k, LANE) // bk) == (8, nk)
    x = rng.standard_normal((m, k)).astype(np.float32)
    w = (rng.standard_normal((k, n) if w_layout == "io" else (n, k))
         / np.sqrt(k)).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32) * 0.1
    want = fm_ref.fused_matmul_ref(x, w, b, None, None, fn="relu", fast=False,
                                   w_layout=w_layout, attrs={})
    got = fused_matmul(x, w, b, fn="relu", w_layout=w_layout, use_pallas=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=2e-5, atol=2e-5)


def test_dense_weight_not_padded_per_call():
    """VGG19's first dense layer at batch 1 on the pallas target, with
    the weights as arguments of the program (``embed_weights=False``):
    the weight reaches the kernel as it is, never padded or copied into
    another shape on each call.  The program is lowered as
    ``JitExecutable`` lowers it, from shapes alone, with the weight in
    the ``oi`` layout the layout pass gives a batch-1 dense node."""
    import re
    from repro.core import select_kernels
    from repro.core.graph import Graph
    from repro.core.lowering import execute_graph

    k, n = 25088, 4096
    g = Graph()
    g.add_input("input", (k,))
    kernel = g.add_param("fc/kernel", np.broadcast_to(np.float32(0), (n, k)))
    bias = g.add_param("fc/bias", np.zeros(n, np.float32))
    g.add_node("dense", "fc", ["input"], attrs={"kernel_layout": "oi"},
               params={"kernel": kernel, "bias": bias})
    g.outputs = ["fc:out"]
    selection = select_kernels(g, batch_size=1, target="pallas")
    assert selection["fc"].geometry == "stream"
    params = {name: jax.ShapeDtypeStruct(v.shape, jnp.float32)
              for name, v in g.params.items()}

    def program(p, x):
        return execute_graph(g, {"input": x}, p, target="pallas",
                             batch_size=1, selection=selection)

    text = jax.jit(program).lower(
        params, jax.ShapeDtypeStruct((1, k), jnp.float32)).as_text("hlo")
    big = []
    for line in text.splitlines():
        found = re.search(r"= f32\[([\d,]+)\]\S* ([\w-]+)\(", line)
        if found:
            dims = tuple(int(d) for d in found.group(1).split(","))
            if np.prod(dims) >= n * k:
                big.append((dims, found.group(2)))
    assert ((n, k), "parameter") in big
    # weight-sized values are the parameter and the kernel's own view of
    # it: never another shape, never a pad, copy or transpose
    assert {dims for dims, _ in big} == {(n, k)}, big
    assert not {op for _, op in big} & {"pad", "copy", "transpose"}, big


# ---------------------------------------------------------------------------
# fast activations (paper §3.4)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fn", ["exp", "tanh", "sigmoid"])
@pytest.mark.parametrize("shape", [(16,), (4, 33), (2, 3, 5)])
def test_fast_act_kernel_matches_ref(fn, shape, rng):
    x = rng.standard_normal(shape).astype(np.float32) * 3
    want = fa_ref.FAST[fn](x)
    got = fast_act(jnp.asarray(x), fn, use_pallas=True)
    # identical math; one-ULP drift allowed (FMA contraction differs
    # between the interpret-mode kernel and the jnp oracle)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=5e-5, atol=1e-6)


def test_schraudolph_accuracy_envelope(rng):
    """Paper cites ~4% max relative error for the exp bit-trick."""
    x = rng.uniform(-10, 10, 4096).astype(np.float32)
    approx = np.asarray(fa_ref.schraudolph_exp(x))
    exact = np.exp(x)
    rel = np.abs(approx - exact) / exact
    assert rel.max() < 0.05


def test_cf_tanh_accuracy():
    x = np.linspace(-6, 6, 4001, dtype=np.float32)
    approx = np.asarray(fa_ref.cf_tanh(x))
    exact = np.tanh(x)
    assert np.max(np.abs(approx - exact)) < 2e-3
    assert np.all(np.abs(approx) <= 1.0 + 1e-6)


def test_fast_softmax_normalized(rng):
    x = rng.standard_normal((8, 64)).astype(np.float32) * 5
    y = np.asarray(fast_softmax(jnp.asarray(x)))
    np.testing.assert_allclose(y.sum(-1), 1.0, atol=1e-3)
    exact = np.asarray(jax.nn.softmax(jnp.asarray(x), axis=-1))
    assert np.max(np.abs(y - exact)) < 0.02


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,h,hkv,d,s", [(2, 4, 2, 16, 64), (1, 8, 1, 32, 100),
                                         (3, 6, 6, 8, 48)])
def test_decode_attention_sweep(b, h, hkv, d, s, rng):
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    lengths = np.asarray([s - i * 7 for i in range(b)], np.int32).clip(1)
    want = da_ref.decode_attention_ref(q, k, v, jnp.asarray(lengths))
    got = decode_attention(q, k, v, jnp.asarray(lengths), use_pallas=True)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-4)


def test_decode_attention_masks_beyond_length(rng):
    """Rows past `length` must not affect the output."""
    b, h, hkv, d, s = 1, 2, 1, 8, 32
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, s, hkv, d)).astype(np.float32)
    lengths = jnp.asarray([10], jnp.int32)
    out1 = decode_attention(q, k, v, lengths, use_pallas=True)
    k2, v2 = k.copy(), v.copy()
    k2[:, 10:], v2[:, 10:] = 99.0, -99.0
    out2 = decode_attention(q, k2, v2, lengths, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-6)
