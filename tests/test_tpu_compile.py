"""Compile guard: every Pallas kernel compiled for a described TPU v5e
at real widths, with no chip attached.

Interpret mode checks a kernel's arithmetic but not what the chip's
compiler accepts (block shapes against the (8, 128) tiling, VMEM use).
Here each kernel is lowered and compiled for one chip of a ``v5e:2x2``
topology and its program must hold a ``tpu_custom_call`` under the
kernel's own name (``pallas_call(name=...)``), which is the name a
profiler trace gives its operations.  The serving decode program's
sampling (``repro.serve.sample_rows``, plain XLA) is compiled here too,
at both served vocabularies.  The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all
import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_p
from repro.kernels.fast_act.kernel import fast_act_2d
from repro.kernels.fused_matmul.kernel import fused_matmul_p, fused_matmul_q8_p
from repro.kernels.tiles import STREAM_MAX_ROWS, pick_block

# qwen2.5-14b widths: the FFN matmul, and decode attention over 40 query
# and 8 KV heads of 128 lanes against a 4096-token cache.
M, K, N = 256, 5120, 13824
BLOCK = (256, 512, 256)


@pytest.fixture(scope="module")
def topo():
    # The TPU library reads this when it loads; unset, it writes its
    # compiler logs to a fixed directory outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compilation_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one; keep the
    cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("w_layout", ["io", "oi"])
def test_fused_matmul_compiles(one_chip, dtype, w_layout):
    w_shape = (K, N) if w_layout == "io" else (N, K)
    text = _compile(
        lambda x, w, b: fused_matmul_p(x, w, b, fn="relu", w_layout=w_layout,
                                       block=BLOCK, interpret=False),
        one_chip, ((M, K), dtype), (w_shape, dtype), ((N,), jnp.float32))
    assert "tpu_custom_call" in text
    assert "%fused_matmul" in text and "%fused_matmul_q8" not in text


@pytest.mark.parametrize("m", [8, STREAM_MAX_ROWS])
def test_fused_matmul_streaming_block_compiles(one_chip, m):
    """VGG19's first dense layer (K 25088, N 4096, weight ``oi``) at few
    rows, with the weight-streaming block ``pick_block`` gives it, at
    ``highest`` precision as the benchmark runs it: the chip's compiler
    accepts the multi-megabyte tiles, their six-pass temporaries and
    the double buffers within the default scoped VMEM."""
    k, n = 25088, 4096
    block = pick_block(m, k, n)
    with jax.default_matmul_precision("highest"):
        text = _compile(
            lambda x, w, b: fused_matmul_p(x, w, b, fn="relu", w_layout="oi",
                                           block=block, interpret=False),
            one_chip, ((m, k), jnp.float32), ((n, k), jnp.float32),
            ((n,), jnp.float32))
    assert text.count("tpu_custom_call") == 1
    assert "%fused_matmul" in text


def test_fused_matmul_q8_compiles(one_chip):
    text = _compile(
        lambda x, w, d, b: fused_matmul_q8_p(x, w, d, b, fn="tanh",
                                             block=BLOCK, interpret=False),
        one_chip, ((M, K), jnp.int8), ((K, N), jnp.int8),
        ((N,), jnp.float32), ((N,), jnp.float32))
    assert "tpu_custom_call" in text
    assert "%fused_matmul_q8" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("fn", ["tanh", "sigmoid", "exp"])
def test_fast_act_compiles(one_chip, dtype, fn):
    text = _compile(lambda x: fast_act_2d(x, fn, interpret=False),
                    one_chip, ((1024, K), dtype))
    assert "tpu_custom_call" in text
    assert "%fast_act" in text


@pytest.mark.parametrize("fast", [False, True], ids=["exact", "fast"])
def test_decode_attention_compiles(one_chip, fast):
    b, h, hkv, d, s = 4, 40, 8, 128, 4096
    text = _compile(
        lambda q, k, v, n: decode_attention_p(q, k, v, n, fast=fast,
                                              interpret=False),
        one_chip, ((b, h, d), jnp.float32), ((b, s, hkv, d), jnp.float32),
        ((b, s, hkv, d), jnp.float32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text
    assert "%decode_attention" in text


@pytest.mark.parametrize("tokens", [32, 256], ids=["decode", "chunk"])
def test_moe_gmm_compiles(one_chip, tokens):
    """DeepSeek-V3's expert MLP over its 8 held experts (d 7168, expert
    width 2048, bf16; the stack of 4 expert layers, one chosen by index)
    in the tiled layout of a 32-slot decode step and a 256-token prefill
    chunk: the gated kernel and the down projection, with the weight
    blocks ``ops.k_block`` gives them, within the kernel's scoped
    VMEM."""
    from repro.kernels.grouped_matmul import ops
    from repro.kernels.grouped_matmul.kernel import gmm_p
    layers, g, d, f = 4, 8, 7168, 2048
    tm = ops.row_tile(tokens * 8)
    rows = (-(-tokens * 8 // tm) + g) * tm
    tiles = rows // tm

    def expert_mlp(x, wg, wu, wo, tg, na, ly):
        h = gmm_p(x, wg, tg, na, ly, wu, tm=tm,
                  bk=ops.k_block(d, f, 2, 2), interpret=False)
        return gmm_p(h, wo, tg, na, ly, tm=tm, bk=ops.k_block(f, d, 2, 1),
                     interpret=False)

    stack = (layers, g, d, f)
    text = _compile(expert_mlp, one_chip, ((rows, d), jnp.bfloat16),
                    (stack, jnp.bfloat16), (stack, jnp.bfloat16),
                    ((layers, g, f, d), jnp.bfloat16), ((tiles,), jnp.int32),
                    ((1,), jnp.int32), ((1,), jnp.int32))
    assert text.count("tpu_custom_call") == 2
    assert "%moe_gmm" in text


@pytest.mark.parametrize("vocab", [152064, 129280], ids=["qwen", "dsv3"])
def test_sample_rows_compiles(one_chip, vocab):
    """The decode program's sampling over a 32-slot step's float32
    logits: the argmax, and the categorical draw behind a conditional
    (both branches compiled; the chip picks one)."""
    from repro.serve import sample_rows

    def sample(logits, temps, n):
        key = jax.random.fold_in(jax.random.PRNGKey(0), n)
        return sample_rows(logits, temps, key)

    text = _compile(sample, one_chip, ((32, vocab), jnp.float32),
                    ((32,), jnp.float32), ((), jnp.uint32))
    assert "conditional" in text
