"""Compile guard: every Pallas kernel compiled for a described TPU v5e
at real widths, with no chip attached.

Interpret mode checks a kernel's arithmetic but not what the chip's
compiler accepts (block shapes against the (8, 128) tiling, VMEM use).
Here each kernel is lowered and compiled for one chip of a ``v5e:2x2``
topology and its program must hold a ``tpu_custom_call``.  The
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


def test_fused_matmul_q8_compiles(one_chip):
    text = _compile(
        lambda x, w, d, b: fused_matmul_q8_p(x, w, d, b, fn="tanh",
                                             block=BLOCK, interpret=False),
        one_chip, ((M, K), jnp.int8), ((K, N), jnp.int8),
        ((N,), jnp.float32), ((N,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("fn", ["tanh", "sigmoid", "exp"])
def test_fast_act_compiles(one_chip, dtype, fn):
    text = _compile(lambda x: fast_act_2d(x, fn, interpret=False),
                    one_chip, ((1024, K), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fast", [False, True], ids=["exact", "fast"])
def test_decode_attention_compiles(one_chip, fast):
    b, h, hkv, d, s = 4, 40, 8, 128, 4096
    text = _compile(
        lambda q, k, v, n: decode_attention_p(q, k, v, n, fast=fast,
                                              interpret=False),
        one_chip, ((b, h, d), jnp.float32), ((b, s, hkv, d), jnp.float32),
        ((b, s, hkv, d), jnp.float32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text
