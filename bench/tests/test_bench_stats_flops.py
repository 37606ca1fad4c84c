"""Order statistics over all samples, and the operation and byte
counts against the published figures."""

import numpy as np
import pytest

from bench import flops, stats
from bench.tests import tiny


@pytest.mark.parametrize("n", [1, 2, 7, 200, 1001])
def test_percentile_is_numpy_linear_over_all_samples(n):
    v = list(np.random.default_rng(n).exponential(size=n))
    for q in (0, 50, 95, 99, 100):
        assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 95) is None


def test_vgg19_counts_match_the_paper():
    cfg = tiny._read("bench/configs/vgg19.json")
    # Simonyan & Zisserman, configuration E: 144 M parameters; 19.5 G
    # multiply-adds in the convolutions at 224x224
    assert flops.cnn_params(cfg) == 143_667_240
    assert flops.cnn_macs(cfg, "conv") == 19_508_428_800
    assert flops.cnn_macs(cfg, "dense") == 123_633_664
    f, b = flops.dense_cost(cfg, batch=1)
    assert f == 2 * 123_633_664
    assert 494e6 < b < 495e6          # float32 weights and biases


def test_qwen_layer_params_match_the_served_model():
    import jax
    from repro.models import get_model
    cfg = tiny._read("bench/configs/qwen2.5-14b.json")
    mod = tiny.cell("qwen14b.chat", {}).config_module()
    arch = mod.arch(dict(cfg, num_hidden_layers=1))
    shapes = jax.eval_shape(
        lambda: get_model(arch).init(jax.random.PRNGKey(0)))
    per_layer = sum(int(np.prod(a.shape))
                    for a in jax.tree.leaves(shapes["layers"]))
    assert flops.lm_layer_params(cfg) == per_layer == 275_268_608
    assert flops.lm_weight_bytes(cfg) == 2 * (
        6 * per_layer + 5120 * 152064)


def test_decode_cost_counts_live_rows_only():
    cfg = tiny._read("bench/configs/qwen2.5-14b.json")
    f1, b1 = flops.lm_decode_cost(cfg, [100], slots=32)
    f2, b2 = flops.lm_decode_cost(cfg, [100, 300], slots=32)
    kv_row = 6 * 2 * 8 * 128 * 2
    assert b2 - b1 == kv_row * 300
    assert f2 - f1 == flops.lm_token_flops(cfg, 300, head=True)
    # prefill of n tokens at once: the per-token operations summed
    n = 5
    want = sum(flops.lm_token_flops(cfg, p + 1, head=False)
               for p in range(n)) + 2 * 5120 * 152064
    assert flops.lm_prefill_flops(cfg, 0, n) == pytest.approx(want)
