"""DeepSeek-V3 served through the normal path against the plain
reference of its benchmark configuration (``bench/configs/deepseek-v3.py``,
loaded by path), at a CPU size in float32 on seeded weights; and the
expert layer's routing and dropless share on their own."""

import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import get_model
from repro.models import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "bench", "configs", "deepseek-v3")

#: The benchmark configuration at a CPU size: every key the reference
#: and ``arch`` read, widths cut, two layers of experts after one dense
#: layer, 4 of 16 experts held (ids 4-7) in 4 groups of which 2 are kept.
SMALL = {"hidden_size": 64, "num_attention_heads": 4,
         "q_lora_rank": 32, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
         "moe_intermediate_size": 32, "vocab_size": 256,
         "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "n_routed_experts": 4, "held_expert_ids": [4, 8],
         "published": {"n_routed_experts": 16}, "n_group": 4,
         "topk_group": 2, "num_experts_per_tok": 4,
         "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                          "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 32,
                          "type": "yarn"},
         "torch_dtype": "float32", "activation_dtype": "float32"}


@pytest.fixture(scope="module")
def ref():
    sys_path = __import__("sys").path
    if ROOT not in sys_path:
        sys_path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location("dsv3_ref", CONFIG + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def setup(ref):
    with open(CONFIG + ".json") as f:
        cfg = json.load(f)
    cfg.update(SMALL)
    w = ref.make_weights(cfg, jax.random.PRNGKey(7))
    arch = ref.arch(cfg)
    return cfg, w, arch, get_model(arch)


def _ref_logits(ref, cfg, w, tokens):
    h, _ = ref.hidden(cfg, w, jnp.asarray(tokens))
    return np.asarray(h @ w["head"].astype(jnp.float32))


def _tokens(n, seed=3):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


def test_whole_prefill_then_decode_matches_reference(ref, setup):
    cfg, w, _, m = setup
    toks = _tokens(28)
    want = _ref_logits(ref, cfg, w, toks)
    s = 20
    lg, cache = m.prefill(w, {"tokens": jnp.asarray(toks[None, :s])},
                          m.init_cache(1, 32))
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[s - 1],
                               rtol=2e-4, atol=2e-4)
    for t in range(s, len(toks)):
        lg, cache = m.decode_step(w, cache, jnp.asarray(toks[None, t:t + 1]))
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[t],
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"position {t}")


def test_chunked_prefill_then_decode_matches_reference(ref, setup):
    """Three chunks of 8, the last one padded, then decoding."""
    cfg, w, _, m = setup
    toks = _tokens(27, seed=4)
    want = _ref_logits(ref, cfg, w, toks)
    s = 21
    cache = m.init_cache(1, 32)
    step = jax.jit(lambda p, t, c, a, n: m.prefill_chunk(p, t, c, a, n,
                                                         stats=True))
    for start in range(0, s, 8):
        n = min(8, s - start)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :n] = toks[start:start + n]
        lg, cache, counts = step(w, jnp.asarray(chunk), cache,
                                 jnp.int32(start), jnp.int32(n))
        assert 0 <= int(counts[0]) <= 8 * 4 * 2 and int(counts[1]) <= 4 * 2
    assert int(cache["pos"][0]) == s
    np.testing.assert_allclose(np.asarray(lg[0, 0]), want[s - 1],
                               rtol=2e-4, atol=2e-4)
    for t in range(s, len(toks)):
        lg, cache, _ = m.decode_step(w, cache,
                                     jnp.asarray(toks[None, t:t + 1]),
                                     stats=True)
        np.testing.assert_allclose(np.asarray(lg[0, 0]), want[t],
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"position {t}")


def test_every_served_position_is_compared(ref, setup):
    """``gaps`` is ``served_gaps`` in window form, and a served token
    that is the reference's best reads 0 while any other reads more;
    ``control_gaps`` likewise over the int8 control's tokens."""
    cfg, w, _, _ = setup
    toks = jnp.asarray(_tokens(24, seed=5))
    h, _ = ref.hidden(cfg, w, toks)
    best = jnp.argmax(h @ w["head"].astype(jnp.float32), -1)
    other = (best + 1) % cfg["vocab_size"]
    assert np.allclose(ref.served_gaps(cfg, w, toks, best), 0.0, atol=1e-5)
    raw = np.asarray(ref.served_gaps(cfg, w, toks, other))
    assert (raw > 0).all()
    np.testing.assert_allclose(
        np.asarray(ref.gaps(cfg, w, toks, other)),
        np.asarray(ref.window_means(cfg, jnp.asarray(raw), toks)), rtol=1e-6)
    ctrl = np.asarray(ref.control_pick_gaps(cfg, w, toks))
    assert (ctrl > -1e-5).all()
    np.testing.assert_allclose(
        np.asarray(ref.control_gaps(cfg, w, toks)),
        np.asarray(ref.window_means(cfg, jnp.asarray(ctrl), toks)),
        rtol=1e-6)


@pytest.mark.parametrize("end", [5, 17, 31])
def test_window_means_sum_forward_up_to_the_end(ref, end):
    """Each position up to the sequence's last token reads the gaps of
    the window from it on, cut at that token, over the whole window;
    each position past it reads its own gap."""
    win = 4
    cfg = {"check": {"window": win}}
    seq = np.zeros(32, np.int32)
    seq[:end + 1] = 1 + np.arange(end + 1)
    raw = np.random.default_rng(end).random(32).astype(np.float32)
    got = np.asarray(ref.window_means(cfg, jnp.asarray(raw),
                                      jnp.asarray(seq)))
    want = [raw[p:min(p + win, end + 1)].sum() / win if p <= end else raw[p]
            for p in range(32)]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_window_dilutes_a_flip_not_a_drift(ref):
    """One wrong token in a window reads its gap over the window; a gap
    at every position reads that gap."""
    cfg = {"check": {"window": 128}}
    seq = jnp.ones(512, jnp.int32)
    flip = jnp.zeros(512).at[300].set(1.0)
    assert float(jnp.max(ref.window_means(cfg, flip, seq))) == 1.0 / 128
    drift = jnp.full(512, 0.2)
    assert abs(float(jnp.max(ref.window_means(cfg, drift, seq))) - 0.2) < 1e-6


def test_served_with_chunked_prefill(setup):
    """Through ``repro.compile``/``repro.serve`` the MLA model prefills
    by chunks (no fallback) and reports its expert counters."""
    import repro
    from repro.serve import Request
    cfg, w, arch, _ = setup
    exe = repro.compile(arch, repro.CompileOptions(target="engine"),
                        params=jax.tree.map(jnp.copy, w))
    sched = repro.serve(exe, repro.SchedulerOptions(
        slots=2, max_len=32, prefill_chunk=8))
    for i in range(3):
        sched.submit(Request(uid=i, prompt=_tokens(10 + 4 * i, seed=i),
                             max_new_tokens=4))
    done = sched.run()
    s = sched.summary()
    sched.shutdown()
    assert sorted(c.uid for c in done) == [0, 1, 2]
    assert s["chunked_prefill"]["enabled"]
    ex = s["experts"]
    assert ex["chunk"]["calls"] == sum(-(-(10 + 4 * i) // 8)
                                       for i in range(3))
    assert ex["decode"]["calls"] > 0
    for k in ("chunk", "decode"):
        assert 0 < ex[k]["experts_hit"] <= ex[k]["held_choices"]


def test_decode_program_returns_tokens_then_counters(setup):
    """The MoE decode program samples its tokens and still returns the
    expert counters as its trailing outputs; served through it, the
    same requests give the tokens and counter totals of a host argmax
    over the program's logits."""
    import repro
    from repro.serve import Request
    cfg, w, arch, _ = setup

    def serve(**kw):
        exe = repro.compile(arch, repro.CompileOptions(target="engine"),
                            params=jax.tree.map(jnp.copy, w))
        sched = repro.serve(exe, repro.SchedulerOptions(
            slots=2, max_len=32, prefill_chunk=8), **kw)
        outs = sched._decode.out_info
        for i in range(3):
            sched.submit(Request(uid=i, prompt=_tokens(10 + 4 * i, seed=i),
                                 max_new_tokens=4))
        toks = {c.uid: c.tokens for c in sched.run()}
        s = sched.summary()
        sched.shutdown()
        return outs, toks, s

    outs, toks, s = serve()
    _, tokens, *counts = outs
    assert (tokens.shape, tokens.dtype) == ((2,), jnp.int32)
    assert counts and all(c.dtype == jnp.int32 for c in counts)
    assert s["sampling"]["reads_per_step"] == 1.0

    def argmax(logits, temperature, *, uid, index):
        return int(np.argmax(np.asarray(logits)[0]))

    outs_h, toks_h, s_h = serve(sampler=argmax)
    assert outs_h[1].shape == (2, cfg["vocab_size"])     # the logits
    assert toks == toks_h
    assert s["experts"] == s_h["experts"]
    assert s["experts"]["decode"]["calls"] == s["decode_steps"] > 0


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------
def _moe_cfg(**kw):
    base = dataclasses.replace(
        get_config("deepseek-v3-671b", smoke=True), dtype="float32",
        param_dtype="float32", n_experts=8, n_group=4, topk_group=2,
        top_k=3)
    return dataclasses.replace(base, **kw)


def _moe_params(cfg, key=0, bias=0.3):
    p = moe.moe_init(jax.random.PRNGKey(key), cfg)
    p["router_bias"] = bias * jax.random.normal(jax.random.PRNGKey(key + 1),
                                                (cfg.n_experts,))
    return p


def _x(t, d, key=5):
    return jax.random.normal(jax.random.PRNGKey(key), (1, t, d))


def test_shares_add_up_to_the_whole_layer():
    """Four shares of two experts each, the shared expert counted once,
    give what the layer holding all eight gives."""
    whole = _moe_cfg()
    p = _moe_params(whole)
    x = _x(24, whole.d_model)
    want, _ = moe.moe_serve(p, whole, x)
    shared = moe._add_shared(p, whole, x, jnp.zeros_like(x))
    parts = []
    for r in range(4):
        cfg = dataclasses.replace(whole, n_held_experts=2, held_offset=2 * r)
        pr = dict(p, **{k: p[k][2 * r:2 * r + 2]
                        for k in ("wi_gate", "wi_up", "wo")})
        y, _ = moe.moe_serve(pr, cfg, x)
        parts.append(y)
    got = sum(parts) - 3 * shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_bias_moves_the_choice_not_the_weights():
    cfg = _moe_cfg()
    p = _moe_params(cfg, bias=0.0)
    x = _x(64, cfg.d_model)[0]
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (cfg.n_experts,))
    idx0, _, _ = moe._route(cfg, x, p["router"])
    idx1, w1, _ = moe._route(cfg, x, p["router"], bias)
    assert (np.sort(np.asarray(idx0), -1)
            != np.sort(np.asarray(idx1), -1)).any()
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen = jnp.take_along_axis(scores, idx1, axis=-1)
    want = chosen / jnp.sum(chosen, -1, keepdims=True) * cfg.routed_scale
    np.testing.assert_allclose(np.asarray(w1), np.asarray(want), rtol=1e-6)


def test_choices_stay_in_the_best_groups_and_weights_sum_to_scale():
    cfg = _moe_cfg()
    p = _moe_params(cfg)
    x = _x(64, cfg.d_model)[0]
    idx, w, _ = moe._route(cfg, x, p["router"], p["router_bias"])
    biased = jax.nn.sigmoid(x @ p["router"]) + p["router_bias"]
    per = cfg.n_experts // cfg.n_group
    g = np.asarray(biased).reshape(64, cfg.n_group, per)
    gscore = np.sort(g, -1)[..., -2:].sum(-1)
    best = np.argsort(-gscore, -1)[:, :cfg.topk_group]
    groups = np.asarray(idx) // per
    assert all(set(groups[t]) <= set(best[t]) for t in range(64))
    np.testing.assert_allclose(np.asarray(w).sum(-1), cfg.routed_scale,
                               rtol=1e-6)


@pytest.mark.parametrize("t", [1, 2, 7, 64])
def test_no_choice_is_dropped(t):
    """Every token-choice on a held expert is computed at every batch:
    the dropless layer equals each held expert applied to every token,
    weighted by its routing weight."""
    cfg = _moe_cfg(n_held_experts=4, held_offset=2)
    p = _moe_params(cfg)
    x = _x(t, cfg.d_model)
    got, counts = moe.moe_serve(p, cfg, x)
    idx, w, _ = moe._route(cfg, x[0], p["router"], p["router_bias"])
    want = moe._add_shared(p, cfg, x, jnp.zeros_like(x))[0]
    held = 0
    for j in range(cfg.top_k):
        for e in range(4):
            on = np.asarray(idx[:, j]) == cfg.held_offset + e
            held += int(on.sum())
            h = jax.nn.silu(x[0] @ p["wi_gate"][e]) * (x[0] @ p["wi_up"][e])
            want = want + jnp.where(on[:, None], w[:, j:j + 1] * (
                h @ p["wo"][e]), 0.0)
    assert int(counts[0]) == held
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
