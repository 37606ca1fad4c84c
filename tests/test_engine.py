"""Serving: continuous batching, determinism, norm-fold."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.inference import fold_norms
from repro.models import get_model
from repro.serve import Request, Scheduler, SchedulerOptions


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2.5-14b", smoke=True)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def test_fold_norms_preserves_logits(setup):
    cfg, m, params = setup
    batch = {"tokens": jnp.arange(16, dtype=jnp.int32)[None, :] % cfg.vocab}
    l0, _ = m.forward(params, batch)
    folded, rep = fold_norms(cfg, params)
    l1, _ = m.forward(folded, batch)
    assert rep["folds"] > 0
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=2e-2, atol=2e-2)
    # gammas zeroed
    assert float(jnp.abs(folded["layers"]["ln1"]).max()) == 0.0


def test_fold_norms_moe():
    cfg = get_config("deepseek-v3-671b", smoke=True)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.arange(16, dtype=jnp.int32)[None, :] % cfg.vocab}
    l0, _ = m.forward(params, batch)
    folded, rep = fold_norms(cfg, params)
    l1, _ = m.forward(folded, batch)
    assert rep["folds"] >= 7
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("arch", ["qwen2.5-14b", "deepseek-v3-671b"])
def test_fold_norms_donates_in_place(arch):
    """With ``donate`` each folded matrix takes its original's buffer:
    the originals are deleted, nothing else is, and the folded model
    computes what the unfolded one does."""
    cfg = get_config(arch, smoke=True)
    m = get_model(cfg)
    batch = {"tokens": jnp.arange(16, dtype=jnp.int32)[None, :] % cfg.vocab}
    params = m.init(jax.random.PRNGKey(0))
    l0, _ = m.forward(params, batch)
    keep = jax.tree.map(jnp.copy, params)
    folded, rep = fold_norms(cfg, params, donate=True)
    assert rep["donated"]
    gone = [p for p in jax.tree.leaves(params) if p.is_deleted()]
    assert len(gone) == rep["folds"]
    assert params["head"].is_deleted() and not folded["head"].is_deleted()
    assert not params["embed"].is_deleted()
    l1, _ = m.forward(folded, batch)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                               rtol=5e-2, atol=5e-2)
    again, _ = fold_norms(cfg, keep)             # without donation
    assert not any(p.is_deleted() for p in jax.tree.leaves(keep))
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(folded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serve_hands_the_weights_over(setup):
    """``repro.serve`` folds the executable's weights in place: the
    originals are gone and the executable keeps the folded ones."""
    import repro
    cfg, m, params = setup
    exe = repro.compile(cfg, repro.CompileOptions(target="engine"),
                        params=jax.tree.map(jnp.copy, params))
    head = exe.params["head"]
    sched = repro.serve(exe, repro.SchedulerOptions(slots=1, max_len=32))
    try:
        assert head.is_deleted()
        assert exe.params is sched.params
        assert sched.fold_report["donated"]
        batch = {"tokens": jnp.arange(8, dtype=jnp.int32)[None, :]}
        np.testing.assert_allclose(
            np.asarray(exe(**batch)["logits"]),
            np.asarray(m.forward(params, batch)[0]), rtol=5e-2, atol=5e-2)
    finally:
        sched.shutdown()


def _scheduler(m, params, slots):
    return Scheduler(m, params, SchedulerOptions(slots=slots, max_len=48))


def test_engine_drains_queue(setup):
    cfg, m, params = setup
    sched = _scheduler(m, params, 2)
    for i in range(5):
        sched.submit(Request(uid=i, prompt=np.arange(4 + i) % cfg.vocab,
                             max_new_tokens=5))
    done = sched.run()
    assert sorted(c.uid for c in done) == list(range(5))
    assert all(len(c.tokens) == 5 for c in done)


def test_batched_equals_solo(setup):
    cfg, m, params = setup
    prompt = np.arange(6) % cfg.vocab
    solo = _scheduler(m, params, 1)
    solo.submit(Request(uid=0, prompt=prompt, max_new_tokens=6))
    want = solo.run()[0].tokens

    crowd = _scheduler(m, params, 3)
    for i in range(4):
        crowd.submit(Request(uid=i, prompt=prompt if i == 2 else
                             (np.arange(3 + i) * 7) % cfg.vocab,
                             max_new_tokens=6))
    got = [c for c in crowd.run() if c.uid == 2][0].tokens
    assert want == got


def test_eos_stops_generation(setup):
    cfg, m, params = setup
    sched = _scheduler(m, params, 1)
    # Find the first greedy token, then use it as EOS for a second run.
    sched.submit(Request(uid=0, prompt=np.arange(5) % cfg.vocab,
                         max_new_tokens=4))
    first = sched.run()[0].tokens
    again = _scheduler(m, params, 1)
    again.submit(Request(uid=0, prompt=np.arange(5) % cfg.vocab,
                         max_new_tokens=32, eos_id=int(first[1])))
    out = again.run()[0].tokens
    assert out[-1] == first[1] and len(out) <= 32


def test_engine_cache_donation_structure(setup):
    """After many steps the cache pytree keeps its structure/shape."""
    cfg, m, params = setup
    sched = _scheduler(m, params, 2)
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=12))
    sched.run()
    assert sched.slot_manager.cache["c1"].shape[1] == 2   # slots preserved
