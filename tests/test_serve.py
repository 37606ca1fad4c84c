"""repro.serve — continuous-batching scheduler: admission order,
eviction, rebatch-vs-sequential equivalence, metrics accounting.

The deterministic step-loop tests drive the scheduler with a scripted
token sampler and a fake clock, so every admission, eviction and
timestamp is asserted exactly; the equivalence tests run the real
greedy sampler against a sequential one-slot scheduler.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.configs import get_config
from repro.models import get_model
from repro.serve import (QueueFullError, Request, Scheduler,
                         SchedulerOptions, sample_rows)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2.5-14b", smoke=True)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


class ScriptedSampler:
    """Returns ``script[uid][index]`` regardless of logits; falls back
    to greedy-0 (token 1) when a request runs off its script."""

    def __init__(self, script):
        self.script = script
        self.calls = []

    def __call__(self, logits, temperature, *, uid, index):
        self.calls.append((uid, index))
        seq = self.script.get(uid, ())
        return seq[index] if index < len(seq) else 1


class TickClock:
    """Monotone integer clock: one tick per call."""

    def __init__(self):
        self.t = 0

    def __call__(self):
        self.t += 1
        return float(self.t)


def _sched(m, params, *, sampler=None, clock=None, **opts) -> Scheduler:
    extra = {}
    if clock is not None:
        extra["clock"] = clock
    return Scheduler(m, params,
                     SchedulerOptions(fold=False, **opts),
                     sampler=sampler, **extra)


# ---------------------------------------------------------------- options
def test_options_validation():
    with pytest.raises(ValueError):
        SchedulerOptions(slots=0)
    with pytest.raises(ValueError):
        SchedulerOptions(admission="lifo")
    with pytest.raises(ValueError):
        SchedulerOptions(max_queue=0)


def test_serve_rejects_graph_executables():
    from repro.core import ModelBuilder
    mb = ModelBuilder().seed(0)
    out = mb.dense(mb.input((4,)), 2)
    exe = repro.compile(mb.build([out]),
                        repro.CompileOptions(target="jit"))
    with pytest.raises(TypeError, match="target='engine'"):
        repro.serve(exe)


# -------------------------------------------------------------- admission
def test_fcfs_admission_order(setup):
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48,
                   sampler=ScriptedSampler({}), clock=TickClock())
    for uid in (3, 1, 2):                       # arrival order, not uid order
        sched.submit(Request(uid=uid, prompt=np.arange(4) % cfg.vocab,
                             max_new_tokens=2))
    sched.run()
    admitted = sorted(sched.request_metrics.values(),
                      key=lambda r: r.admitted_at)
    assert [r.uid for r in admitted] == [3, 1, 2]


def test_shortest_admission_order(setup):
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48, admission="shortest",
                   sampler=ScriptedSampler({}), clock=TickClock())
    for uid, plen in ((0, 10), (1, 3), (2, 6)):
        sched.submit(Request(uid=uid, prompt=np.arange(plen) % cfg.vocab,
                             max_new_tokens=2))
    sched.run()
    admitted = sorted(sched.request_metrics.values(),
                      key=lambda r: r.admitted_at)
    assert [r.uid for r in admitted] == [1, 2, 0]


def test_queue_admission_control(setup):
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48, max_queue=2)
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab))
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab))
    sched.submit(Request(uid=1, prompt=np.arange(4) % cfg.vocab))
    with pytest.raises(QueueFullError):
        sched.submit(Request(uid=2, prompt=np.arange(4) % cfg.vocab))
    assert sched.metrics.rejected == 1
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(Request(uid=3, prompt=np.arange(48) % cfg.vocab))


# --------------------------------------------------------------- eviction
def test_eos_evicts_slot_and_admits_next(setup):
    cfg, m, params = setup
    # uid 0 emits EOS (=7) as its second token; uid 1 runs to length
    sampler = ScriptedSampler({0: (5, 7), 1: (2, 3, 4)})
    sched = _sched(m, params, slots=1, max_len=48, sampler=sampler)
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=8, eos_id=7))
    sched.submit(Request(uid=1, prompt=np.arange(5) % cfg.vocab,
                         max_new_tokens=3, eos_id=7))
    done = sched.run()
    assert [c.uid for c in done] == [0, 1]      # finish order
    assert done[0].tokens == [5, 7]
    assert done[0].finish_reason == "eos"
    assert done[1].tokens == [2, 3, 4]
    assert done[1].finish_reason == "length"
    assert sched.request_metrics[0].finish_reason == "eos"


def test_eos_on_first_token_retires_at_admission(setup):
    cfg, m, params = setup
    sampler = ScriptedSampler({0: (7,)})
    sched = _sched(m, params, slots=2, max_len=48, sampler=sampler)
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=8, eos_id=7))
    done = sched.run()
    assert done[0].tokens == [7]
    assert done[0].finish_reason == "eos"
    assert sched.metrics.decode_steps == 0      # never needed a decode


def test_max_new_tokens_clamped_to_cache_budget(setup):
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=12)
    # prompt of 8 leaves a budget of 4 new tokens in a 12-wide cache
    sched.submit(Request(uid=0, prompt=np.arange(8) % cfg.vocab,
                         max_new_tokens=100))
    done = sched.run()
    assert len(done[0].tokens) == 4
    assert done[0].finish_reason == "length"


# ------------------------------------------------- rebatch vs sequential
def test_rebatched_matches_sequential_engine(setup):
    """Continuous batching with mid-flight arrivals must reproduce the
    sequential greedy decode token-for-token (acceptance criterion)."""
    cfg, m, params = setup
    prompts = {uid: (np.arange(3 + (uid % 4)) * (uid + 2)) % cfg.vocab
               for uid in range(10)}

    # sequential reference: a one-slot scheduler per request
    want = {}
    for uid, prompt in prompts.items():
        solo = _sched(m, params, slots=1, max_len=48)
        solo.submit(Request(uid=uid, prompt=prompt, max_new_tokens=6))
        want[uid] = solo.run()[0].tokens

    # concurrent: 8 slots, first 8 requests up front, 2 arrive mid-loop
    sched = _sched(m, params, slots=8, max_len=48)
    for uid in range(8):
        sched.submit(Request(uid=uid, prompt=prompts[uid],
                             max_new_tokens=6))
    sched.step()
    assert sched.num_active() == 8              # ≥8 concurrent requests
    sched.step()
    for uid in (8, 9):
        sched.submit(Request(uid=uid, prompt=prompts[uid],
                             max_new_tokens=6))
    done = {c.uid: c.tokens for c in sched.run()}
    assert done == want
    assert sched.summary()["completed"] == 10


# ---------------------------------------------------------------- metrics
def test_metrics_accounting(setup):
    cfg, m, params = setup
    clock = TickClock()
    sched = _sched(m, params, slots=2, max_len=48,
                   sampler=ScriptedSampler({}), clock=clock)
    for uid in range(2):
        sched.submit(Request(uid=uid, prompt=np.arange(4) % cfg.vocab,
                             max_new_tokens=3))
    done = sched.run()
    s = sched.summary()
    assert s["submitted"] == s["admitted"] == s["completed"] == 2
    assert s["total_new_tokens"] == sum(len(c.tokens) for c in done) == 6
    # both slots busy for both decode steps (1 prefill + 2 decode tokens)
    assert s["decode_steps"] == 2
    assert s["mean_batch_occupancy"] == 2.0
    assert s["peak_queue_depth"] == 2
    for uid in range(2):
        rm = sched.request_metrics[uid]
        assert rm.prompt_tokens == 4 and rm.new_tokens == 3
        assert (rm.submitted_at < rm.admitted_at < rm.first_token_at
                <= rm.finished_at)
        assert rm.ttft == rm.first_token_at - rm.submitted_at
        assert rm.queue_time == rm.admitted_at - rm.submitted_at
        assert rm.decode_tokens_per_s > 0
    assert sched.request_metrics[0].queue_depth_at_submit == 0
    assert sched.request_metrics[1].queue_depth_at_submit == 1


def test_pop_completions_streams(setup):
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48,
                   sampler=ScriptedSampler({}))
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2))
    sched.submit(Request(uid=1, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2))
    assert sched.pop_completions() == []
    while not sched.pop_completions():
        sched.step()
    # uid 0 drained exactly once; uid 1 still pending or drained later
    sched.run()
    rest = sched.pop_completions()
    assert [c.uid for c in rest] == [1]
    assert len(sched.done) == 2


# --------------------------------------------------- named multi-inputs
def test_request_named_multi_inputs():
    """A serve() request can carry the model signature's non-token
    inputs by name (audio frames here); they reach prefill verbatim
    and actually change the decode."""
    cfg = get_config("whisper-base", smoke=True)
    m = get_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    frames = np.random.default_rng(0).standard_normal(
        (cfg.n_frames, cfg.d_model)).astype(np.float32)   # batch-less

    sched = _sched(m, params, slots=1, max_len=32)
    batch = sched._prefill_batch(np.arange(4, dtype=np.int32)[None],
                                 {"frames": frames})
    assert sorted(batch) == ["frames", "tokens"]
    np.testing.assert_array_equal(np.asarray(batch["frames"][0]), frames)
    with pytest.raises(ValueError, match="expected"):
        sched._prefill_batch(np.arange(4, dtype=np.int32)[None],
                             {"frames": frames[: cfg.n_frames // 2]})

    # zeros vs real frames change the prefill logits...
    prompt = np.arange(4, dtype=np.int32)[None]
    logits_zero, _ = sched._prefill(
        params, sched._prefill_batch(prompt, None), m.init_cache(1, 32))
    logits_real, _ = sched._prefill(
        params, sched._prefill_batch(prompt, {"frames": frames}),
        m.init_cache(1, 32))
    assert not np.allclose(np.asarray(logits_zero), np.asarray(logits_real))

    # ...and a request carrying them runs end to end
    s = _sched(m, params, slots=1, max_len=32)
    s.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                     max_new_tokens=4, inputs={"frames": frames}))
    assert len(s.run()[0].tokens) == 4

    # names outside the model's signature — and wrong shapes — are
    # rejected at submit, before the request can enter the step loop
    sched2 = _sched(m, params, slots=1, max_len=32)
    with pytest.raises(ValueError, match="unknown inputs"):
        sched2.submit(Request(uid=1, prompt=np.arange(4) % cfg.vocab,
                              inputs={"patches": frames}))
    with pytest.raises(ValueError, match="expected"):
        sched2.submit(Request(uid=2, prompt=np.arange(4) % cfg.vocab,
                              inputs={"frames": frames[:3]}))
    assert sched2.queue_depth() == 0              # nothing got enqueued


def test_pop_completions_purge_frees_state_and_uids(setup):
    """A long-running server drains with purge=True: per-request state
    is released and finished uids become reusable."""
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48,
                   sampler=ScriptedSampler({}))
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2))
    sched.run()
    popped = sched.pop_completions(purge=True)
    assert [c.uid for c in popped] == [0]
    assert sched.done == [] and sched.generated == {}
    assert sched.request_metrics == {}
    # the uid is reusable now, and aggregate counters keep accumulating
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2))
    sched.run()
    assert sched.metrics.completed == 2
    assert [c.uid for c in sched.pop_completions(purge=True)] == [0]


# ------------------------------------------------- serve hot loop (PR 8)
def test_hot_loop_options_validation():
    with pytest.raises(ValueError, match="divide"):
        SchedulerOptions(max_len=48, prefill_chunk=10)
    with pytest.raises(ValueError, match="positive"):
        SchedulerOptions(prefill_chunk=0)
    with pytest.raises(ValueError, match="requires prefill_chunk"):
        SchedulerOptions(prefix_cache=4)
    with pytest.raises(ValueError, match="min_prefix"):
        SchedulerOptions(min_prefix=-1)
    # the new admission policy and the combined options are accepted
    o = SchedulerOptions(max_len=64, admission="deadline",
                         prefill_chunk=16, prefix_cache=4)
    assert o.to_dict()["prefill_chunk"] == 16


def test_chunked_prefill_model_bit_identity(setup):
    """Incremental prefill_chunk over an existing cache reproduces the
    full-sequence prefill EXACTLY: last-token logits and every written
    cache row are bitwise equal (online-softmax masking makes the pad
    positions contribute exact zeros)."""
    cfg, m, params = setup
    max_len, plen, chunk = 64, 37, 16
    prompt = (np.arange(plen, dtype=np.int32) * 3 + 1) % cfg.vocab

    logits_full, cache_full = jax.jit(
        lambda p, t, c: m.prefill(p, {"tokens": t}, c))(
        params, prompt[None], m.init_cache(1, max_len))

    cache = m.init_cache(1, max_len)
    step = jax.jit(lambda p, t, c, s, n: m.prefill_chunk(p, t, c, s, n))
    off = 0
    while off < plen:
        n = min(chunk, plen - off)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = prompt[off:off + n]
        logits, cache = step(params, padded, cache,
                             np.int32(off), np.int32(n))
        off += n

    np.testing.assert_array_equal(np.asarray(logits_full[:, -1]),
                                  np.asarray(logits[:, 0]))
    for k in ("c1", "c2"):
        np.testing.assert_array_equal(
            np.asarray(cache_full[k])[:, :, :plen],
            np.asarray(cache[k])[:, :, :plen])
    assert int(cache["pos"][0]) == plen


def _mixed_requests(vocab, *, head=None, n=6, max_new=5):
    """Deterministic mixed stream; with ``head`` every odd request's
    prompt starts with it (the shared system prompt)."""
    rng = np.random.default_rng(42)
    reqs = []
    for i in range(n):
        tail = rng.integers(0, vocab, int(rng.integers(3, 12))).astype(
            np.int32)
        if head is not None and i % 2 == 1:
            prompt = np.concatenate([head, tail])
        else:
            prompt = tail
        reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=max_new))
    return reqs


def _tokens(m, params, reqs, **opts):
    sched = _sched(m, params, **opts)
    for r in reqs:
        sched.submit(r)
    sched.run()
    out = {c.uid: tuple(c.tokens) for c in sched.done}
    summ = sched.summary()
    sched.shutdown()
    return out, summ


def test_chunked_prefill_scheduler_tokens_identical(setup):
    """Long prompts fed through chunked prefill (with and without batch
    buckets) produce bit-identical token streams to the fixed-shape
    whole-prompt scheduler."""
    cfg, m, params = setup
    head = (np.arange(20, dtype=np.int32) * 7 + 2) % cfg.vocab
    reqs = _mixed_requests(cfg.vocab, head=head)

    want, base_summ = _tokens(m, params, reqs, slots=3, max_len=64)
    assert "runtime" not in base_summ and "chunked_prefill" not in base_summ

    got, summ = _tokens(m, params, reqs, slots=3, max_len=64,
                        prefill_chunk=8)
    assert got == want
    assert summ["chunked_prefill"] == {"enabled": True, "chunk_len": 8}
    assert summ["prefill_chunks"] > len(reqs)   # long prompts = >1 chunk
    assert summ["runtime"]["chunk"]["compile_stalls"] == 0

    pol = repro.BucketPolicy.default(max_batch=3, max_len=64)
    got_b, summ_b = _tokens(m, params, reqs, slots=3, max_len=64,
                            prefill_chunk=8, buckets=pol)
    assert got_b == want
    # chunked prefill replaces the padded length-bucket prefill engine
    assert "prefill" not in summ_b["runtime"]
    assert "chunk" in summ_b["runtime"]


# ------------------------------------------- sampling inside the program
def _host_argmax(logits, temperature, *, uid, index):
    """Greedy sampling on the host, one read per slot."""
    return int(np.argmax(np.asarray(logits)[0]))


@pytest.mark.parametrize("path", ["fixed", "bucketed", "chunked", "mesh"])
def test_program_sampling_matches_host_argmax(setup, path):
    """Greedy tokens sampled inside the decode program (and the first
    token's program) equal a host argmax over the same logits, token
    for token, on each decode path (``mesh``: the fixed path placed on
    a one-device mesh)."""
    cfg, m, params = setup
    opts = {"fixed": {},
            "bucketed": {"buckets": repro.BucketPolicy.default(
                max_batch=3, max_len=64)},
            "chunked": {"prefill_chunk": 8},
            "mesh": {"mesh": "data=1"}}[path]
    head = (np.arange(20, dtype=np.int32) * 7 + 2) % cfg.vocab
    reqs = _mixed_requests(cfg.vocab, head=head, max_new=6)
    want, host = _tokens(m, params, reqs, slots=3, max_len=64,
                         sampler=_host_argmax, **opts)
    got, prog = _tokens(m, params, reqs, slots=3, max_len=64, **opts)
    assert got == want
    assert prog["sampling"]["program_steps"] == prog["decode_steps"] > 0
    assert host["sampling"]["host_steps"] == host["decode_steps"] > 0


def test_sample_rows_mixed_batch():
    """Rows at temperature 0 or below take their argmax whatever their
    neighbours draw; one key gives one set of tokens; every token lies
    in the vocabulary; the hot rows draw (another key, other tokens)."""
    vocab = 97
    logits = jnp.asarray(np.random.default_rng(0).standard_normal(
        (6, vocab)), jnp.float32)
    temps = jnp.asarray([0.0, 0.8, 0.0, 1.5, -1.0, 3.0], jnp.float32)
    greedy = np.argmax(np.asarray(logits), axis=-1)
    cold = np.asarray(temps) <= 0
    key = lambda n: jax.random.fold_in(jax.random.PRNGKey(3), n)

    toks = np.asarray(sample_rows(logits, temps, key(7)))
    assert toks.dtype == np.int32 and toks.shape == (6,)
    assert ((toks >= 0) & (toks < vocab)).all()
    np.testing.assert_array_equal(toks[cold], greedy[cold])
    np.testing.assert_array_equal(
        np.asarray(sample_rows(logits, jnp.zeros(6), key(7))), greedy)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(sample_rows)(logits, temps, key(7))), toks)

    draws = np.stack([np.asarray(sample_rows(logits, temps, key(n)))
                      for n in range(16)])
    assert ((draws >= 0) & (draws < vocab)).all()
    assert (draws[:, cold] == greedy[cold]).all()
    assert len(set(draws[:, 5].tolist())) > 1     # t = 3 draws vary


def test_program_sampling_seeded(setup):
    """Requests at a temperature above 0: the same seed serves the same
    tokens, another seed other tokens."""
    cfg, m, params = setup
    reqs = [Request(uid=r.uid, prompt=r.prompt, max_new_tokens=6,
                    temperature=0.0 if r.uid % 3 == 0 else 1.0)
            for r in _mixed_requests(cfg.vocab)]
    a, _ = _tokens(m, params, reqs, slots=3, max_len=48, seed=5)
    b, _ = _tokens(m, params, reqs, slots=3, max_len=48, seed=5)
    c, _ = _tokens(m, params, reqs, slots=3, max_len=48, seed=6)
    assert a == b
    assert a != c
    assert all(0 <= t < cfg.vocab for toks in a.values() for t in toks)


def test_sampling_summary_names_the_path(setup):
    """``summary()["sampling"]``: one token read a decode step when the
    program samples; one a slot, and no program step, with a host
    sampler."""
    cfg, m, params = setup
    reqs = _mixed_requests(cfg.vocab, n=4, max_new=4)
    _, prog = _tokens(m, params, reqs, slots=2, max_len=48)
    assert prog["sampling"] == {"program_steps": prog["decode_steps"],
                                "host_steps": 0, "reads_per_step": 1.0}
    _, host = _tokens(m, params, reqs, slots=2, max_len=48,
                      sampler=ScriptedSampler({}))
    assert host["sampling"]["program_steps"] == 0
    assert host["sampling"]["host_steps"] == host["decode_steps"]
    assert host["sampling"]["reads_per_step"] == pytest.approx(
        host["mean_batch_occupancy"])


def test_prefix_sharing_bit_identity_and_head_prefilled_once(setup):
    """Requests sharing a prompt head: tokens stay bit-identical to the
    unshared scheduler, the head is prefilled exactly ONCE (one insert;
    every other sharer takes a snapshot copy), and the shared chunks
    are actually skipped (fewer chunk dispatches than without the
    cache)."""
    cfg, m, params = setup
    chunk = 8
    head = (np.arange(2 * chunk, dtype=np.int32) * 5 + 3) % cfg.vocab
    reqs = _mixed_requests(cfg.vocab, head=head)
    n_shared = sum(1 for r in reqs if len(r.prompt) > len(head))

    want, _ = _tokens(m, params, reqs, slots=3, max_len=64)
    plain, plain_summ = _tokens(m, params, reqs, slots=3, max_len=64,
                                prefill_chunk=chunk)
    shared, summ = _tokens(m, params, reqs, slots=3, max_len=64,
                           prefill_chunk=chunk, prefix_cache=4)
    assert plain == want and shared == want

    pc = summ["prefix_cache"]
    assert pc["inserts"] == 1                       # head prefilled once
    assert pc["hits"] == n_shared - 1               # every other sharer
    assert pc["shared_tokens"] == (n_shared - 1) * len(head)
    # the skipped head chunks are real dispatch savings
    saved = (n_shared - 1) * (len(head) // chunk)
    assert summ["prefill_chunks"] == plain_summ["prefill_chunks"] - saved


def test_prefix_cache_lru_and_proper_prefix():
    """Unit-level PrefixCache behavior: longest proper prefix wins,
    whole-prompt keys never match, LRU evicts beyond capacity."""
    from repro.serve import PrefixCache
    import jax.numpy as jnp
    pc = PrefixCache(2)
    mk = lambda v: {"c": jnp.full((2, 1, 4), v), "pos": jnp.array([0])}
    a = np.arange(8, dtype=np.int32)
    pc.insert(PrefixCache.key_for(a[:4]), 4, mk(1.0))
    pc.insert(PrefixCache.key_for(a[:6]), 6, mk(2.0))

    h, snap = pc.take(a)                  # longest proper prefix: 6
    assert h == 6 and float(snap["c"][0, 0, 0]) == 2.0
    assert pc.take(a[:4]) is None         # whole prompt == head: no hit
    assert pc.take(np.flip(a).copy()) is None
    # taken snapshots are copies: mutating one leaves the cache intact
    snap["c"] = snap["c"].at[0].set(9.0)
    _, snap2 = pc.take(a)
    assert float(snap2["c"][0, 0, 0]) == 2.0

    pc.insert(PrefixCache.key_for(a[:2]), 2, mk(3.0))   # evicts LRU (4)
    assert pc.evictions == 1 and len(pc) == 2
    h, _ = pc.take(a[:3])
    assert h == 2
    assert pc.stats()["hits"] == 3


def test_deadline_admission_order(setup):
    """EDF under a fake clock: earliest absolute deadline first, no-SLO
    requests last (FCFS among themselves)."""
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48, admission="deadline",
                   sampler=ScriptedSampler({}), clock=TickClock())
    # submit order: no-SLO, loose, tight -> admit order: tight, loose, no
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2))
    sched.submit(Request(uid=1, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2, slo_ms=9000.0))
    sched.submit(Request(uid=2, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=2, slo_ms=1000.0))
    assert sched.request_metrics[1].deadline == pytest.approx(2.0 + 9.0)
    assert sched.request_metrics[2].deadline == pytest.approx(3.0 + 1.0)
    sched.run()
    admitted = sorted(sched.request_metrics.values(),
                      key=lambda r: r.admitted_at)
    assert [r.uid for r in admitted] == [2, 1, 0]


def test_slo_violations_counted(setup):
    """First tokens landing after the deadline are counted and flagged;
    on-time requests are flagged False; no-SLO requests stay None."""
    cfg, m, params = setup
    sched = _sched(m, params, slots=1, max_len=48,
                   sampler=ScriptedSampler({}), clock=TickClock())
    sched.submit(Request(uid=0, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=3, slo_ms=60_000.0))
    sched.submit(Request(uid=1, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=3, slo_ms=4000.0))   # will queue
    sched.submit(Request(uid=2, prompt=np.arange(4) % cfg.vocab,
                         max_new_tokens=3))
    sched.run()
    s = sched.summary()
    assert s["slo_violations"] == 1
    assert sched.request_metrics[0].slo_violated is False
    assert sched.request_metrics[1].slo_violated is True
    assert sched.request_metrics[2].slo_violated is None
    assert s["ttft_p50"] is not None and s["ttft_p99"] is not None


def test_summary_percentiles_match_numpy():
    """The dependency-free percentile matches numpy's default (linear
    interpolation), and summary() reports the tail keys."""
    from repro.serve.metrics import percentile
    rng = np.random.default_rng(3)
    for n in (1, 2, 5, 100):
        vals = rng.standard_normal(n).tolist()
        for q in (0.0, 50.0, 90.0, 99.0, 100.0):
            assert percentile(vals, q) == pytest.approx(
                float(np.percentile(vals, q)))
    assert percentile([], 50.0) is None


def test_chunked_prefill_auto_disabled_for_ring_caches(setup):
    """All-sliding-window models allocate a ring cache whose absolute
    row indices alias; chunked prefill must switch itself off (surfaced
    in summary) and serving must still work via whole-prompt prefill."""
    import dataclasses
    cfg, _, _ = setup
    ring_cfg = dataclasses.replace(cfg, pattern="swa", window=8)
    m = get_model(ring_cfg)
    params = m.init(jax.random.PRNGKey(0))
    sched = _sched(m, params, slots=2, max_len=32, prefill_chunk=8)
    assert sched._chunk_engine is None
    sched.submit(Request(uid=0, prompt=np.arange(6) % ring_cfg.vocab,
                         max_new_tokens=3))
    done = sched.run()
    assert len(done[0].tokens) == 3
    assert sched.summary()["chunked_prefill"] == {
        "enabled": False, "chunk_len": 8}


def test_steady_state_decode_zero_allocations(setup):
    """The donated step loop: across steady-state decode steps every
    cache leaf keeps its device buffer (the donated program updates it
    in place) and the number of live device arrays does not grow — no
    per-step slice / write-back allocations, on both the fixed-shape
    and the bucketed path."""
    cfg, m, params = setup
    pol = repro.BucketPolicy.default(max_batch=4, max_len=48)
    for buckets in (None, pol):
        sched = Scheduler(m, params,
                          SchedulerOptions(slots=4, max_len=48,
                                           fold=False, buckets=buckets),
                          engine_worker="sync")
        for uid in range(4):
            sched.submit(Request(uid=uid,
                                 prompt=np.arange(6) % cfg.vocab,
                                 max_new_tokens=30))
        sched.step()                     # admissions + first decode
        sched.step()
        ptrs = sched.slot_manager.buffer_pointers()
        live = len(jax.live_arrays())
        for _ in range(6):
            sched.step()
            assert sched.slot_manager.buffer_pointers() == ptrs
        assert len(jax.live_arrays()) == live
        sched.shutdown()
