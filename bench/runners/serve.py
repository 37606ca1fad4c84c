"""The serving path: a model through ``repro.compile(target="engine")``
and ``repro.serve``, under an open loop of requests.

One thread submits each request when it is due and drives
``Scheduler.step()`` in between; a request's latency counts from when
it was due, so a late generator shows in the tails.  Set-up makes the
weights from the seed, builds the executable and the scheduler, serves
a burst that puts every slot to use once (every program is compiled or
loaded then), and serves the mix for ``warmup.seconds``, so that the
window starts from a loaded server.  With ``drain`` the requests due in
the window are served to the end after it, with no new arrivals, for at
most ``drain_s``; one still unfinished then has failed.

Afterwards the program is freed and a seeded sample of the finished
requests, the longest among them, is run through the configuration's
plain reference: each served token's logit is compared with the
reference's best at its position.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from .. import flops, record, stats, trace_reduce, traffic

STREAM_CHECK = 9
WARM_UID = 1 << 40


class Loop:
    """Submits requests and steps the scheduler, recording when each
    token became available (the end of the step that produced it; a
    first token at the scheduler's own ``first_token_at``)."""

    def __init__(self, sched, cfg, temperature, rec):
        self.sched, self.cfg, self.temp = sched, cfg, temperature
        self.rec = rec
        self.by_uid: Dict[int, record.Request] = {}
        self.prompts: Dict[int, np.ndarray] = {}
        self.tokens: Dict[int, List[int]] = {}
        self.live = set()

    def submit(self, arr: traffic.Arrival, due: float, in_window: bool):
        from repro.serve import Request
        with _span("bench.submit"):
            self.sched.submit(Request(uid=arr.uid, prompt=arr.prompt,
                                      max_new_tokens=arr.max_new_tokens,
                                      temperature=self.temp))
        r = record.Request(uid=arr.uid, due=due, submitted=time.perf_counter(),
                           prompt_tokens=len(arr.prompt),
                           max_new_tokens=arr.max_new_tokens)
        self.by_uid[arr.uid] = r
        self.prompts[arr.uid] = arr.prompt
        self.live.add(arr.uid)
        if in_window:
            self.rec.requests.append(r)

    def busy(self) -> bool:
        m = self.sched.metrics
        return m.submitted > m.completed

    def step(self) -> None:
        sched = self.sched
        with _span("bench.step"):
            a = time.perf_counter()
            sched.step()
            b = time.perf_counter()
        gen, rm = sched.generated, sched.request_metrics
        ctx, pre, made = [], 0.0, 0
        for uid in list(self.live):
            toks = gen.get(uid)
            r = self.by_uid[uid]
            if toks is None or len(toks) == len(r.token_times):
                continue
            made += len(toks) - len(r.token_times)
            if not r.token_times:
                m = rm[uid]
                r.admitted, r.first_token = m.admitted_at, m.first_token_at
                r.token_times.append(m.first_token_at)
                pre += flops.lm_prefill_flops(self.cfg, 0, r.prompt_tokens)
            for j in range(len(r.token_times), len(toks)):
                ctx.append(r.prompt_tokens + j)
                r.token_times.append(b)
        for c in sched.pop_completions():
            r = self.by_uid[c.uid]
            r.finished = True
            self.tokens[c.uid] = list(c.tokens)
            self.live.discard(c.uid)
        self.rec.steps.append(record.Step(a, b, ctx, pre, made))

    def serve(self, arrivals, t0, until, in_window, stop_when_idle=False):
        """Submit ``arrivals`` at ``t0 + due`` and step until ``until``
        (or, with ``stop_when_idle``, until nothing is in flight and no
        arrival is left); returns the arrivals not yet submitted."""
        i = 0
        while True:
            now = time.perf_counter()
            if now >= until:
                break
            while i < len(arrivals) and t0 + arrivals[i].due_s <= now:
                self.submit(arrivals[i], t0 + arrivals[i].due_s, in_window)
                i += 1
            if self.busy():
                self.step()
            elif i < len(arrivals):
                time.sleep(max(0.0, min(t0 + arrivals[i].due_s, until)
                               - time.perf_counter()))
            elif stop_when_idle:
                break
            else:
                time.sleep(max(0.0, until - time.perf_counter()))
        return arrivals[i:]


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _runtime(sched) -> dict:
    rt = sched.summary().get("runtime", {})
    return {k: rt.get(k, 0) for k in ("compile_stalls", "background_compiles",
                                      "bucket_misses")}


def build(cell, cfgmod, seed, say, broken=None):
    """Weights from the seed, the executable and the scheduler; returns
    a :class:`Loop` over the scheduler and the weights' key."""
    import jax
    import repro
    from repro.models import get_model

    cfg, mix = cell.config, cell.traffic
    wkey = traffic.jax_key(seed, 0)
    t = time.perf_counter()
    arch = cfgmod.arch(cfg)
    weights = jax.block_until_ready(cfgmod.make_weights(cfg, wkey))
    say(f"[setup] weights made {time.perf_counter() - t:.3f}s")
    want = jax.eval_shape(lambda: get_model(arch).init(jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: weights)
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != jax.tree.map(
            lambda a: (a.shape, a.dtype), got):
        raise ValueError("the configuration's weights do not have the "
                         "served model's layout")
    t = time.perf_counter()
    exe = repro.compile(arch, repro.CompileOptions(target="engine"),
                        params=weights)
    extra = {}
    if broken is not None:
        exe, extra = broken(exe)
    sched = repro.serve(exe, repro.SchedulerOptions(**cfg["serve"]), **extra)
    # The scheduler holds the folded weights; the originals can go.
    del exe, weights
    gc.collect()
    say(f"[setup] executable and scheduler {time.perf_counter() - t:.3f}s "
        f"layers={arch.num_layers} d_model={arch.d_model} "
        f"heads={arch.n_heads}/{arch.n_kv_heads} head_dim={arch.head_dim} "
        f"d_ff={arch.d_ff} vocab={arch.vocab} dtype={arch.param_dtype}/"
        f"{arch.dtype} serve={cfg['serve']}")
    return Loop(sched, cfg, mix["temperature"], record.Run(cfg, mix)), wkey


def warm(loop, cell, seed, say, watch, rate=None):
    """A burst that puts every slot to use once, then ``warmup.seconds``
    of the mix at ``rate`` (the mix's own by default)."""
    cfg, mix = cell.config, dict(cell.traffic)
    mix["rate_per_s"] = rate or mix["rate_per_s"]
    burst, steady = traffic.warmup(mix, seed, cfg["vocab_size"],
                                   cfg["serve"]["slots"], WARM_UID)
    t = time.perf_counter()
    loop.serve(burst, t, t + 1e6, False, stop_when_idle=True)
    say(f"[setup] burst of {len(burst)} served in "
        f"{time.perf_counter() - t:.3f}s compiles={watch.snapshot()}")
    t = time.perf_counter()
    loop.serve(steady, t, t + mix["warmup"]["seconds"], False)
    m = loop.sched.metrics
    say(f"[setup] warm-up at {mix['rate_per_s']}/s for "
        f"{time.perf_counter() - t:.3f}s; in flight "
        f"{m.submitted - m.completed}")


def run(cell, cfgmod, *, seed, seconds, trace_dir, t_start, watch, say,
        broken=None):
    """One run of a serving cell.  ``broken``, for tests only, takes the
    executable and returns it with a fault planted, and extra keyword
    arguments for ``repro.serve``."""
    import jax
    from .. import device

    cfg, mix = cell.config, cell.traffic
    rec = record.Run(config=cfg, traffic=mix)
    say(f"[setup] process and device up {time.perf_counter() - t_start:.3f}s")
    loop, wkey = build(cell, cfgmod, seed, say, broken)
    sched = loop.sched
    warm(loop, cell, seed, say, watch)

    loop.rec = rec
    held = max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in jax.devices()[:cell.chips])
    say(f"[memory] bytes_in_use at window start={held}")
    arrivals = traffic.open_loop(mix, seed, seconds, cfg["vocab_size"])
    before, rt_before = watch.snapshot(), _runtime(sched)
    if trace_dir:
        trace_reduce.start(trace_dir)
    rec.setup_s = time.perf_counter() - t_start
    with _span("bench.window"):
        rec.window_start = t0 = time.perf_counter()
        left = loop.serve(arrivals, t0, t0 + seconds, True)
        rec.window_end = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    rec.counters["window_compiles"] = watch.since(before, watch.snapshot())
    rec.counters["window_runtime"] = {
        k: v - rt_before[k] for k, v in _runtime(sched).items()}
    late = [r.submitted - r.due for r in rec.requests]
    say(f"[window] window_s={rec.window_s!r} due={len(arrivals)} "
        f"submitted={len(rec.requests)} steps={len(rec.window_steps())} "
        f"generator_late_ms p50={1e3 * (stats.percentile(late, 50) or 0)!r} "
        f"p95={1e3 * (stats.percentile(late, 95) or 0)!r} "
        f"max={1e3 * max(late or [0])!r} "
        f"compiles={rec.counters['window_compiles']} "
        f"scheduler={rec.counters['window_runtime']}")
    if mix["drain"]:
        with _span("bench.drain"):
            t = time.perf_counter()
            for arr in left:                     # due in the window, late
                loop.submit(arr, t0 + arr.due_s, True)
            loop.serve([], t, t + mix["drain_s"], True, stop_when_idle=True)
        say(f"[drain] {time.perf_counter() - t:.3f}s")
    rec.attempted = len(arrivals)
    rec.failed = (sum(not r.finished for r in rec.requests)
                  if mix["drain"] else 0)
    done = sum(r.finished for r in rec.requests)
    say(f"[requests] due={len(arrivals)} finished={done} failed={rec.failed} "
        f"in_flight_at_end={sched.metrics.submitted - sched.metrics.completed}")
    rec.device_json = device.describe(jax.devices(), cell.chips)
    say(f"[memory] peak_bytes_in_use={rec.device_json['memory_peak_bytes']}")

    # The program's state goes before the reference runs.
    sched.shutdown()
    loop.sched = None
    del sched
    gc.collect()
    rec.correct = check(cfg, cfgmod, wkey, seed, loop, rec, say)
    return rec


def sample(cfg, seed, loop, rec) -> List[int]:
    """Uids to compare: the finished window request with the most served
    tokens, then others in an order drawn from the seed, until
    ``min_tokens`` served tokens or ``max_requests`` requests."""
    fin = [r.uid for r in rec.requests if r.finished]
    if not fin:
        return []
    longest = max(fin, key=lambda u: (len(loop.tokens[u]), -u))
    rest = [u for u in fin if u != longest]
    order = traffic.rng(seed, STREAM_CHECK).permutation(len(rest))
    out, n = [longest], len(loop.tokens[longest])
    for i in order:
        if n >= cfg["check"]["min_tokens"] or len(out) >= cfg["check"][
                "max_requests"]:
            break
        out.append(rest[i])
        n += len(loop.tokens[rest[i]])
    return out


def sequences(cfg, loop, uids):
    """For each uid: (prompt + served tokens but the last, each
    position's next served token, first position, count), padded to
    ``max_len`` so that one reference program serves every request."""
    n_max = cfg["serve"]["max_len"]
    for u in uids:
        p, toks = loop.prompts[u], loop.tokens[u]
        seq = np.zeros(n_max, np.int32)
        nxt = np.zeros(n_max, np.int32)
        full = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        seq[:len(full)] = full
        nxt[len(p) - 1:len(p) - 1 + len(toks)] = toks
        yield u, seq, nxt, len(p) - 1, len(toks)


def check(cfg, cfgmod, wkey, seed, loop, rec, say) -> bool:
    """Run the reference over the sampled requests; the compared number
    is the widest gap of a served token."""
    import jax
    uids = sample(cfg, seed, loop, rec)
    bad = [u for u in uids if not all(0 <= t < cfg["vocab_size"]
                                      for t in loop.tokens[u])
           or len(loop.tokens[u]) > loop.by_uid[u].max_new_tokens]
    weights = cfgmod.make_weights(cfg, wkey)
    fn = jax.jit(lambda w, s, n: cfgmod.gaps(cfg, w, s, n))
    t = time.perf_counter()
    widest, served = 0.0, 0
    for u, seq, nxt, at, n in sequences(cfg, loop, uids):
        if u in bad:
            continue
        g = np.asarray(fn(weights, seq, nxt))[at:at + n]
        widest = max(widest, float(np.max(g)))
        served += n
    say(f"[check] {len(uids)} requests, {served} served tokens compared with "
        f"the reference in {time.perf_counter() - t:.3f}s; malformed {bad}")
    ok = rec.check(cfg["check"]["number"], widest if uids else float("nan"),
                   cfg["check"]["limit"])

    def control() -> float:
        """The same number for the int8 control in the program's
        place (``bench/control.py``)."""
        cf = jax.jit(lambda w, s: cfgmod.control_gaps(cfg, w, s))
        return max(float(np.max(np.asarray(cf(weights, seq))[at:at + n]))
                   for _, seq, _, at, n in sequences(cfg, loop, uids))

    rec.control_reading = control
    return ok and not bad and bool(uids)
