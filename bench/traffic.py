"""The one traffic generator: it reads a mix's parameters (a JSON file
under ``bench/traffic/``) and the run's seed, and nothing else.

Two loops:

* ``closed``: ``callers`` callers, each sending its next call when the
  last has returned, ``batch`` inputs a call, drawn from a pool of
  ``pool`` distinct seeded batches (made on the device in one call and
  copied to the host, so every call transfers host inputs).
* ``open``: requests arrive on a schedule whether or not earlier ones
  have finished.  Every seed gets the same multiset of gaps and of
  prompt and output lengths, in another order: the quantiles of the
  stated distributions.  So seeds change the order and the token ids,
  not the amount of work.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Tuple

import numpy as np

#: Streams of one seed: each draws from its own generator.
STREAM_WINDOW, STREAM_WARMUP, STREAM_BURST, STREAM_POOL = 1, 2, 3, 4


def rng(seed: int, stream: int) -> np.random.Generator:
    """numpy generator for one stream of ``seed`` (any whole number)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def jax_key(seed: int, stream: int):
    """A JAX PRNG key for one stream of ``seed``; seeds wider than 32
    bits are fine."""
    import jax
    import jax.numpy as jnp
    state = np.random.SeedSequence([int(seed), stream]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state), impl="threefry2x32")


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` whole-number draws of ``dist`` at the mid-quantiles
    ``(i + 0.5) / n``, clipped to ``[min, max]``.  ``lognormal`` takes
    ``median`` and ``sigma``; ``fixed`` takes ``value``."""
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of a Poisson process at
    ``rate`` per second, at the mid-quantiles."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


@dataclasses.dataclass
class Arrival:
    """One request of an open loop: when it is due (seconds from the
    start of its phase), its prompt and how many tokens it asks for."""

    uid: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _requests(mix: dict, g: np.random.Generator, n: int, vocab: int,
              uid0: int, out_dist=None) -> List[Tuple[np.ndarray, int]]:
    plen = g.permutation(quantiles(mix["prompt_tokens"], n))
    olen = g.permutation(quantiles(out_dist or mix["output_tokens"], n))
    return [(g.integers(0, vocab, int(p), dtype=np.int64).astype(np.int32),
             int(o)) for p, o in zip(plen, olen)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int,
              uid0: int = 0) -> List[Arrival]:
    """The requests due in a window of ``seconds``: ``round(rate *
    seconds)`` of them, their gaps and lengths the same multiset for
    every seed."""
    if mix["loop"] != "open":
        raise ValueError("not an open-loop mix")
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    g = rng(seed, STREAM_WINDOW)
    t = np.cumsum(g.permutation(gaps(mix["rate_per_s"], n)))
    t = t - t[0]                      # the first request at the start
    t = t * (seconds * (n - 1) / n / t[-1]) if n > 1 else t
    reqs = _requests(mix, g, n, vocab, uid0)
    return [Arrival(uid0 + i, float(d), p, o)
            for i, (d, (p, o)) in enumerate(zip(t, reqs))]


def warmup(mix: dict, seed: int, vocab: int, slots: int,
           uid0: int) -> Tuple[List[Arrival], List[Arrival]]:
    """Set-up traffic: a burst of ``slots`` short requests, which puts
    every slot to use once, then ``warmup.seconds`` of arrivals at the
    mix's rate, so that the window starts from a loaded server."""
    w = mix["warmup"]
    burst = [Arrival(uid0 + i, 0.0, p, o) for i, (p, o) in enumerate(
        _requests(mix, rng(seed, STREAM_BURST), slots, vocab, uid0,
                  out_dist={"dist": "fixed",
                            "value": w["burst_output_tokens"]}))]
    steady = open_loop(mix, int(seed) + 1, w["seconds"], vocab,
                       uid0 + slots)
    return burst, steady


def closed_pool(mix: dict, seed: int, shape: Tuple[int, ...]) -> np.ndarray:
    """``pool`` distinct host batches of ``batch`` standard-normal
    inputs of ``shape``, made on the device in one call."""
    import jax
    import jax.numpy as jnp
    if mix["loop"] != "closed":
        raise ValueError("not a closed-loop mix")
    full = (mix["pool"], mix["batch"]) + tuple(shape)
    make = jax.jit(lambda k: jax.random.normal(k, full, jnp.float32))
    return np.asarray(make(jax_key(seed, STREAM_POOL)))


def check_mix(mix: dict) -> None:
    """Refuse a mix with a parameter the generator does not know."""
    if mix.get("loop") == "closed":
        need = {"loop", "callers", "batch", "pool"}
    elif mix.get("loop") == "open":
        need = {"loop", "rate_per_s", "prompt_tokens", "output_tokens",
                "temperature", "warmup", "drain_s", "drain"}
    else:
        raise ValueError(f"unknown loop {mix.get('loop')!r}")
    extra = set(mix) - need - {"why"}
    missing = need - set(mix)
    if extra or missing:
        raise ValueError(f"traffic mix: unknown keys {sorted(extra)}, "
                         f"missing {sorted(missing)}")
    if mix["loop"] == "closed" and mix["callers"] != 1:
        raise ValueError("closed loop: one caller only")
    if mix["loop"] == "open" and not (
            math.isfinite(mix["rate_per_s"]) and mix["rate_per_s"] > 0):
        raise ValueError("open loop: rate_per_s must be positive")
