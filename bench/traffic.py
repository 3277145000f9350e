"""One general generator for every traffic mix.

A mix is a data file ``bench/traffic/<name>.json``:

* ``kind`` ``open_poisson``: independent users; requests are due on a
  Poisson schedule at ``rate_rps`` from the start of the traffic, and keep
  arriving through the ramp, the window and the drain.
* ``kind`` ``backlog``: an offline job; ``requests`` requests are due at
  time 0 and the client keeps ``queue_depth`` of them queued in the server.

``prompt`` and ``output`` give each length distribution (``lognormal``
with ``median`` and ``sigma``, clipped to ``[min, max]``). Each consecutive
block of ``block`` requests holds the block's stratified quantiles of each
distribution (and of the exponential gap), in an order drawn once from a
fixed generator. The schedule -- every due time, every length and the
order of the backlog -- is the same for every seed; the seed draws only
the token ids. So runs with different seeds do the same work.

A mix file may name ``base``, another mix, and give only the keys it
changes (see ``bench/spec.py``).
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def seed_words(seed: int) -> tuple:
    """Split a non-negative seed of up to 64 bits into two 32-bit words."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} must lie in [0, 2**64)")
    return seed & 0xFFFFFFFF, seed >> 32


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, *seed_words(seed)])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2) / n of a length distribution,
    as whole numbers within its clip range."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    v = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """n stratified quantiles of the exponential inter-arrival time."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


# the schedule's generators are fixed, so that no seed changes the work
LAYOUT = 0x5C4ED


def layout_rng(stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, LAYOUT])


def _blocked(values: np.ndarray, n: int, rng) -> np.ndarray:
    """Repeat the block of stratified values to n entries, permuting each
    block on its own."""
    blocks = []
    while sum(len(b) for b in blocks) < n:
        blocks.append(rng.permutation(values))
    return np.concatenate(blocks)[:n]


def count(mix: dict, seconds: float) -> int:
    """Number of requests the mix generates for a window of ``seconds``."""
    if mix["kind"] == "open_poisson":
        horizon = mix["ramp_s"] + seconds + mix["drain_s"]
        return int(math.ceil(mix["rate_rps"] * horizon))
    if mix["kind"] == "backlog":
        return int(mix["requests"])
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Requests of one run: dicts with ``rid``, ``due`` (seconds from the
    start of the traffic), ``prompt`` (int32 token ids below ``vocab``) and
    ``max_new``."""
    n = count(mix, seconds)
    block = int(mix.get("block", 64))
    plen = _blocked(quantiles(mix["prompt"], block), n, layout_rng(1))
    olen = _blocked(quantiles(mix["output"], block), n, layout_rng(2))
    if mix["kind"] == "open_poisson":
        gaps = _blocked(exp_gaps(mix["rate_rps"], block), n, layout_rng(3))
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = np.zeros(n)
    tok_rng = rng_for(seed, 2)
    return [{"rid": i, "due": float(due[i]),
             "prompt": tok_rng.integers(0, vocab, size=int(plen[i]),
                                        dtype=np.int32),
             "max_new": int(olen[i])}
            for i in range(n)]


def max_positions(mix: dict) -> int:
    """Largest prompt plus output the mix can draw."""
    return int(mix["prompt"]["max"] + mix["output"]["max"])
