"""Open-loop serving traffic from a mix file and a seed.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (stratified quantiles of the mix's distributions),
each in its own seeded order, with its own uniform token ids.  So two
seeds send the same amount of work at the same mean rate, and the run
to run spread measures the system rather than the draw.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    due_s: float            # when the open loop sends it, from window start
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _lognormal_quantiles(n, median, sigma, lo, hi):
    """n stratified draws of a lognormal clipped to [lo, hi]."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return np.clip(np.rint(median * np.exp(sigma * np.asarray(z))),
                   lo, hi).astype(np.int64)


def _exponential_quantiles(n, mean):
    return np.asarray([-mean * math.log(1.0 - (i + 0.5) / n)
                       for i in range(n)])


def request_count(mix, seconds):
    """Requests due in a window of `seconds` at the mix's rate."""
    return max(1, int(round(float(mix["rate_per_s"]) * seconds)))


def requests(mix, vocab_size, seed, seconds):
    """The requests due in [0, seconds), sorted by due time."""
    n = request_count(mix, seconds)
    rng = np.random.default_rng(seed)
    p = mix["prompt_tokens"]
    o = mix["output_tokens"]
    plens = rng.permutation(_lognormal_quantiles(
        n, p["median"], p["sigma"], p["min"], p["max"]))
    olens = rng.permutation(_lognormal_quantiles(
        n, o["median"], o["sigma"], o["min"], o["max"]))
    gaps = rng.permutation(_exponential_quantiles(
        n, 1.0 / float(mix["rate_per_s"])))
    # scale the gaps so the n-th request is due just inside the window
    due = np.cumsum(gaps) - gaps[0]
    due *= (seconds * (n - 0.5) / n) / max(due[-1], 1e-9)
    out = []
    for d, pl, ol in zip(due, plens, olens):
        prompt = rng.integers(0, vocab_size, int(pl), dtype=np.int32)
        out.append(ServeRequest(float(d), prompt, int(ol)))
    return out
