"""The one traffic generator: a mix file's parameters -> a request schedule.

A mix (``perfbench/traffic/<name>.json``) gives the offered rate, the
length distributions and the sampling.  The schedule is one fixed draw of
the mix: prompt lengths, generation lengths and inter-arrival gaps are the
evenly spaced quantiles of the stated distributions, in an order that is
the same for every seed; the seed draws the prompt tokens (and, in
``model.py``, the weights and the sampler's noise).  Every run therefore
offers the same work at the same times: with the order drawn from the
seed, the ~12 requests of a chat window and the ~50 of a code window
differed so much from seed to seed that the runs' spread was 15-52%
(see PERF.md), and no bound could hold.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np


ORDER_SEED = 0  # the one order of lengths and gaps


@dataclasses.dataclass
class Planned:
    rid: int
    due_s: float  # offset from the start of the measured window
    prompt: np.ndarray
    max_new: int


def _quantiles(dist: Dict, n: int) -> np.ndarray:
    """n evenly spaced quantiles of a clipped lognormal (median, sigma)."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    nd = statistics.NormalDist(math.log(dist["median"]), dist["sigma"])
    x = np.exp([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def schedule(mix: Dict, seconds: float, seed: int, vocab: int,
             max_len: int) -> List[Planned]:
    """Requests due from ``-warmup_s`` to ``seconds + tail_s`` (offsets
    from the window's start), Poisson at ``rate_per_s``."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    rate = mix["rate_per_s"]
    span = mix["warmup_s"] + seconds + mix["tail_s"]
    n = max(1, math.ceil(rate * span))
    order = np.random.default_rng(ORDER_SEED)
    # exponential quantiles: gaps whose sum is the same for every seed
    gaps = order.permutation(-np.log1p(-(np.arange(n) + 0.5) / n) / rate)
    due = np.cumsum(gaps) - gaps[0] - mix["warmup_s"]
    prompts = order.permutation(_quantiles(mix["prompt_tokens"], n))
    news = order.permutation(_quantiles(mix["new_tokens"], n))
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        p, k = int(prompts[i]), int(news[i])
        if p + k > max_len:  # only a rehearsal's small engine cuts lengths
            p = max(1, min(p, max_len // 2))
            k = max(1, min(k, max_len - p))
        out.append(Planned(i, float(due[i]),
                           rng.integers(0, vocab, size=p, dtype=np.int32), k))
    return out
