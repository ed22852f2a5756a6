"""The one general traffic generator. A mix is a data file of parameters;
every seed gets the same multiset of lengths and the same multiset of
inter-arrival gaps (stratified quantiles of the stated distributions). A mix
that states an ``order_seed`` also fixes their order, so that every seed
replays one schedule and only the token ids (and the weights) follow the
run's seed: on the chip the order of a mix's bursts moved its tail latency
by a factor of three from seed to seed, while two runs of one order agreed.
Without it the run's seed orders them."""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int) -> np.ndarray:
    """n stratified quantiles of a log-normal, clipped to [lo, hi]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(int)


def serve_schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Requests due in a window of ``seconds``: dicts with ``due`` (s from
    the window's start), ``tokens`` (list[int]) and ``max_new_tokens``."""
    rate = mix["rate_per_s"]
    n = max(1, round(rate * seconds))
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix.get("order_seed", seed))
    prompts = _lognormal_quantiles(n, **mix["prompt_tokens"])
    outputs = _lognormal_quantiles(n, **mix["output_tokens"])
    order.shuffle(prompts)
    order.shuffle(outputs)
    if mix["arrivals"] == "poisson":
        # Exponential gaps by stratified quantiles, scaled to fill the
        # window exactly; the seed orders them.
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
        gaps *= seconds / gaps.sum()
        order.shuffle(gaps)
        due = np.cumsum(gaps) - gaps[0]
    elif mix["arrivals"] == "at_start":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return [{"due": float(due[i]),
             "tokens": rng.integers(0, vocab, int(prompts[i])).tolist(),
             "max_new_tokens": int(outputs[i])} for i in range(n)]


def train_batches(mix: dict, seed: int, vocab: int) -> list[np.ndarray]:
    """``distinct_batches`` arrays [rows, seq_len + 1] of token ids drawn
    from the seed, every row different."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (mix["rows"], mix["seq_len"] + 1),
                         dtype=np.int32)
            for _ in range(mix["distinct_batches"])]
