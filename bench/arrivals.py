"""The general traffic generator: a serving mix from its parameter file.

Stratified: for a given rate and window every seed gets the same multiset
of arrival gaps, prompt lengths, output lengths and adapter choices (each a
set of quantiles of its distribution), and the seed only permutes them and
draws the token ids. The permutation stays inside each of the mix's
``strata``, consecutive blocks of arrivals that hold the same share of
every multiset for every seed, so each stretch of the window brings the
same work whatever the seed. The spread between seeds then comes from the
order of the work, not from how much of it there is, or when.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Arrival:
    due_s: float                      # seconds after the window opens
    prompt: np.ndarray                # (T,) int32
    max_new_tokens: int
    adapter_id: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(n: int, spec: dict) -> np.ndarray:
    """n quantiles of a lognormal of the given median and sigma, rounded and
    clipped to [min, max]."""
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(n: int, span_s: float) -> np.ndarray:
    """n quantiles of an exponential, scaled so that all n arrivals fall
    inside ``span_s`` (the last half a mean gap before its end)."""
    g = -np.log1p(-_quantiles(n))
    return g * (span_s * (1 - 0.5 / n) / g.sum())


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of n requests go to each of k items under Zipf(s),
    largest-remainder rounded."""
    p = 1.0 / np.arange(1, k + 1) ** s
    exact = n * p / p.sum()
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(-(exact - counts))[:n - counts.sum()]] += 1
    return counts


def strata(n: int, k: int) -> np.ndarray:
    """The stratum of each of n sorted values: dealt in a snake over k
    strata (0..k-1, k-1..0, ...), so that each stratum gets an even share
    of small and large values."""
    i = np.arange(n)
    col = i % k
    return np.where((i // k) % 2 == 0, col, k - 1 - col)


def permuted_within(values: np.ndarray, k: int, rng) -> np.ndarray:
    """The sorted values dealt to k strata, each permuted, strata in order."""
    v = np.sort(values)
    s = strata(len(v), k)
    return np.concatenate([rng.permutation(v[s == j]) for j in range(k)])


def serve_mix(t: dict, n_adapters: int, vocab: int, seed: int,
              seconds: float) -> List[Arrival]:
    """The requests due in a window of ``seconds`` at ``t["rate_per_s"]``."""
    n = max(1, int(round(t["rate_per_s"] * seconds)))
    k = min(t["strata"], n)
    rng = np.random.default_rng(seed)
    due = np.cumsum(permuted_within(poisson_gaps(n, seconds), k, rng))
    users = permuted_within(lognormal_lengths(n, t["user_tokens"]), k, rng)
    outs = permuted_within(lognormal_lengths(n, t["output_tokens"]), k, rng)
    adapters = permuted_within(np.repeat(
        np.arange(n_adapters), zipf_counts(n, n_adapters, t["adapter_zipf"])),
        k, rng)
    per = t["system_prompts_per_adapter"]
    system = rng.integers(0, vocab, (n_adapters, per,
                                     t["system_prompt_tokens"]), np.int32)
    seen = np.zeros(n_adapters, np.int64)
    out = []
    for i in range(n):
        a = int(adapters[i])
        sp = system[a, seen[a] % per]
        seen[a] += 1
        user = rng.integers(0, vocab, int(users[i]), np.int32)
        out.append(Arrival(due_s=float(due[i]),
                           prompt=np.concatenate([sp, user]),
                           max_new_tokens=int(outs[i]), adapter_id=a))
    return out
