"""Open-loop request schedules from a traffic file and a seed.

The shapes follow ``repro.serving.loadgen``: Poisson arrivals
(exponential gaps) and lognormal prompt and output lengths.  Unlike
that closed-loop generator, every request here is due at a fixed time
whatever the server does, so a stall shows as waiting.

The schedule is the same for every seed: the gaps and lengths are the
distributions' quantiles at evenly spaced points, in an order drawn
from a fixed stream, and the seed draws only the token ids (and, in
the harness, the weights).  The order is stratified: consecutive
blocks of ``BLOCK`` requests each take one value from every stratum of
``n / BLOCK`` neighbouring quantiles, so every stretch of the schedule
carries about the same work.  With the order drawn from the seed, two
seeds differed in which long prompts were in flight together, and
that alone spread tokens/s by 11-15% and TTFT's tail by a factor of
two between seeds, against 0.3% between runs of one seed.

A traffic file is JSON:

    {"rate_rps": 0.8,                      # mean arrival rate
     "lead_s": 5,                          # traffic before the window opens
     "prompt": {"median": 384, "sigma": 0.8, "min": 32, "max": 1536},
     "output": {"median": 96, "sigma": 0.8, "min": 8, "max": 512},
     "tiers": {"0.5": 1.0}}                # tier -> share of requests
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

#: independent random streams, so that changing one field of a mix (say
#: its tiers) leaves every other draw unchanged
_STREAM = {"gaps": 1, "prompt": 2, "output": 3, "tiers": 4, "tokens": 5}

#: the seed of the schedule's order, the same for every run's seed
ORDER_SEED = 0


@dataclass(frozen=True)
class Request:
    idx: int
    due: float  # seconds after the traffic starts
    prompt: np.ndarray  # int32 token ids
    max_new: int
    tier: float


#: requests per stratified block (see the module docstring)
BLOCK = 8


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**64 - 1), _STREAM[stream]])


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _stratified(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which each block of
    ``BLOCK`` consecutive entries holds one value of every stratum."""
    v = np.sort(values)
    nb = -(-len(v) // BLOCK)
    blocks = [[] for _ in range(nb)]
    for start in range(0, len(v), nb):
        stratum = v[start:start + nb]
        for b, x in zip(rng.permutation(nb), stratum):
            blocks[b].append(x)
    return np.array([x for blk in blocks for x in rng.permutation(blk)],
                    dtype=values.dtype)


def _lognormal_lengths(d: Dict[str, Any], n: int,
                       rng: np.random.Generator) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(p) for p in _quantiles(n)])
    v = np.round(d["median"] * np.exp(d["sigma"] * z))
    v = np.clip(v, d["min"], d["max"]).astype(np.int64)
    return _stratified(v, rng)


def _allocate(weights: Dict[str, float], n: int,
              rng: np.random.Generator) -> List[str]:
    """Exactly ``round(share * n)`` of each key (largest remainders),
    in an order drawn from ``rng``."""
    keys = sorted(weights)
    w = np.array([weights[k] for k in keys], np.float64)
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    out = [k for k, c in zip(keys, counts) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def schedule(mix: Dict[str, Any], seed: int, horizon_s: float, vocab: int,
             max_len: int) -> List[Request]:
    """Requests due in ``[0, horizon_s)``, in due order; ``seed`` draws
    only their token ids."""
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * horizon_s)))
    gaps = -np.log1p(-_quantiles(n)) / rate  # exponential quantiles
    gaps = _stratified(gaps, _rng(ORDER_SEED, "gaps"))
    due = np.cumsum(gaps) - gaps[0]
    prompts = _lognormal_lengths(mix["prompt"], n,
                                 _rng(ORDER_SEED, "prompt"))
    outputs = _lognormal_lengths(mix["output"], n,
                                 _rng(ORDER_SEED, "output"))
    tiers = _allocate(mix["tiers"], n, _rng(ORDER_SEED, "tiers"))
    tok = _rng(seed, "tokens")
    out = []
    for i in range(n):
        if due[i] >= horizon_s:
            continue
        prompt = tok.integers(0, vocab, int(prompts[i]),
                              dtype=np.int64).astype(np.int32)
        if len(prompt) + int(outputs[i]) > max_len:
            raise ValueError(
                f"request {i}: {len(prompt)} + {outputs[i]} tokens exceed "
                f"max_len {max_len}: the mix does not fit the config")
        out.append(Request(i, float(due[i]), prompt, int(outputs[i]),
                           float(tiers[i])))
    out.sort(key=lambda r: r.due)
    return out


def horizon(mix: Dict[str, Any], window_s: float) -> float:
    return float(mix["lead_s"]) + float(window_s)


def longest_request_tokens(mix: Dict[str, Any]) -> int:
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])
