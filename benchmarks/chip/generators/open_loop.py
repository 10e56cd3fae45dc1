"""Open-loop request schedule from a traffic file's parameters.

Requests are due on a schedule whatever the server does.  So that a seed
changes the order of the work and not the work, every seed gets the same
set of prompt lengths, output lengths and gaps between arrivals: each is
taken at evenly spaced quantiles of its distribution, and the seed
shuffles each set and draws the prompt tokens.

Parameters (a traffic file's ``"arrivals"``, ``"prompt_tokens"`` and
``"output_tokens"``):

- arrivals: ``{"rate_per_s": r}``, Poisson at ``r``;
- lengths: ``{"dist": "lognormal", "median": m, "sigma": s, "min": lo,
  "max": hi}``, ``{"dist": "uniform", "min": lo, "max": hi}`` or
  ``{"dist": "fixed", "value": n}``; drawn lengths are clipped to
  ``[min, max]``.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``, ascending."""
    u = _quantiles(n)
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif dist == "uniform":
        raw = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def arrival_times(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """Due times in seconds from the schedule's start, ascending."""
    rate = float(spec["rate_per_s"])
    gaps = -np.log1p(-_quantiles(n)) / rate
    return np.cumsum(rng.permutation(gaps))


def horizon_requests(arrivals: Dict, seconds: float) -> int:
    """Requests needed to keep arrivals coming for ``seconds``."""
    return max(1, math.ceil(float(arrivals["rate_per_s"]) * seconds))


def generate(traffic: Dict, seed: int, seconds: float, vocab: int) -> List[Dict]:
    """The schedule for a window of ``seconds`` after ``traffic["lead_s"]``
    seconds of lead-in: ``[{"due_s", "prompt", "n_out"}]`` by due time."""
    n = horizon_requests(traffic["arrivals"], seconds + traffic["lead_s"])
    rng = np.random.default_rng(seed)
    due = arrival_times(traffic["arrivals"], n, rng)
    prompt_len = rng.permutation(lengths(traffic["prompt_tokens"], n))
    n_out = rng.permutation(lengths(traffic["output_tokens"], n))
    return [
        {
            "due_s": float(due[i]),
            "prompt": rng.integers(0, vocab, size=int(prompt_len[i]), dtype=np.int32),
            "n_out": int(n_out[i]),
        }
        for i in range(n)
    ]
