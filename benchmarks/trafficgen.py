"""The one traffic generator: a mix is a data file, this reads it.

Serving mixes are built so that a run's load does not depend on the
seed. For the n requests that the rate and the window give, prompt
lengths, output lengths and inter-arrival gaps are the n quantile
midpoints of their distributions: nothing is drawn. `--seed` permutes
each list and fills the token ids, so every seed offers exactly the same
requests and the same gaps, in another order. (PR 23's Poisson draws put
375 +- 19 requests into a 30 s window, a +-5% swing of offered load, and
its 99th-percentile gap swung with it.)

Arrival kinds (the copy of `bigdl_tpu/workload/record.py`'s seeded
generators, made repeatable): `exponential` is that file's
`poisson_arrivals`, `two_state` its `bursty_arrivals` (a share
`burst_fraction` of the window at `burst_factor` times the mean rate,
the rest at the compensating calm rate, in `dwells` alternating
stretches).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """n whole-number lengths at the quantile midpoints of `spec`:
    {"dist": "lognormal", "median", "sigma", "min", "max"},
    {"dist": "uniform", "min", "max"} or {"dist": "fixed", "value"}."""
    u = _midpoints(n)
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(v)) for v in u])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _exp_gaps(n: int) -> np.ndarray:
    """n quantile midpoints of the unit exponential, scaled to mean 1."""
    g = -np.log1p(-_midpoints(n))
    return g * (n / g.sum())


def arrival_gaps(spec: Dict[str, Any], n: int, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """n gaps that sum to `seconds`, permuted by `rng`."""
    kind = spec.get("kind", "exponential")
    if kind == "exponential":
        gaps = rng.permutation(_exp_gaps(n))
    elif kind == "two_state":
        frac, factor = spec["burst_fraction"], spec["burst_factor"]
        if not 0.0 < frac < 1.0 or factor <= 1.0 or frac * factor >= 1.0:
            raise ValueError("two_state needs 0 < burst_fraction < 1 < "
                             "burst_factor and burst_fraction * burst_factor < 1")
        dwells = int(spec.get("dwells", 4))
        n_burst = int(round(n * frac * factor))
        n_calm = n - n_burst
        # each state's gaps fill that state's share of the window
        burst = rng.permutation(_exp_gaps(n_burst)) * (frac / n_burst)
        calm = rng.permutation(_exp_gaps(n_calm)) * ((1 - frac) / n_calm)
        parts = []
        for b, c in zip(np.array_split(burst, dwells),
                        np.array_split(calm, dwells)):
            parts += [c, b]
        shift = int(rng.integers(0, len(parts)))
        gaps = np.concatenate(parts[shift:] + parts[:shift]) * n
    else:
        raise ValueError(f"unknown arrival kind {kind!r}")
    return gaps * (seconds / gaps.sum())


def serving_schedule(mix: Dict[str, Any], vocab: int, seed: int,
                     seconds: float) -> List[Dict[str, Any]]:
    """The requests of one window: [{"due_s", "prompt" (1-based ids,
    int32), "max_new_tokens"}], ordered by due time. The first is due at
    0 and the gaps, the one after the last request included, sum to
    `seconds`."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    plen = rng.permutation(quantile_lengths(mix["prompt_len"], n))
    olen = rng.permutation(quantile_lengths(mix["output_len"], n))
    gaps = arrival_gaps(mix.get("arrivals", {}), n, seconds, rng)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    shared = int(mix.get("shared_prefix_len", 0))
    prefix = rng.integers(1, vocab + 1, shared, dtype=np.int64)
    out = []
    for i in range(n):
        ids = rng.integers(1, vocab + 1, int(plen[i]), dtype=np.int64)
        k = min(shared, ids.size - 1)
        ids[:k] = prefix[:k]
        out.append({"due_s": float(due[i]), "prompt": ids.astype(np.int32),
                    "max_new_tokens": int(olen[i])})
    return out
