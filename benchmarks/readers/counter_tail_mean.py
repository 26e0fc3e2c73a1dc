"""The mean of the slowest share of a list the driver kept (every gap
between two tokens): a tail with no edge between two modes to sit on,
where a percentile has one, and with every stall of the host in it,
where a percentile has none (PERF.md, section 2)."""

import math

import numpy as np


def tail_mean(values, share: float) -> float:
    """Mean of the `ceil(share x n)` largest of `values`."""
    v = np.sort(np.asarray(values, np.float64))
    # 0.07 x 100 is 7.000000000000001 in floats: 7 gaps, not 8
    k = max(1, math.ceil(share * v.size - 1e-9))
    return float(v[-k:].mean())


def read(ctx, out, reduced, args):
    values = out["counters"].get(args["counter"])
    if not values:
        return None
    return tail_mean(values, args["share"])
