"""A percentile of a list the driver kept (milliseconds of each step,
lateness of each send)."""

import numpy as np


def read(ctx, out, reduced, args):
    values = out["counters"].get(args["counter"])
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), args["q"]))
