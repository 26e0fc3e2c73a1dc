"""A percentile of the durations of one of the program's spans, as the
profiler's trace holds them (milliseconds)."""

import numpy as np


def read(ctx, out, reduced, args):
    durations = reduced["spans"].get(args["span"])
    if not durations:
        return None
    return float(np.percentile(np.asarray(durations) * 1e3, args["q"]))
