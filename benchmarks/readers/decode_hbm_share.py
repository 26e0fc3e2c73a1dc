"""How near a decode step comes to the memory's bandwidth: the bytes a
step has to read (the weights in the type they are served in, plus the
live cache prefix of the slots in use, counted from the requests'
positions over the window) over the device time of one run of the decode
program in the trace, times the peak bandwidth."""

import numpy as np

from benchmarks.files import load_py


def read(ctx, out, reduced, args):
    counts = load_py("counts", ctx.cfg["counts"])
    c = out["counters"]
    module = reduced["modules"].get(args["module"])
    if not module or not c.get("decode_steps"):
        return None
    serving = ctx.cfg["serving"]
    w = counts.decode_weight_bytes(ctx.cfg,
                                   np.dtype(serving["weight_dtype"]).itemsize)
    per_pos = counts.cache_bytes_per_position(
        ctx.cfg, np.dtype(serving["cache_dtype"]).itemsize)
    step_bytes = w + per_pos * c["cache_positions_read"] / c["decode_steps"]
    step_s = module[1] / module[0]
    return 100.0 * step_bytes / (step_s * ctx.peaks["hbm_bytes_per_s"])
