"""`decode_hbm_share` for a model some of whose layers keep a fixed-size
state a slot in place of keys and values: the bytes a step has to move
are the weights, the live cache prefix of the layers that keep K/V, and
each LIVE slot's recurrent state read once and written once (a step
replaces it). Live slot-steps are counted from the answers: every token
of a request but its first, which its prefill gave. Over the device
time of one run of the decode program in the trace, times the peak
bandwidth."""

import numpy as np

from benchmarks.files import load_py


def read(ctx, out, reduced, args):
    counts = load_py("counts", ctx.cfg["counts"])
    c = out["counters"]
    module = reduced["modules"].get(args["module"])
    if not module or not c.get("decode_steps"):
        return None
    serving = ctx.cfg["serving"]
    cache_itemsize = np.dtype(serving["cache_dtype"]).itemsize
    w = counts.decode_weight_bytes(ctx.cfg,
                                   np.dtype(serving["weight_dtype"]).itemsize)
    per_pos = counts.cache_bytes_per_position(ctx.cfg, cache_itemsize)
    per_slot = counts.recurrent_bytes_per_slot(
        ctx.cfg, np.dtype(serving["state_dtype"]).itemsize, cache_itemsize)
    slot_steps = max(c["tokens_out"] - c["requests"], 0)
    step_bytes = w + (per_pos * c["cache_positions_read"]
                      + 2 * per_slot * slot_steps) / c["decode_steps"]
    step_s = module[1] / module[0]
    return 100.0 * step_bytes / (step_s * ctx.peaks["hbm_bytes_per_s"])
