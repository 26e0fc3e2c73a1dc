"""Work of one grouped-query flash-attention forward call
(`flash_fwd_gqa`, `flash_fwd_window` in ops/attention_kernel.py), from
the shapes in its `custom-call` and the configuration's window: q and
the result are [B * Hkv, group, T, D], k and v [B * Hkv, T, D]. Counted
are the causal pairs a query may attend to, inside the window where the
call is the windowed one (position p attends to min(p + 1, window)
keys), two matmuls of 2 * D operations a pair and query head; q read and
the result written once, K and V read once a K/V head, however many
query heads or q blocks share them."""

from benchmarks.trace.reduce import shape_bytes

KERNELS = ("flash_fwd_gqa", "flash_fwd_window")


def pairs(t: int, window) -> int:
    """Query-key pairs of one head over T positions."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def work(cfg, kernel: str, results, operands):
    q = max(results + operands, key=shape_bytes)
    if kernel not in KERNELS or len(q[1]) != 4:
        return None
    bh, group, t, d = q[1]
    window = cfg["sliding_window_size"] if kernel == "flash_fwd_window" \
        else None
    flops = 2 * 2.0 * d * bh * group * pairs(t, window)
    return flops, float(sum(shape_bytes(s) for s in results + operands))
