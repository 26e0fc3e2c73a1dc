"""Work of one latent-attention prefill call of the grouped flash
forward (`flash_fwd_gqa` in ops/attention_kernel.py, with a `v` width of
its own), from the shapes in its `custom-call` and the configuration's
head widths: q [B * H, 1, T, dq], k [B * H, T, dq], v and the result
dv wide. Counted are the causal pairs a query may attend to, 2 *
(192 + 128) operations a pair and head at the PUBLISHED widths whatever
widths the call was padded to (a padded call counts its least form),
and the bytes of q, k, v and the result once at those widths."""

from benchmarks.trace.reduce import shape_bytes

KERNELS = ("flash_fwd_gqa",)


def work(cfg, kernel: str, results, operands):
    q = max(results + operands, key=shape_bytes)
    if kernel not in KERNELS or len(q[1]) != 4:
        return None
    bh, group, t, d = q[1]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = t * (t + 1) // 2
    flops = 2.0 * (dq + dv) * bh * group * pairs
    itemsize = shape_bytes(q) / (bh * group * t * d)
    return flops, float(itemsize * bh * group * t * 2 * (dq + dv))
