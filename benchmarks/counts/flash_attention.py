"""Work of one flash-attention kernel (forward, dq, or dk/dv), from the
shapes in its `custom-call`. Each of the three does two of the six
T x T matmuls the mathematics needs (forward: QK^T and PV; backward: dV
and dP, dQ and dK); the score matrix each backward kernel recomputes is
not counted. Causal attention needs half of each. Every operand is read
once and every result written once."""

from benchmarks.trace.reduce import shape_bytes


def work(results, operands, causal: bool = True):
    big = max(results + operands, key=shape_bytes)
    if len(big[1]) not in (3, 4):  # [B*H, T, D] as the kernel sees it
        return None
    *heads, t, d = big[1]
    bh = heads[0] * (heads[1] if len(heads) == 2 else 1)
    flops = 2 * (2.0 * bh * t * t * d) * (0.5 if causal else 1.0)
    return flops, float(sum(shape_bytes(s) for s in results + operands))
