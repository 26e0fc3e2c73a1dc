"""Work of one fused BatchNorm(+ReLU) tail kernel, from the shapes in
its `custom-call`: an elementwise pass over [rows, C], so every operand
is read once and every result written once, and a handful of operations
an element (the backward kernel's sums included)."""

from benchmarks.trace.reduce import shape_bytes

OPS_PER_ELEMENT = 8


def work(results, operands):
    """(flops, bytes) of the kernel, or None if the shapes are not this
    kernel's: its largest array is [rows, C]."""
    big = max(results + operands, key=shape_bytes)
    if len(big[1]) != 2:
        return None
    rows, c = big[1]
    return (float(OPS_PER_ELEMENT * rows * c),
            float(sum(shape_bytes(s) for s in results + operands)))
