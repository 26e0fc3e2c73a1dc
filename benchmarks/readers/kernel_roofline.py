"""A Pallas kernel's share of its roofline: for every event of the
kernel in the trace, the least time the chip could take (the larger of
counted operations over the peak and counted bytes over the memory's
bandwidth), summed, over the time the events took."""

from benchmarks.files import load_py
from benchmarks.trace.reduce import shapes


def split(text):
    """(result shapes, operand shapes) of a `custom-call`'s HLO text; the
    layout constraints after the operand list repeat shapes and are cut."""
    head, _, tail = text.partition("custom-call(")
    tail = tail.split("custom_call_target=", 1)[0]
    return shapes(head), shapes(tail)


def read(ctx, out, reduced, args):
    count = load_py("counts", args["count"])
    least = took = 0.0
    for text, seconds in reduced["kernels"]:
        results, operands = split(text)
        if not results or not operands:
            continue
        w = count.work(results, operands)
        if w is None:
            continue
        least += max(w[0] / ctx.peaks["bf16_flops"],
                     w[1] / ctx.peaks["hbm_bytes_per_s"])
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
