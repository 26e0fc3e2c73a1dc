"""The roofline share of the Pallas kernels that carry one of the names
the count file lists (`KERNELS`): a `custom-call` of the trace is taken
by the name the program gave its `pallas_call` (`%flash_fwd_window.3 =
...`), never by its shapes alone, so a kernel of another name over the
same shapes is left out. For every such event the least time the chip
could take (the larger of counted operations over the peak and counted
bytes over the memory's bandwidth), summed, over the time the events
took. The count file is handed the configuration. Nothing to read (a
program with no such kernel): no value."""

import re

from benchmarks.files import load_py

_NAME = re.compile(r"^\s*%?([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)?\s*$")


def kernel_name(text: str):
    """`%flash_fwd_window.3 = (...) custom-call(...)` -> `flash_fwd_window`."""
    found = _NAME.match(text.split(" = ", 1)[0])
    return found.group(1) if found else None


def read(ctx, out, reduced, args):
    count = load_py("counts", args["count"])
    split = load_py("readers", "kernel_roofline").split
    least = took = 0.0
    for text, seconds in reduced["kernels"]:
        name = kernel_name(text)
        if name not in count.KERNELS:
            continue
        results, operands = split(text)
        w = count.work(ctx.cfg, name, results, operands) \
            if results and operands else None
        if w is None:
            continue
        least += max(w[0] / ctx.peaks["bf16_flops"],
                     w[1] / ctx.peaks["hbm_bytes_per_s"])
        took += seconds
    if took <= 0:
        return None
    return 100.0 * least / took
