"""Milliseconds a step spends in collectives while nothing else runs on
the device: operations on the `XLA Ops` line are serial, so an
all-reduce (or the `-done` that waits for one) that sits there is time
the core computes nothing; the part that overlaps compute is on the
async line and is not counted."""


def read(ctx, out, reduced, args):
    steps = reduced["modules"].get(args["module"])
    if not steps:
        return None
    exposed, found = 0.0, False
    for text, (_, seconds) in reduced["ops"].items():
        name = text.split(" = ", 1)[0]
        if any(k in name for k in args["ops"]):
            exposed += seconds
            found = True
    if not found:
        return None
    return 1e3 * exposed / steps[0]
