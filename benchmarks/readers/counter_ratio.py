"""One of the program's counters over another, over the window."""


def read(ctx, out, reduced, args):
    num = out["counters"].get(args["num"])
    den = out["counters"].get(args["den"])
    if num is None or not den:
        return None
    return args.get("scale", 1.0) * num / den
