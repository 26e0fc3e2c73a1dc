"""A number the driver counted."""


def read(ctx, out, reduced, args):
    return out["counters"].get(args["counter"])
