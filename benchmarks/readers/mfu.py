"""The whole step's share of the chip's peak: the operations the work of
the window needs (counted from shapes by the configuration's file under
counts/) over the window, the chips and the peak."""

from benchmarks.files import load_py


def read(ctx, out, reduced, args):
    counts = load_py("counts", ctx.cfg["counts"])
    c = out["counters"]
    if args["kind"] == "train":
        flops_per_s = counts.train_flops_per_item(ctx.cfg, ctx.mix) \
            * c["items_per_s_per_chip"]
    else:
        flops_per_s = counts.serve_flops(ctx.cfg, c) / out["window_s"] \
            / ctx.chips
    if not ctx.peaks or flops_per_s <= 0:
        return None
    return 100.0 * flops_per_s / ctx.peaks["bf16_flops"]
