"""python3 -m benchmarks.readings --workload <name> --seeds 1,2,3 --what program,control,half_batch

The readings that a cell's limits are set from, on the chip at the
cell's own size, one JSON line a seed and reading:

  program     a short run of the cell as `benchmarks.run` makes it: each
              number compared, and the worst leaf
  control     the plain reference put in the program's place and computed
              in the precision below the one the configuration states
              (`--control fp8` for bfloat16), against the float32
              reference: has to come out as not correct
  half_batch  a planted fault: half of the batch left out, the mean taken
              over the rest (training cells; planted in the reference)
  shard_only  a planted fault: the exchange between chips left out, each
              chip keeping the gradient of its own rows (cells on four
              chips; planted in the reference as chip 0's rows alone)

  sweep       serving cells: the cell at each of `--rates` requests a
              second, to find the knee (the highest rate with no growing
              backlog and no refusal) that the mix then stores

The benchmark's own runs never call this; PERF.md holds what it read.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial


def _training_readings(ctx, what, control):
    from benchmarks import compare
    from benchmarks.reference import numerics as nx
    ref = ctx.reference
    x, y = ref.train_batch(ctx.cfg, ctx.mix, ctx.seed, ctx.chips)

    def trace(precision, rows):
        return nx.train_trace(
            partial(ref.loss, ctx.cfg, precision=precision),
            lambda: ref.init_weights(ctx.cfg, ctx.seed), x[:rows], y[:rows],
            ctx.cfg["optimizer"], steps=3,
            row_block=ref.row_block(ctx.cfg, ctx.mix))

    n = x.shape[0]
    want = trace("f32", n)
    for w in what:
        got = {"control": lambda: trace(control, n),
               "half_batch": lambda: trace("f32", n // 2),
               "shard_only": lambda: trace("f32", n // ctx.chips)}[w]()
        yield w, compare.training(got, want)


def _serving_readings(ctx, out, control):
    from benchmarks.files import load_py
    driver = load_py("drivers", ctx.mix["driver"])
    ref = ctx.reference
    weights = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, ctx.seed))
    sample = out["served"][:int(ctx.mix["check_requests"])]
    gap, n = driver.served_gaps(ctx, weights, sample, control=control)
    yield "control", {"logit_gap_max": {"value": gap, "tokens": n}}


def _sweep(workload, seed, seconds, rates):
    """One line a rate: the tails, and whether the backlog grew (first
    tokens of the last third of the requests against the first third's,
    and how long the run drained past its close)."""
    import numpy as np
    from benchmarks.files import Manifest
    from benchmarks.harness import run_cell

    class AtRate(Manifest):
        rate = None

        def traffic(self, name):
            return dict(super().traffic(name), rate_per_s=self.rate)

    m = AtRate()
    for rate in rates:
        m.rate = rate
        keep = {}
        res = run_cell(workload, seed, seconds, False, manifest=m, keep=keep)
        out = keep["out"]
        ttft = np.asarray(out["counters"]["ttft_ms"])
        k = len(ttft) // 3
        print(json.dumps({
            "rate_per_s": rate, "requests": len(ttft),
            "failed": res["failed"], "correct": res["correct"],
            **{n: v["value"] for n, v in res["metrics"].items()},
            "ttft_p50_first_third_ms": float(np.median(ttft[:k])),
            "ttft_p50_last_third_ms": float(np.median(ttft[-k:])),
            "drain_s": out["window_s"] - seconds,
            "tokens_per_s": out["counters"]["tokens_out"] / out["window_s"],
        }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    from benchmarks.harness import run_cell
    what = args.what.split(",")
    if what == ["sweep"]:
        _sweep(args.workload, int(args.seeds.split(",")[0]), args.seconds,
               [float(r) for r in args.rates.split(",")])
        return 0
    for seed in (int(s) for s in args.seeds.split(",")):
        keep = {}
        t = time.perf_counter()
        res = run_cell(args.workload, seed, args.seconds, False, keep=keep)
        ctx, out = keep["ctx"], keep["out"]
        if "program" in what:
            print(json.dumps({"seed": seed, "what": "program",
                              "correct": res["correct"],
                              "rows": keep["rows"],
                              "s": time.perf_counter() - t}), flush=True)
        rest = [w for w in what if w != "program"]
        if not rest:
            continue
        t = time.perf_counter()
        readings = _training_readings(ctx, rest, args.control) \
            if "program" in out else _serving_readings(ctx, out, args.control)
        for name, numbers in readings:
            print(json.dumps({"seed": seed, "what": name, "numbers": numbers,
                              "s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
