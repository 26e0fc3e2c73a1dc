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

  gaps        serving cells: one line a seed with the run's candidate
              tail statistics (the median, the 99th percentile, the mean
              of the slowest 1%, 2% and 5% of all token gaps), the count
              of gaps well over the median, the steps and the sender's
              lateness (a slow spell of the machine shows in both), the
              ten longest gaps (a pause of the whole process reads as
              one long gap in every live slot), and a histogram of the
              gaps in quarter milliseconds; with `--out <dir>` every
              gap, as `gaps-<cell>-<seed>.npy`. What a `benchmark` issue
              chooses a cell's tail metric and its bound from

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
            **out["end_to_end"],
            "setup_s": res["metrics"]["setup_s"]["value"],
            "ttft_p50_first_third_ms": float(np.median(ttft[:k])),
            "ttft_p50_last_third_ms": float(np.median(ttft[-k:])),
            "drain_s": out["window_s"] - seconds,
            "tokens_per_s": out["counters"]["tokens_out"] / out["window_s"],
        }), flush=True)


#: the gaps' histogram: quarter milliseconds up to 64 ms, the rest last
_HIST_BIN_MS, _HIST_BINS = 0.25, 256


def _gaps(workload, seeds, seconds, out_dir, **run_kw):
    """One dict a seed; `run_kw` goes to `run_cell` (a test's tiny
    manifest, past the look for a chip)."""
    import os
    import numpy as np
    from benchmarks.files import load_py
    from benchmarks.harness import run_cell
    tail_mean = load_py("readers", "counter_tail_mean").tail_mean

    def percentile(values, q):
        return float(np.percentile(values, q))

    for seed in seeds:
        keep = {}
        res = run_cell(workload, seed, seconds, False, keep=keep, **run_kw)
        ctx, out = keep["ctx"], keep["out"]
        c = out["counters"]
        gaps = np.asarray(c["itl_ms"], np.float64)
        p50 = percentile(gaps, 50)
        hist = np.bincount(np.minimum((gaps / _HIST_BIN_MS).astype(np.int64),
                                      _HIST_BINS), minlength=_HIST_BINS + 1)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            np.save(os.path.join(out_dir, f"gaps-{workload}-{seed}.npy"),
                    gaps.astype(np.float32))
        yield {
            "seed": seed, "what": "gaps", "correct": res["correct"],
            "failed": res["failed"], "requests": c["requests"],
            "gaps": int(gaps.size), "itl_p50_ms": p50,
            "itl_p99_ms": percentile(gaps, 99),
            **{f"itl_tail{k}_ms": tail_mean(gaps, k / 100)
               for k in (1, 2, 5)},
            "gaps_over_p50_plus_5ms": int((gaps > p50 + 5).sum()),
            "gaps_over_p50_plus_10ms": int((gaps > p50 + 10).sum()),
            "decode_steps": c["decode_steps"],
            "decode_depth_share": c.get("decode_depth_share"),
            "gen_late_ms_p99": percentile(c["gen_late_ms"], 99),
            "gen_late_ms_max": max(c["gen_late_ms"]),
            "ttft_p95_ms": percentile(c["ttft_ms"], 95),
            "lowerings_in_window": c["lowerings_in_window"],
            "longest_gaps_ms": np.sort(gaps)[-10:][::-1].tolist(),
            "window_s": out["window_s"],
            "setup_s": out["t_window"] - ctx.t_start,
            "hist_bin_ms": _HIST_BIN_MS, "hist": hist.tolist(),
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", default="program,control")
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from benchmarks.harness import run_cell
    what = args.what.split(",")
    if what == ["sweep"]:
        _sweep(args.workload, int(args.seeds.split(",")[0]), args.seconds,
               [float(r) for r in args.rates.split(",")])
        return 0
    if what == ["gaps"]:
        for line in _gaps(args.workload,
                          [int(s) for s in args.seeds.split(",")],
                          args.seconds, args.out):
            print(json.dumps(line), flush=True)
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
