"""The controls, at a size a test run can hold: the plain reference put
in the program's place and computed in the precision below the one the
configuration states (fp8 for bfloat16) has to come out as not correct
under the cell's limits, on three seeds; computed in the stated precision
it passes."""

from functools import partial

import pytest

from benchmarks import compare
from benchmarks.files import load_py
from benchmarks.reference import numerics as nx

SEEDS = (5, 6, 7)


def _trace(tiny_manifest, seed, precision):
    cfg = tiny_manifest.config("tiny-lm")
    mix = tiny_manifest.traffic("tiny-train")
    ref = load_py("reference", cfg["reference"])
    x, y = ref.train_batch(cfg, mix, seed, 1)
    return nx.train_trace(partial(ref.loss, cfg, precision=precision),
                          lambda: ref.init_weights(cfg, seed), x, y,
                          cfg["optimizer"], steps=3,
                          row_block=ref.row_block(cfg, mix))


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_in_fp8_is_not_correct(tiny_manifest, seed):
    limits = tiny_manifest.limits("tiny-lm.train")
    want = _trace(tiny_manifest, seed, "f32")
    rows = compare.judge(compare.training(_trace(tiny_manifest, seed, "fp8"),
                                          want), limits)
    failed = [r["name"] for r in rows if not r["ok"]]
    assert "grad_diff_median" in failed, rows
    # the configuration's own precision, emulated the same way, passes
    rows = compare.judge(compare.training(_trace(tiny_manifest, seed, "bf16"),
                                          want), limits)
    assert all(r["ok"] for r in rows), rows


def test_serving_control_in_fp8_is_not_correct(tiny_manifest, run_tiny):
    """At every position of what the tiny cell served, the token that the
    fp8 reference puts first: over a few hundred positions some lie
    further below the float32 reference's best than the limit allows."""
    from benchmarks.harness import run_cell
    limit = tiny_manifest.limits("tiny-lm.serve")["logit_gap_max"]
    driver = load_py("drivers", "serve_open_loop")
    worst = []
    for seed in SEEDS:
        keep = {}
        result = run_cell("tiny-lm.serve", seed, 1.0, False,
                          manifest=tiny_manifest, require_chip=False,
                          keep=keep)
        ctx, out = keep["ctx"], keep["out"]
        assert result["compared"]["logit_gap_max"]["value"] <= limit
        ref = ctx.reference
        w = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, seed))
        gap, n = driver.served_gaps(ctx, w, out["served"],
                                    control="fp8")
        assert n > 100
        worst.append(gap)
    assert min(worst) > limit, worst


def test_judge_needs_a_limit_for_every_number_and_skips_a_null_one():
    numbers = {"a": {"value": 0.5}, "b": {"value": float("nan")}}
    with pytest.raises(KeyError):
        compare.judge(numbers, {"a": 1.0})
    rows = compare.judge(numbers, {"a": 1.0, "b": 1.0})
    assert [r["ok"] for r in rows] == [True, False]  # NaN is not correct
    assert [r["name"] for r in compare.judge(numbers, {"a": 0.1, "b": None})] \
        == ["a"]
