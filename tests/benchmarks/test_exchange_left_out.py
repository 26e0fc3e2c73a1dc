"""A run that leaves the exchange between chips out (each chip keeping
the gradient of its own rows: planted in the reference as chip 0's rows
alone, as `benchmarks.readings --what shard_only` plants it) is not
correct under a four-chip cell's limits, at a size a test run can hold:
the tiny LM over four virtual devices. (The test a four-chip cell of the
benchmark needs; `resnet50.train-dp4` itself waits for chip time: PERF.md
section 7.)"""

from functools import partial

import pytest

from benchmarks import compare
from benchmarks.files import Manifest, load_py
from benchmarks.reference import numerics as nx


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_a_run_that_leaves_the_exchange_out_is_not_correct(tiny_manifest,
                                                           seed):
    cfg = tiny_manifest.config("tiny-lm")
    mix = tiny_manifest.traffic("tiny-train-dp4")
    limits = tiny_manifest.limits("tiny-lm.train-dp4")
    ref = load_py("reference", cfg["reference"])
    chips = tiny_manifest.cell("tiny-lm.train-dp4")["chips"]
    x, y = ref.train_batch(cfg, mix, seed, chips)

    def trace(rows):
        return nx.train_trace(partial(ref.loss, cfg, precision="f32"),
                              lambda: ref.init_weights(cfg, seed), x[:rows],
                              y[:rows], cfg["optimizer"], steps=3,
                              row_block=ref.row_block(cfg, mix))
    n = x.shape[0]
    want = trace(n)
    alone = compare.judge(compare.training(trace(n // chips), want), limits)
    assert [r["name"] for r in alone if not r["ok"]], alone
    assert not all(r["ok"] for r in alone)
    whole = compare.judge(compare.training(trace(n), want), limits)
    assert all(r["ok"] for r in whole)
