"""The traffic generator: the same requests under every seed, permuted."""

import numpy as np
import pytest

from benchmarks import trafficgen
from benchmarks.files import Manifest

SEEDS = (0, 7, 2 ** 31 + 12345)


@pytest.fixture(scope="module")
def chat():
    return Manifest().traffic("chat-open-0.8")


def _schedules(mix, seconds=30.0):
    return [trafficgen.serving_schedule(mix, 32000, s, seconds) for s in SEEDS]


def test_same_multiset_of_lengths_for_every_seed(chat):
    runs = _schedules(chat)
    for key in ("prompt", "max_new_tokens"):
        sizes = [sorted(len(r[key]) if key == "prompt" else r[key]
                        for r in run) for run in runs]
        assert sizes[0] == sizes[1] == sizes[2]


def test_same_multiset_of_gaps_and_they_sum_to_the_window(chat):
    gaps = []
    for run in _schedules(chat):
        due = np.array([r["due_s"] for r in run])
        assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 30.0
        gaps.append(np.sort(np.diff(due)))
    np.testing.assert_allclose(gaps[0][1:], gaps[1][1:], rtol=0, atol=0.2)
    n = len(_schedules(chat)[0])
    rng = np.random.default_rng(3)
    g = trafficgen.arrival_gaps(chat["arrivals"], n, 30.0, rng)
    assert g.sum() == pytest.approx(30.0, rel=1e-12)
    g2 = trafficgen.arrival_gaps(chat["arrivals"], n, 30.0,
                                 np.random.default_rng(4))
    np.testing.assert_allclose(np.sort(g), np.sort(g2), rtol=1e-12)


def test_order_and_token_ids_differ_between_seeds(chat):
    a, b, _ = _schedules(chat)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert not np.array_equal(a[0]["prompt"][:16], b[0]["prompt"][:16])


def test_same_seed_gives_the_same_requests(chat):
    a = trafficgen.serving_schedule(chat, 32000, SEEDS[2], 30.0)
    b = trafficgen.serving_schedule(chat, 32000, SEEDS[2], 30.0)
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["due_s"] == y["due_s"] for x, y in zip(a, b))


def test_request_count_follows_rate_and_lengths_keep_their_clips(chat):
    run = trafficgen.serving_schedule(chat, 32000, 1, 30.0)
    assert len(run) == round(chat["rate_per_s"] * 30.0)
    plen = [len(r["prompt"]) for r in run]
    olen = [r["max_new_tokens"] for r in run]
    assert min(plen) >= 16 and max(plen) <= 1024
    assert min(olen) >= 16 and max(olen) <= 256
    assert abs(np.median(plen) - 160) <= 4 and abs(np.median(olen) - 96) <= 3
    assert all(r["prompt"].min() >= 1 and r["prompt"].max() <= 32000
               for r in run)


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "fixed", "value": 9}, 9, 9),
    ({"dist": "uniform", "min": 4, "max": 12}, 4, 12)])
def test_other_length_distributions(dist, lo, hi):
    x = trafficgen.quantile_lengths(dist, 50)
    assert x.min() >= lo and x.max() <= hi and len(x) == 50


def test_two_state_bursts_keep_the_mean_rate_and_repeat():
    spec = {"kind": "two_state", "burst_fraction": 0.2, "burst_factor": 3.0}
    a = trafficgen.arrival_gaps(spec, 300, 30.0, np.random.default_rng(1))
    b = trafficgen.arrival_gaps(spec, 300, 30.0, np.random.default_rng(2))
    assert a.sum() == pytest.approx(30.0) and len(a) == 300
    np.testing.assert_allclose(np.sort(a), np.sort(b), rtol=1e-9)
    # a fifth of the window holds three fifths of the arrivals, so the
    # 180 shortest gaps take no more than that fifth
    assert 2.0 < np.sort(a)[:180].sum() <= 6.0


def test_shared_prefix_is_shared():
    mix = {"rate_per_s": 10, "shared_prefix_len": 6,
           "prompt_len": {"dist": "fixed", "value": 10},
           "output_len": {"dist": "fixed", "value": 4}}
    run = trafficgen.serving_schedule(mix, 100, 5, 2.0)
    assert all(np.array_equal(r["prompt"][:6], run[0]["prompt"][:6])
               for r in run)
    assert not np.array_equal(run[0]["prompt"][6:], run[1]["prompt"][6:])
