"""The serving cells' tails (PR 33): the mean of the slowest share of
all token gaps on hand-made gaps and the property it was tried for (no
edge between two modes to sit on, where a percentile has one), which
cell reports which tail end to end and which per layer, the window's own
depth share, and the `gaps` reading that a `benchmark` issue chooses a
tail from."""

import json
import os

import numpy as np
import pytest

from benchmarks.files import HERE, Manifest, load_py

CHAT, MIXED = "neox-3.6b.serve-chat", "smallthinker-21b.serve-mixed"


@pytest.fixture(scope="module")
def driver():
    return load_py("drivers", "serve_open_loop")


@pytest.fixture(scope="module")
def tail_mean():
    return load_py("readers", "counter_tail_mean").tail_mean


@pytest.mark.parametrize("values, share, want", [
    ([7.0], 0.01, 7.0),                              # n = 1: the one gap
    (range(1, 100), 0.01, 99.0),                     # n = 99: ceil(0.99) = 1
    (range(1, 101), 0.01, 100.0),                    # n = 100: exactly 1
    (range(1, 102), 0.01, 100.5),                    # n = 101: ceil(1.01) = 2
    (range(1, 101), 0.02, 99.5),
    (range(1, 101), 0.05, 98.0),
    (range(1, 1001), 0.05, 975.5),
    (range(1, 301), 0.01, 299.0),                    # 0.01 x 300 is 3
    (range(1, 101), 0.07, 97.0),          # 0.07 x 100 is 7, in floats too
    ([3.0, 1.0, 2.0, 5.0, 4.0], 0.4, 4.5),           # order does not matter
    ([2.0] * 50 + [9.0] * 50, 0.01, 9.0),            # ties: inside a mode
    ([9.0] * 99 + [2.0], 0.05, 9.0),
    ([2.0] * 97 + [9.0] * 3, 0.05, (3 * 9.0 + 2 * 2.0) / 5),  # across two
])
def test_tail_mean_is_the_mean_of_the_ceil_share_n_largest(
        tail_mean, values, share, want):
    assert tail_mean(list(values), share) == pytest.approx(want)
    assert tail_mean(np.asarray(list(values), np.float32), share) \
        == pytest.approx(want)


def _two_modes(n, in_upper, low=4.3, high=14.3):
    return [low] * (n - in_upper) + [high] * in_upper


@pytest.mark.parametrize("share, q", [(0.01, 99), (0.02, 98), (0.05, 95)])
def test_a_percentile_jumps_between_two_modes_and_the_tail_mean_does_not(
        driver, tail_mean, share, q):
    """Two modes 10 ms apart, the upper one holding just under `share` of
    100 000 gaps. Moving a twentieth of the tail's gaps from the lower
    mode to the upper carries the mode's edge across the percentile:
    the percentile moves by the whole distance between the modes, the
    tail mean by a fortieth of it: the property the statistic is
    reported for."""
    n, dist = 100_000, 10.0
    tail = int(share * n)
    before = _two_modes(n, tail - tail // 40)
    after = _two_modes(n, tail + tail // 40)
    assert driver.percentile(after, q) - driver.percentile(before, q) \
        == pytest.approx(dist)
    moved = tail_mean(after, share) - tail_mean(before, share)
    assert 0 < moved < dist / 10
    assert moved == pytest.approx(dist / 40)


def test_the_tail_mean_moves_in_even_steps_where_the_percentile_has_one(
        driver, tail_mean):
    """The upper mode grows from 0.5% to 1.5% of the gaps in ten even
    steps: the mean of the slowest 1% rises by the same amount each step
    until the mode fills the tail, and the 99th percentile makes all
    but a hundredth of the move in one."""
    n = 100_000
    grown = [_two_modes(n, k) for k in range(500, 1501, 100)]
    means = np.array([tail_mean(g, 0.01) for g in grown])
    p99 = np.array([driver.percentile(g, 99) for g in grown])
    rising = np.diff(means)[:5]
    assert np.allclose(rising, rising[0]) and rising[0] == pytest.approx(1.0)
    assert np.allclose(np.diff(means)[5:], 0)
    assert np.diff(p99).max() > 0.98 * 10.0 and p99[-1] - p99[0] == 10.0


@pytest.mark.parametrize("name, bound", [
    ("train_items_per_s_per_chip", 0.015), ("itl_p50_ms", 0.05),
    ("itl_p99_ms", 0.01), ("setup_s", 0.1)])
def test_the_bounds_are_those_that_pr_33_read_on_the_chip(name, bound):
    """PERF.md, section 2: each lies between twice the spread that the
    check's tightness rule read and eight times the widest."""
    e2e = {m["name"]: m for m in Manifest().doc["end_to_end"]}
    assert e2e[name]["bound"] == bound


def _reports(manifest, cell):
    names = {m["name"] for m in manifest.doc["end_to_end"]}
    return {m["name"] for m in manifest.metrics("end_to_end", cell, names)}


@pytest.mark.parametrize("cell, want", [
    (CHAT, {"itl_p50_ms", "setup_s"}),      # no tail fits a bound of 0.1
    (MIXED, {"itl_p50_ms", "itl_p99_ms", "setup_s"}),
    ("resnet50.train-b128", {"train_items_per_s_per_chip", "setup_s"}),
    ("neox-3.6b.train-t2048", {"train_items_per_s_per_chip", "setup_s"}),
])
def test_each_cell_reports_exactly_its_end_to_end_metrics(cell, want):
    assert _reports(Manifest(), cell) == want


@pytest.mark.parametrize("base, cell, want", [
    ("tiny", "tiny-lm.serve", {"itl_p50_ms", "setup_s"}),
    ("tiny-sparse", "tiny-sparse.serve",
     {"itl_p50_ms", "itl_p99_ms", "setup_s"}),
])
def test_the_tiny_manifests_report_what_the_cells_they_stand_for_do(
        base, cell, want):
    base = os.path.join(HERE, "testdata", base)
    assert _reports(Manifest(os.path.join(base, "BENCHMARK.json"), base),
                    cell) == want


def test_a_pause_of_the_host_is_in_the_tail_mean_and_not_in_the_percentile(
        driver, tail_mean):
    """What kept the tail mean from standing end to end (PERF.md,
    section 2): one 125 ms pause of the process, read by each of 20
    live slots, in 53 000 gaps of which 800 hold a prefill."""
    gaps = [4.3] * 52_200 + [16.0] * 800
    paused = gaps[20:] + [125.0] * 20
    assert driver.percentile(paused, 99) == driver.percentile(gaps, 99)
    assert tail_mean(paused, 0.01) - tail_mean(gaps, 0.01) \
        == pytest.approx(20 * (125.0 - 16.0) / 530)      # +4.1 ms on 16
    assert tail_mean(paused, 0.05) - tail_mean(gaps, 0.05) \
        == pytest.approx(20 * (125.0 - 4.3) / 2650)      # +0.9 ms on 7.8


def test_the_chat_cell_keeps_its_tails_per_layer():
    m = Manifest()
    e2e = {e["name"]: e for e in m.doc["end_to_end"]}
    assert e2e["itl_p99_ms"]["workloads"] == [MIXED]
    assert not any(CHAT in e.get("workloads", []) and "tail" in e["name"]
                   for e in m.doc["end_to_end"])
    per_layer = {e["name"]: e for e in m.doc["per_layer"]}
    for name, reader, args in [
            ("itl_p99_ms.chat", "counter_percentile",
             {"counter": "itl_ms", "q": 99}),
            ("itl_tail1_ms", "counter_tail_mean",
             {"counter": "itl_ms", "share": 0.01}),
            ("itl_tail5_ms", "counter_tail_mean",
             {"counter": "itl_ms", "share": 0.05})]:
        assert per_layer[name]["workloads"] == [CHAT]
        assert per_layer[name]["layer"] == "serving engine"
        spec = m.metric_file(name)
        assert (spec["reader"], spec["args"]) == (reader, args)


@pytest.mark.parametrize("name, want", [
    ("itl_p99_ms.chat", 16.0), ("itl_tail1_ms", 16.0),
    ("itl_tail5_ms", (15 * 16.0 + 35 * 4.0) / 50)])
def test_the_chat_cells_tails_read_every_gap_and_nothing_where_none_is(
        name, want):
    spec = Manifest().metric_file(name)
    read = load_py("readers", spec["reader"]).read
    out = {"counters": {"itl_ms": [4.0] * 985 + [16.0] * 15}}
    assert read(None, out, None, spec["args"]) == pytest.approx(want)
    assert read(None, {"counters": {}}, None, spec["args"]) is None
    assert read(None, {"counters": {"itl_ms": []}}, None, spec["args"]) \
        is None


@pytest.mark.parametrize("name", [
    "prefill_ms_p50", "serve_mfu", "itl_p95_ms", "ttft_p95_ms"])
def test_a_tail_metric_of_both_serving_cells_is_split_by_what_it_moves(name):
    """A per-layer metric's cells all report the metric it moves, so
    what moves the 99th percentile in the mixed cell, and in the chat
    cell has only the median to name, is two entries over one reader."""
    m = Manifest()
    per_layer = {e["name"]: e for e in m.doc["per_layer"]}
    mixed, chat = per_layer[name], per_layer[name + ".chat"]
    assert (mixed["workloads"], mixed["moves"]) == ([MIXED], "itl_p99_ms")
    assert (chat["workloads"], chat["moves"]) == ([CHAT], "itl_p50_ms")
    a, b = m.metric_file(name), m.metric_file(name + ".chat")
    assert (a["reader"], a.get("args")) == (b["reader"], b.get("args"))
    assert {k: mixed[k] for k in ("unit", "better", "source", "layer")} \
        == {k: chat[k] for k in ("unit", "better", "source", "layer")}


@pytest.mark.parametrize("name", ["compiles_in_window.serve",
                                  "gen_late_ms_p99", "decode_depth_share"])
def test_what_both_serving_cells_report_moves_what_both_report(name):
    entry = [m for m in Manifest().doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == [CHAT, MIXED]
    assert entry[0]["moves"] == "itl_p50_ms"


def _stats(by_depth):
    return {"decode_steps_by_depth": {str(d): n for d, n in by_depth.items()},
            "decode_read_depth_total": sum(d * n
                                           for d, n in by_depth.items())}


@pytest.mark.parametrize("warm, end, max_len, want", [
    # the warm-up's steps at the deepest rung do not count
    ({256: 0, 2048: 8}, {256: 100, 2048: 8}, 2048, 0.125),
    ({256: 3, 512: 3, 1024: 3, 2048: 3},
     {256: 13, 512: 23, 1024: 53, 2048: 13}, 2048,
     (10 * 256 + 20 * 512 + 50 * 1024 + 10 * 2048) / (90 * 2048)),
    ({16384: 5}, {16384: 1425}, 16384, 1.0),     # a model with no ladder
    ({64: 5}, {64: 5}, 64, None),                # no step in the window
])
def test_depth_share_is_the_windows_own(driver, warm, end, max_len, want):
    got = driver.depth_share(_stats(warm), _stats(end), max_len)
    assert got == (pytest.approx(want) if want is not None else None)


def test_depth_share_is_silent_where_the_program_does_not_count_it(driver):
    assert driver.depth_share({"decode_steps": 1}, {"decode_steps": 9},
                              2048) is None
    spec = Manifest().metric_file("decode_depth_share")
    read = load_py("readers", spec["reader"]).read
    assert read(None, {"counters": {}}, None, spec["args"]) is None
    assert read(None, {"counters": {"decode_depth_share": 0.49}}, None,
                spec["args"]) == 0.49
    assert (spec["source"], spec["moves"]) == ("program_counter",
                                               "itl_p50_ms")


def test_a_tiny_serving_run_reports_no_tail_and_counts_its_depth(
        tiny_manifest, tmp_path):
    from benchmarks.harness import run_cell
    keep = {}
    result = run_cell("tiny-lm.serve", 2 ** 31 + 11, 1.0, False,
                      manifest=tiny_manifest, require_chip=False,
                      scratch=str(tmp_path), keep=keep)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"itl_p50_ms", "setup_s"}
    out = keep["out"]
    # the driver computes what some manifest lists and nothing else;
    # which cell reports which is the manifest's
    assert set(out["end_to_end"]) == {"itl_p50_ms", "itl_p99_ms"}
    assert out["counters"]["decode_depth_share"] == 1.0  # one rung at 64
    assert out["counters"]["decode_steps"] > 0


def test_the_gaps_reading_on_the_tiny_cell(tiny_manifest, tmp_path,
                                           tail_mean):
    """`readings --what gaps`, past the look for a chip: one line a
    seed, every candidate statistic from the run's own gaps, the
    histogram and the kept array agreeing with them."""
    from benchmarks import readings
    seeds = [2 ** 31 + 11, 12345]
    lines = list(readings._gaps(
        "tiny-lm.serve", seeds, 1.0, str(tmp_path / "gaps"),
        manifest=tiny_manifest, require_chip=False,
        scratch=str(tmp_path / "scratch")))
    assert [l["seed"] for l in lines] == seeds
    for line in lines:
        line = json.loads(json.dumps(line))
        assert line["what"] == "gaps" and line["correct"] is True
        assert line["failed"] == 0 and line["requests"] == 20
        kept = np.load(tmp_path / "gaps"
                       / f"gaps-tiny-lm.serve-{line['seed']}.npy")
        assert kept.size == line["gaps"] == sum(line["hist"])
        assert len(line["hist"]) == 257 and line["hist_bin_ms"] == 0.25
        for share in (1, 2, 5):
            assert line[f"itl_tail{share}_ms"] == pytest.approx(
                tail_mean(kept, share / 100), rel=1e-5)
        assert line["itl_p50_ms"] <= line["itl_tail5_ms"] \
            <= line["itl_tail2_ms"] <= line["itl_tail1_ms"]
        assert line["itl_p99_ms"] <= line["itl_tail1_ms"]
        assert line["gaps_over_p50_plus_10ms"] \
            <= line["gaps_over_p50_plus_5ms"] <= line["gaps"]
        assert line["longest_gaps_ms"][0] == pytest.approx(kept.max(),
                                                           rel=1e-5)
        assert line["decode_depth_share"] == 1.0  # max_len 64: one rung
        assert line["decode_steps"] > 0 and line["lowerings_in_window"] == 0


def test_the_chat_mix_offers_what_it_offered_and_names_its_knee():
    """`rate_per_s`, the lengths, the slots and the buckets are those of
    PR 26; the knee is the one swept on today's program."""
    mix = Manifest().traffic("chat-open-0.8")
    assert mix["rate_per_s"] == 16.0
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 160,
                                 "sigma": 0.9, "min": 16, "max": 1024}
    assert mix["output_len"] == {"dist": "lognormal", "median": 96,
                                 "sigma": 0.6, "min": 16, "max": 256}
    assert mix["engine"] == {"slots": 32, "max_len": 2048,
                             "prefill_batch": 4, "queue_capacity": 4096,
                             "seq_buckets": [16, 32, 64, 128, 256, 512, 1024]}
    assert mix["knee_per_s"] == 32.0 and mix["knee_found"]
