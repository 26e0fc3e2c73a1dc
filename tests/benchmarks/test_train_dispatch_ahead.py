"""A training mix with a `sync_interval` lets the loop dispatch steps
ahead of the device between loss syncs. The window still counts every
step sent over the whole time to its end on the device, and the steps
the reference follows are read from the step itself, so the comparison
is the one an every-step sync gives."""

import pytest

from benchmarks.files import Manifest


@pytest.fixture()
def synced_every(monkeypatch):
    traffic = Manifest.traffic

    def set_interval(k):
        def mix(self, name):
            m = dict(traffic(self, name))
            if name == "tiny-train":
                m["sync_interval"] = k
            return m
        monkeypatch.setattr(Manifest, "traffic", mix)
    return set_interval


@pytest.mark.parametrize("every", [3, 1000])
def test_a_run_that_dispatches_ahead_compares_as_an_every_step_run(
        run_tiny, synced_every, every):
    seed = 2 ** 31 + 29
    base = run_tiny("tiny-lm.train", seed=seed)
    synced_every(every)
    ahead = run_tiny("tiny-lm.train", seed=seed)
    assert ahead["correct"] is True and ahead["failed"] == 0
    assert ahead["attempted"] >= 3
    assert ahead["metrics"]["train_items_per_s_per_chip"]["value"] > 0
    # the same three steps on the same program: the same numbers compared
    assert ahead["compared"] == base["compared"]


def test_a_step_that_returns_its_state_unchanged_is_not_correct_ahead(
        run_tiny, synced_every, monkeypatch):
    from bigdl_tpu.optim.optim_method import Adam
    monkeypatch.setattr(Adam, "update",
                        lambda self, grads, state, params, lr: (params, state))
    synced_every(3)
    result = run_tiny("tiny-lm.train")
    assert result["correct"] is False
    assert result["compared"]["change_norm_gap"]["value"] == pytest.approx(1)


def test_the_window_ends_with_the_last_step_on_the_device(
        tiny_manifest, synced_every, tmp_path):
    """Every step sent is in the count, and the sync-to-sync step times
    cover the window: their steps add up to the count."""
    from benchmarks.harness import run_cell
    synced_every(3)
    keep = {}
    run_cell("tiny-lm.train", 7, 0.6, False, manifest=tiny_manifest,
             require_chip=False, scratch=str(tmp_path), keep=keep)
    out = keep["out"]
    c = out["counters"]
    assert c["steps"] == out["attempted"] >= 3
    assert len(c["step_ms"]) >= 1 and min(c["step_ms"]) > 0
    assert c["items_per_s_per_chip"] == pytest.approx(
        c["steps"] * c["items_per_step"] / out["window_s"])
