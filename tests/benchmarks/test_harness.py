"""One whole run of a tiny cell on the CPU, past the look for a chip: a
sound program comes out correct, and the program with its timed path
broken underneath comes out not correct, once for each fault."""

import json

import pytest


def _line_is_well_formed(result, metrics):
    line = json.loads(json.dumps(result))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == set(metrics)
    assert all(set(m) == {"value", "unit"} and m["value"] > 0
               for m in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    assert all(set(c) == {"value", "limit"}
               for c in line["compared"].values())


def test_sound_training_run_is_correct(run_tiny, capfd):
    result = run_tiny("tiny-lm.train")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3  # whole steps in the window
    _line_is_well_formed(result, {"train_items_per_s_per_chip", "setup_s"})
    assert set(result["compared"]) == {
        "loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
        "change_norm_gap", "grad_norm_median", "change_norm_median",
        "grad_diff_median", "change_diff_median"}
    # each number compared is printed beside its limit, last on stderr
    err = capfd.readouterr().err.strip().splitlines()
    assert all("compared" in l and "limit" in l for l in err[-9:])


def test_sound_serving_run_is_correct(run_tiny):
    result = run_tiny("tiny-lm.serve", seconds=1.0)
    assert result["correct"] is True
    assert result["attempted"] == 20 and result["failed"] == 0
    _line_is_well_formed(result, {"itl_p50_ms", "setup_s"})
    assert set(result["compared"]) == {"answers_short", "logit_gap_max"}


def test_sound_run_over_four_devices_is_correct(run_tiny):
    """`data=4` through DistriOptimizer on the CPU's virtual devices: the
    probe reads sharded state, the reference's rows lie over the same
    four devices."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    result = run_tiny("tiny-lm.train-dp4")
    assert result["correct"] is True and result["device"]["count"] == 4


def test_another_seed_is_correct_too(run_tiny):
    assert run_tiny("tiny-lm.train", seed=12345)["correct"] is True
    assert run_tiny("tiny-lm.serve", seed=12345)["correct"] is True


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        run_tiny, monkeypatch):
    from bigdl_tpu.optim.optim_method import Adam
    monkeypatch.setattr(Adam, "update",
                        lambda self, grads, state, params, lr: (params, state))
    result = run_tiny("tiny-lm.train")
    assert result["correct"] is False
    # the measure of the training bullet reads 1 for an unmoved leaf
    assert result["compared"]["change_norm_gap"]["value"] == pytest.approx(1)
    assert result["compared"]["grad_norm_gap"]["value"] == pytest.approx(1)


def test_half_of_the_batch_left_out_is_not_correct(run_tiny, monkeypatch):
    from bigdl_tpu.nn.criterion import TimeDistributedMaskCriterion as Crit
    whole = Crit.loss

    def half(self, output, target):
        n = output.shape[0] // 2
        return whole(self, output[:n], target[:n])  # mean over the rest
    monkeypatch.setattr(Crit, "loss", half)
    result = run_tiny("tiny-lm.train")
    assert result["correct"] is False
    c = result["compared"]
    assert c["grad_norm_gap"]["value"] > c["grad_norm_gap"]["limit"]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        run_tiny, monkeypatch, tiny_manifest):
    from bigdl_tpu.serving.generation import TokenStream
    vocab = tiny_manifest.config("tiny-lm")["vocab_size"]
    put = TokenStream._put

    def altered(self, tok):
        if len(self._tokens) == 2:
            tok = tok % vocab + 1
        put(self, tok)
    monkeypatch.setattr(TokenStream, "_put", altered)
    result = run_tiny("tiny-lm.serve", seconds=1.0)
    assert result["correct"] is False
    c = result["compared"]["logit_gap_max"]
    assert c["value"] > 10 * c["limit"]


def test_a_refused_request_counts_as_failed_and_not_correct(
        run_tiny, monkeypatch):
    from bigdl_tpu.serving import GenerationEngine
    from bigdl_tpu.serving.engine import QueueFullError
    generate, calls = GenerationEngine.generate, []

    def refusing(self, prompt, **kw):
        calls.append(1)
        if len(calls) == 3:
            raise QueueFullError("full")
        return generate(self, prompt, **kw)
    monkeypatch.setattr(GenerationEngine, "generate", refusing)
    result = run_tiny("tiny-lm.serve", seconds=1.0)
    assert result["failed"] == 1 and result["correct"] is False
    assert result["compared"]["answers_short"]["value"] == 1
