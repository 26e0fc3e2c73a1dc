"""A configuration and its cells join the benchmark through files of
their own and entries appended at the end of `BENCHMARK.json`'s lists.
So every test that pins an accepted cell says what that cell has, never
what comes after it: each such pin holds on a copy of the manifest with
one configuration, one cell, and that cell on every shared serving
metric's list, appended."""

import json
import os
import shutil

import pytest

import test_hybrid_config
import test_latent_config
import test_scope_reader
import test_span_metrics
import test_tail
from benchmarks.files import HERE, Manifest, load_json

NEW_CONFIG = "appended-moe"
NEW_CELL = NEW_CONFIG + ".serve-chat"
SOURCE = os.path.join(HERE, "testdata", "tiny-sparse", "configs",
                      "tiny-sparse.json")
#: per-layer metrics that are one accepted cell's alone
_OWN = (".hybrid", ".mla")


def _appended(doc, tmp_path, before=None):
    """`doc` with NEW_CONFIG (a file of its own under `tmp_path`) and
    NEW_CELL added, as a later PR adds them: at the end of `configs` and
    `workloads`, and at the end of each serving metric's `workloads`
    that is not an accepted cell's own. With `before`, the configuration
    goes in front of that one instead."""
    doc = json.loads(json.dumps(doc))
    os.makedirs(tmp_path / "configs", exist_ok=True)
    shutil.copy(SOURCE, tmp_path / "configs" / (NEW_CONFIG + ".json"))
    config = {"name": NEW_CONFIG, "source": load_json(SOURCE)["source"],
              "file": "configs/" + NEW_CONFIG + ".json", "reduced": [],
              "why": "a configuration appended after the accepted ones"}
    names = [c["name"] for c in doc["configs"]]
    doc["configs"].insert(names.index(before) if before else len(names),
                          config)
    doc["workloads"].append({"name": NEW_CELL, "config": NEW_CONFIG,
                             "traffic": "chat-open-0.8", "chips": 1,
                             "why": "a cell appended after the accepted ones"})
    serving = set()
    for m in doc["end_to_end"]:
        if m["name"] in ("itl_p50_ms", "itl_p99_ms"):
            serving |= set(m["workloads"])
            m["workloads"].append(NEW_CELL)
    for m in doc["per_layer"]:
        if serving & set(m.get("workloads", [])) \
                and not m["name"].endswith(_OWN) \
                and not m["name"].startswith("mla_"):
            m["workloads"].append(NEW_CELL)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(doc, indent=2) + "\n")
    return Manifest(str(path))


def appended_only(accepted, candidate):
    """The accepted configurations' (name, file) and cells' names are, in
    order, where `candidate` has them: nothing changed or moved."""
    def order(doc):
        return ([(c["name"], c["file"]) for c in doc["configs"]],
                [w["name"] for w in doc["workloads"]])
    (configs, cells), (theirs, their_cells) = order(accepted), order(candidate)
    return theirs[:len(configs)] == configs \
        and their_cells[:len(cells)] == cells


PINS = [("latent", test_latent_config.cell_pins),
        ("hybrid", test_hybrid_config.cell_pins),
        ("chat_tails", test_tail.chat_tail_pins)] \
    + [("scope." + name, lambda m, n=name, c=cells:
        test_scope_reader.scope_metric_pins(m, n, c))
       for name, cells in test_scope_reader.SCOPE_METRICS.items()] \
    + [("span." + name, lambda m, n=name:
        test_span_metrics.span_metric_pins(m, n))
       for name in sorted(test_span_metrics.SPAN_METRICS)] \
    + [("split." + name, lambda m, n=name: test_tail.split_tail_pins(m, n))
       for name in test_tail.SPLIT_TAILS] \
    + [("shared." + name, lambda m, n=name:
        test_tail.shared_serving_pins(m, n))
       for name in test_tail.SHARED_SERVING]


@pytest.mark.parametrize("pin", [p for _, p in PINS],
                         ids=[i for i, _ in PINS])
def test_every_pin_holds_with_a_configuration_and_a_cell_appended(
        pin, tmp_path):
    accepted = Manifest()
    grown = _appended(accepted.doc, tmp_path)
    pin(grown)
    assert appended_only(accepted.doc, grown.doc)
    assert grown.config(NEW_CONFIG) == load_json(SOURCE)
    assert grown.doc["workloads"][-1]["name"] == NEW_CELL
    # among them the lists a new serving cell joins first
    for name in ("decode_step_ms", "decode_kv_write_ms",
                 "decode_attention_ms", "decode_experts_ms",
                 "engine_step_ms_p50"):
        entry = next(m for m in grown.doc["per_layer"] if m["name"] == name)
        assert entry["workloads"][-1] == NEW_CELL, name


def test_a_configuration_put_before_an_accepted_one_moves_it(tmp_path):
    """A moved configuration: the new one in front of an accepted one, as
    a test that pinned the latent configuration to the last place would
    have asked for. The order rule and that configuration's pin see it."""
    accepted = Manifest()
    moved = _appended(accepted.doc, tmp_path, before="kanana-2-30b-a3b")
    assert not appended_only(accepted.doc, moved.doc)
    with pytest.raises(AssertionError):
        test_latent_config.cell_pins(moved)
