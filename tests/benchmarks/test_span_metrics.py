"""The per-layer metrics that read the program's spans through
`span_percentile`: each reads its own span from a hand-made
`reduced["spans"]`, and nothing where the span is absent."""

import os
import types

import pytest

from benchmarks.files import Manifest, load_json, load_py, HERE


@pytest.fixture(scope="module")
def ctx():
    m = Manifest()
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]["TPU v5 lite"]
    return types.SimpleNamespace(
        cfg=m.config("neox-3.6b"), mix=m.traffic("train-t2048"), chips=1,
        peaks=peaks)


def _read(name, ctx, out, reduced):
    spec = Manifest().metric_file(name)
    return load_py("readers", spec["reader"]).read(ctx, out, reduced,
                                                   spec.get("args", {}))


#: metric -> (span, percentile, cells that list it)
_SERVE = ["neox-3.6b.serve-chat"]
_TRAIN = ["resnet50.train-b128", "neox-3.6b.train-t2048"]
#: the LM syncs its loss every step; ResNet-50 every 100 steps, so its
#: traced slice holds a sync only where the window's end meets one
_SYNCED_EVERY_STEP = ["neox-3.6b.train-t2048"]
SPAN_METRICS = {
    "engine_step_ms_p50": ("generate step", 50, _SERVE),
    "engine_step_ms_p95": ("generate step", 95, _SERVE),
    "decode_dispatch_ms_p50": ("decode dispatch", 50, _SERVE),
    "decode_fetch_ms_p50": ("decode fetch", 50, _SERVE),
    "decode_deliver_ms_p50": ("decode deliver", 50, _SERVE),
    "step_dispatch_ms_p50": ("step dispatch", 50, _TRAIN),
    "loss_sync_ms_p50": ("loss sync", 50, _SYNCED_EVERY_STEP),
    "step_bookkeeping_ms_p50": ("step bookkeeping", 50, _TRAIN),
}


def span_metric_pins(m, name):
    """`name` reads its span, in `m`, and lists at least its cells: a cell
    appended after them may list it too."""
    span, q, cells = SPAN_METRICS[name]
    spec = m.metric_file(name)
    assert spec["reader"] == "span_percentile"
    assert spec["args"] == {"span": span, "q": q}
    assert (spec["unit"], spec["source"]) == ("ms", "program_span")
    entry = [e for e in m.doc["per_layer"] if e["name"] == name]
    assert len(entry) == 1 and set(cells) <= set(entry[0]["workloads"])
    assert entry[0]["better"] == "lower"


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_span_metrics_read_their_span_and_nothing_else(ctx, name):
    span_metric_pins(Manifest(), name)
    span, q, _ = SPAN_METRICS[name]
    # durations in seconds, 1 to 20 ms: the median is 10.5 ms, the 95th
    # percentile 19.05 ms
    durations = [k * 1e-3 for k in range(1, 21)]
    want = {50: 10.5, 95: 19.05}[q]
    others = {s: [1.0] for s, _, _ in SPAN_METRICS.values() if s != span}
    assert _read(name, ctx, None, {"spans": {**others, span: durations}}) \
        == pytest.approx(want)
    # a program without the span (the parent of the PR that added it):
    # nothing to read, and the metric is left off the line
    assert _read(name, ctx, None, {"spans": others}) is None
    assert _read(name, ctx, None, {"spans": {span: []}}) is None
