"""Observability subsystem tests: spans, telemetry sinks, health monitors,
and the optimizer integration (per-step JSONL records + Chrome trace from a
real short training run, NaN-guard skip/raise semantics)."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bigdl_tpu.nn as nn
import bigdl_tpu.optim as optim
from bigdl_tpu.dataset.dataset import LocalDataSet
from bigdl_tpu.dataset.sample import MiniBatch
from bigdl_tpu.observability import (InMemorySink, JsonlSink, NanGuard,
                                     SpanTracer, StragglerDetector,
                                     SummarySink, Telemetry,
                                     ThroughputMonitor, TrainingHealthError)
from bigdl_tpu.optim.distri_optimizer import DistriOptimizer
from bigdl_tpu.optim.local_optimizer import LocalOptimizer
from bigdl_tpu.optim.metrics import Metrics


# ------------------------------------------------------------------ #
# spans
# ------------------------------------------------------------------ #
class TestSpans:
    def test_nesting_and_export(self, tmp_path):
        tr = SpanTracer(process_name="test-proc")
        with tr.span("outer", kind="phase"):
            with tr.span("inner"):
                pass
            with tr.span("inner2"):
                pass
        events = tr.events
        assert [e["name"] for e in events] == ["inner", "inner2", "outer"]
        outer = events[-1]
        for inner in events[:2]:
            # children lie within the parent's [ts, ts+dur] interval
            assert inner["ts"] >= outer["ts"] - 1
            assert inner["ts"] + inner["dur"] <= \
                outer["ts"] + outer["dur"] + 1
        assert outer["args"] == {"kind": "phase"}

        path = str(tmp_path / "trace.json")
        tr.export(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        spans = [e for e in evs if e["ph"] == "X"]
        assert any(m["name"] == "process_name" and
                   m["args"]["name"] == "test-proc" for m in metas)
        assert len(spans) == 3
        for e in spans:
            assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid",
                              "tid"}
            assert e["dur"] >= 0

    def test_reset(self):
        tr = SpanTracer()
        with tr.span("a"):
            pass
        tr.reset()
        assert tr.events == []
        assert tr.dropped_events == 0

    def test_max_events_bounds_memory(self):
        """Long runs must not grow host memory without bound: the oldest
        events are dropped past the cap and the drop count is reported in
        the exported process metadata."""
        tr = SpanTracer(max_events=2)
        for name in ("a", "b", "c"):
            with tr.span(name):
                pass
        assert [e["name"] for e in tr.events] == ["b", "c"]
        assert tr.dropped_events == 1
        meta = [e for e in tr.to_chrome_trace()["traceEvents"]
                if e["ph"] == "M" and e["name"] == "process_name"][0]
        assert meta["args"]["dropped_events"] == 1


# ------------------------------------------------------------------ #
# telemetry sinks
# ------------------------------------------------------------------ #
class TestTelemetry:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        tel = Telemetry(JsonlSink(path), resources=False)
        tel.run_start(model="M")
        tel.step(step=1, loss=0.5, lr=0.1, throughput=100.0,
                 step_time_s=0.01, records=32)
        tel.event("nan_guard", step=1, action="warn")
        tel.run_end(step=1, metrics={})
        tel.close()
        with open(path) as f:
            recs = [json.loads(line) for line in f]
        assert [r["type"] for r in recs] == ["run_start", "step", "event",
                                             "run_end"]
        assert all("time" in r for r in recs)
        step = recs[1]
        assert step["loss"] == 0.5 and step["throughput"] == 100.0

    def test_jsonl_append_vs_truncate(self, tmp_path):
        path = str(tmp_path / "s.jsonl")
        for _ in range(2):
            s = JsonlSink(path, append=True)
            s.emit({"a": 1})
            s.close()
        with open(path) as f:
            assert len(f.readlines()) == 2
        s = JsonlSink(path, append=False)
        s.emit({"a": 2})
        s.close()
        with open(path) as f:
            assert len(f.readlines()) == 1

    def test_resource_sampling(self):
        tel = Telemetry(sink := InMemorySink(), resources=True)
        tel.step(step=1, loss=0.0)
        rec = sink.steps()[0]
        # procfs is available on the linux CI image
        assert rec.get("host_rss_mb", 0) > 0

    def test_summary_sink_bridges_scalars(self, tmp_path):
        from bigdl_tpu.visualization.summary import TrainSummary
        summary = TrainSummary(str(tmp_path), "app")
        tel = Telemetry(SummarySink(summary), resources=False)
        tel.step(step=1, loss=0.25, throughput=10.0)
        tel.step(step=2, loss=0.125, throughput=20.0)
        got = summary.read_scalar("telemetry/loss")
        assert [(s, v) for s, v in got] == [(1, 0.25), (2, 0.125)]
        tel.close()

    def test_metrics_as_dict(self):
        m = Metrics()
        m.add("phase a", 2e9)
        m.add("phase a", 4e9)
        d = m.as_dict()
        assert d["phase a"]["count"] == 2
        assert d["phase a"]["mean"] == pytest.approx(3.0)
        assert d["phase a"]["total"] == pytest.approx(6.0)


# ------------------------------------------------------------------ #
# health monitors (unit)
# ------------------------------------------------------------------ #
class TestHealthMonitors:
    def test_nan_guard_action_validation(self):
        with pytest.raises(ValueError):
            NanGuard(action="explode")

    def test_nan_guard_warn_counts(self):
        g = NanGuard(action="warn")
        sink = InMemorySink()
        tel = Telemetry(sink, resources=False)
        g.observe({"step": 1, "loss": 1.0}, tel)
        assert g.nonfinite_steps == 0
        g.observe({"step": 2, "loss": float("nan")}, tel)
        g.observe({"step": 3, "loss": 1.0, "nonfinite_steps": 2}, tel)
        assert g.nonfinite_steps == 3
        events = [r for r in sink.records if r["type"] == "event"]
        assert [e["event"] for e in events] == ["nan_guard", "nan_guard"]

    def test_nan_guard_raise(self):
        g = NanGuard(action="raise")
        with pytest.raises(TrainingHealthError):
            g.observe({"step": 5, "loss": float("inf")})

    def test_straggler_detector(self):
        d = StragglerDetector(factor=3.0, window=16, min_history=4)
        sink = InMemorySink()
        tel = Telemetry(sink, resources=False)
        for i in range(8):
            d.observe({"step": i, "step_time_s": 0.01}, tel)
        assert d.stragglers == 0
        d.observe({"step": 8, "step_time_s": 0.2}, tel)
        assert d.stragglers == 1
        ev = [r for r in sink.records if r["type"] == "event"][0]
        assert ev["event"] == "straggler"
        assert ev["p50_step_time_s"] == pytest.approx(0.01)

    def test_throughput_monitor(self):
        m = ThroughputMonitor(tolerance=0.3, window=10, min_history=3)
        sink = InMemorySink()
        tel = Telemetry(sink, resources=False)
        for i in range(5):
            m.observe({"step": i, "throughput": 100.0}, tel)
        assert m.regressions == 0
        m.observe({"step": 5, "throughput": 60.0}, tel)
        assert m.regressions == 1
        ev = [r for r in sink.records if r["type"] == "event"][0]
        assert ev["event"] == "throughput_regression"


# ------------------------------------------------------------------ #
# optimizer integration
# ------------------------------------------------------------------ #
def _toy_batches(n_batches=8, batch=32, poison_step=None):
    """Classification MiniBatches; `poison_step` (0-based batch index)
    gets NaN features — a deterministically poisoned step."""
    rs = np.random.RandomState(0)
    out = []
    for i in range(n_batches):
        x = rs.randn(batch, 6).astype(np.float32)
        if i == poison_step:
            x[:] = np.nan
        y = (rs.randint(0, 2, size=batch) + 1).astype(np.int32)
        out.append(MiniBatch(x, y))
    return out


def _toy_model():
    return (nn.Sequential().add(nn.Linear(6, 8)).add(nn.ReLU())
            .add(nn.Linear(8, 2)).add(nn.LogSoftMax()))


class _OrderedDataSet(LocalDataSet):
    """LocalDataSet that feeds batches in order (no permutation, no
    epoch-boundary shuffling), so a poisoned batch lands on a known
    iteration."""

    def data(self, train):
        if not train:
            return iter(self.items)

        def looped():
            while True:
                yield from self.items

        return looped()

    def shuffle(self):
        pass


class TestOptimizerIntegration:
    def _run(self, opt_cls, iters=6, sync=1, batches=None, **monitors):
        model = _toy_model()
        ds = _OrderedDataSet(batches or _toy_batches())
        crit = nn.ClassNLLCriterion()
        opt = opt_cls(model, ds, crit)
        opt.set_optim_method(optim.SGD(learning_rate=0.05))
        opt.set_end_when(optim.max_iteration(iters))
        opt.set_sync_interval(sync)
        return opt

    @pytest.mark.parametrize("opt_cls", [LocalOptimizer, DistriOptimizer],
                             ids=["local", "distri"])
    def test_telemetry_stream_and_trace(self, tmp_path, opt_cls):
        """Acceptance: a short CPU training run emits (a) a valid JSONL
        stream with step/loss/throughput/step-time fields and (b) a
        Chrome-trace JSON with the loop's host phases."""
        path = str(tmp_path / "run.jsonl")
        sink = InMemorySink()
        opt = self._run(opt_cls, iters=5)
        opt.set_telemetry(Telemetry(JsonlSink(path), sink,
                                    grad_norms=True))
        tracer = SpanTracer()
        opt.set_tracer(tracer)
        opt.optimize()
        opt.telemetry.close()

        with open(path) as f:
            recs = [json.loads(line) for line in f]
        assert recs[0]["type"] == "run_start"
        assert recs[0]["loop"] == ("local" if opt_cls is LocalOptimizer
                                   else "distri")
        assert recs[-1]["type"] == "run_end"
        assert recs[-1]["step"] == 5
        assert "computing time average" in recs[-1]["metrics"]
        steps = [r for r in recs if r["type"] == "step"]
        assert [r["step"] for r in steps] == [1, 2, 3, 4, 5]
        for r in steps:
            assert math.isfinite(r["loss"])
            assert r["lr"] == pytest.approx(0.05)
            assert r["throughput"] > 0
            assert r["step_time_s"] > 0
            assert r["records"] == 32
            assert r["grad_norm"] > 0 and r["param_norm"] > 0
            assert r["host_rss_mb"] > 0
        # in-memory sink saw the identical stream
        assert sink.steps() == steps

        trace = str(tmp_path / "trace.json")
        tracer.export(trace)
        with open(trace) as f:
            doc = json.load(f)
        names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
        assert {"data fetch", "step dispatch", "loss sync"} <= names

    @pytest.mark.parametrize("opt_cls", [LocalOptimizer, DistriOptimizer],
                             ids=["local", "distri"])
    def test_nan_guard_skip_reverts_update(self, opt_cls):
        """A poisoned batch (NaN features) must not corrupt the weights:
        skip mode reverts that step's update in-graph and training
        continues to a finite loss."""
        sink = InMemorySink()
        opt = self._run(opt_cls, iters=6,
                        batches=_toy_batches(poison_step=2))
        opt.set_telemetry(Telemetry(sink, resources=False))
        opt.set_health_monitors(NanGuard(action="skip"))
        trained = opt.optimize()
        for leaf in jax.tree_util.tree_leaves(trained.ensure_params()):
            assert np.isfinite(np.asarray(leaf)).all()
        steps = sink.steps()
        assert sum(r.get("nonfinite_steps", 0) for r in steps) == 1
        # the poisoned step reports a NaN loss, later steps recover
        assert math.isnan(steps[2]["loss"])
        assert math.isfinite(steps[-1]["loss"])

    def test_nan_guard_skip_matches_clean_run(self):
        """Stronger skip property: params after [clean, clean, poisoned]
        equal params after just [clean, clean] — the poisoned update is a
        true no-op."""
        def run(batches, iters):
            model = _toy_model()
            opt = LocalOptimizer(model, _OrderedDataSet(batches),
                                 nn.ClassNLLCriterion())
            opt.set_optim_method(optim.SGD(learning_rate=0.05))
            opt.set_end_when(optim.max_iteration(iters))
            opt.set_health_monitors(NanGuard(action="skip"))
            return opt.optimize().ensure_params()

        clean = _toy_batches(n_batches=3)
        poisoned = _toy_batches(n_batches=3, poison_step=2)
        p_skip = run(poisoned, iters=3)
        p_clean = run(clean, iters=2)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
            p_skip, p_clean)

    @pytest.mark.parametrize("opt_cls", [LocalOptimizer, DistriOptimizer],
                             ids=["local", "distri"])
    def test_nan_guard_raise_aborts(self, opt_cls):
        sink = InMemorySink()
        opt = self._run(opt_cls, iters=6,
                        batches=_toy_batches(poison_step=2))
        opt.set_telemetry(Telemetry(sink, resources=False))
        opt.set_health_monitors(NanGuard(action="raise"))
        with pytest.raises(TrainingHealthError):
            opt.optimize()
        # the stream closes the aborted run: run_start pairs with run_abort
        assert sink.records[0]["type"] == "run_start"
        assert sink.records[-1]["type"] == "event"
        assert sink.records[-1]["event"] == "run_abort"
        assert "TrainingHealthError" in sink.records[-1]["error"]

    def test_nan_guard_warn_continues(self):
        opt = self._run(LocalOptimizer, iters=6,
                        batches=_toy_batches(poison_step=2))
        g = NanGuard(action="warn", check_grads=True)
        opt.set_health_monitors(g)
        opt.optimize()
        assert g.nonfinite_steps >= 1
        assert opt.optim_method.state["neval"] == 6

    def test_nan_guard_raise_recovers_via_checkpoint(self, tmp_path):
        """raise + checkpoint = rollback-on-NaN: DistriOptimizer's retry
        path reloads the newest snapshot and completes the run."""
        batches = _toy_batches(n_batches=8, poison_step=4)
        opt = self._run(DistriOptimizer, iters=8, batches=batches)
        opt.set_checkpoint(str(tmp_path / "ckpt"),
                           optim.several_iteration(2))
        opt.retry_interval_s = 0.01
        opt.set_health_monitors(NanGuard(action="raise"))
        # after the retry resumes from iteration 4's checkpoint, the replay
        # hits the same poisoned batch; un-poison it so the retry succeeds
        # (the rollback itself is what this test pins down)
        def unpoison(state):
            if state["neval"] >= 4:
                batches[4].get_input()[:] = 0.0
        opt.set_iteration_hook(unpoison)
        opt.optimize()
        assert opt.optim_method.state["neval"] >= 8

    @pytest.mark.parametrize("opt_cls", [LocalOptimizer, DistriOptimizer],
                             ids=["local", "distri"])
    def test_nan_guard_skip_with_partial_model_state(self, opt_cls):
        """Skip mode must honor the partial-state module contract: a
        stateful (BatchNorm) model whose params were loaded via
        set_params has model._state == {}, so the step's new_ms has a
        different dict structure than the old state — the revert must not
        tree_map the two against each other (regression: trace-time
        'Dict key mismatch' crash)."""
        model = (nn.Sequential().add(nn.Linear(6, 8))
                 .add(nn.BatchNormalization(8)).add(nn.ReLU())
                 .add(nn.Linear(8, 2)).add(nn.LogSoftMax()))
        params = model.init(jax.random.PRNGKey(3))
        model.set_params(params)  # loaded-weights path: _state stays {}
        ds = _OrderedDataSet(_toy_batches(poison_step=2))
        opt = opt_cls(model, ds, nn.ClassNLLCriterion())
        opt.set_optim_method(optim.SGD(learning_rate=0.05))
        opt.set_end_when(optim.max_iteration(6))
        opt.set_health_monitors(NanGuard(action="skip"))
        trained = opt.optimize()
        for leaf in jax.tree_util.tree_leaves(trained.ensure_params()):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_sync_interval_window_guard(self):
        """With sync_interval > 1 the guard still sees mid-window steps
        via the batched aux fetch (nonfinite_steps counts the window)."""
        sink = InMemorySink()
        opt = self._run(LocalOptimizer, iters=6, sync=3,
                        batches=_toy_batches(poison_step=1))
        opt.set_telemetry(Telemetry(sink, resources=False))
        opt.set_health_monitors(NanGuard(action="skip"))
        opt.optimize()
        steps = sink.steps()
        assert [r["step"] for r in steps] == [3, 6]
        assert steps[0]["nonfinite_steps"] == 1
        assert steps[1].get("nonfinite_steps", 0) == 0

    def test_no_instrumentation_no_aux(self):
        """Without telemetry/monitors the step stays uninstrumented (aux
        is empty) and training works as before."""
        opt = self._run(LocalOptimizer, iters=3)
        trained = opt.optimize()
        assert opt.optim_method.state["neval"] == 3
        out = np.asarray(trained.forward(
            jnp.asarray(np.zeros((2, 6), np.float32)), training=False))
        assert np.isfinite(out).all()


# ------------------------------------------------------------------ #
# the train loops' spans: every phase of an iteration has one
# ------------------------------------------------------------------ #
_LOOPS = {"local": (LocalOptimizer, False), "distri": (DistriOptimizer, False),
          "distri_elastic": (DistriOptimizer, True)}
_ITERS = 6


def _traced_loop(loop, sync):
    opt_cls, elastic = _LOOPS[loop]
    opt = opt_cls(_toy_model(), _OrderedDataSet(_toy_batches()),
                  nn.ClassNLLCriterion())
    opt.set_optim_method(optim.SGD(learning_rate=0.05))
    opt.set_end_when(optim.max_iteration(_ITERS))
    opt.set_sync_interval(sync)
    if elastic:
        opt.set_elastic()
    tracer = SpanTracer()
    opt.set_tracer(tracer)
    opt.optimize()
    spans = {}
    for e in tracer.events:
        if e["ph"] == "X":
            spans.setdefault(e["name"], []).append(
                (e["ts"], e["ts"] + e["dur"]))
    return {n: sorted(v) for n, v in spans.items()}


@pytest.fixture(scope="module", params=sorted(_LOOPS))
def loop_spans(request):
    return {"loop": request.param,
            **{sync: _traced_loop(request.param, sync) for sync in (1, 3)}}


def _inside(span, holders, eps=1.0):
    return [h for h in holders
            if h[0] - eps <= span[0] and span[1] <= h[1] + eps]


class TestTrainLoopSpans:
    @pytest.mark.parametrize("sync", [1, 3])
    def test_loss_sync_once_a_synced_step(self, loop_spans, sync):
        assert len(loop_spans[sync]["loss sync"]) == _ITERS // sync

    @pytest.mark.parametrize("name", ["step prepare", "step dispatch",
                                      "step bookkeeping", "validation"])
    @pytest.mark.parametrize("sync", [1, 3])
    def test_step_spans_once_a_step_synced_or_not(self, loop_spans, sync,
                                                  name):
        assert len(loop_spans[sync][name]) == _ITERS

    @pytest.mark.parametrize("sync", [1, 3])
    def test_loss_sync_is_one_call_with_no_span_inside(self, loop_spans,
                                                       sync):
        """`loss sync` is one span around the one `float(loss)`, after
        its step's dispatch: the loop opens nothing inside it."""
        s = loop_spans[sync]
        syncs = s["loss sync"]
        for one, dispatch in zip(syncs, s["step dispatch"][sync - 1::sync]):
            assert dispatch[1] <= one[0] + 1.0
        for name, spans in s.items():
            if name != "loss sync":
                assert not [x for x in spans if _inside(x, syncs)], name

    @pytest.mark.parametrize("sync", [1, 3])
    def test_bookkeeping_is_the_tail_of_the_step(self, loop_spans, sync):
        """From the end of `loss sync` (or of the data fetch, on a step
        that does not sync) to the end of the iteration: the next thing
        the loop's lane shows is the next step's `step prepare`."""
        s = loop_spans[sync]
        books = s["step bookkeeping"]
        for b, sync_span in zip(books[sync - 1::sync], s["loss sync"]):
            assert sync_span[1] <= b[0] + 1.0
        for b, dispatch in zip(books, s["step dispatch"]):
            assert dispatch[1] <= b[0] + 1.0
        for b, nxt in zip(books, s["step prepare"][1:]):
            assert b[1] <= nxt[0] + 1.0
        for v in s["validation"]:
            assert len(_inside(v, books)) == 1
        # nothing else of the loop's spans begins inside a tail
        for name in ("step prepare", "step dispatch", "loss sync",
                     "data fetch"):
            assert not [x for x in s[name] if _inside(x, books)], name

    @pytest.mark.parametrize("sync", [1, 3])
    def test_the_final_gather_has_a_span(self, loop_spans, sync):
        """The distributed loops end by pulling parameters and state to
        the host, device idle: after the last tail, under a span. The
        local loop leaves them on the device and has none."""
        s = loop_spans[sync]
        gathers = s.get("gather params", [])
        assert len(gathers) == (0 if loop_spans["loop"] == "local" else 1)
        for g in gathers:
            assert s["step bookkeeping"][-1][1] <= g[0] + 1.0

    @pytest.mark.parametrize("loop", sorted(_LOOPS))
    def test_a_tracer_changes_no_call_to_the_device(self, loop, monkeypatch):
        """Traced and untraced, the loop syncs the same way: a tracer
        adds no `block_until_ready` (the measured path is the path a
        user runs)."""
        calls = []
        wait = jax.block_until_ready

        def counted(x):
            calls.append(1)
            return wait(x)
        monkeypatch.setattr(jax, "block_until_ready", counted)
        opt_cls, elastic = _LOOPS[loop]
        counts = []
        for tracer in (None, SpanTracer()):
            opt = TestOptimizerIntegration()._run(opt_cls, iters=3)
            if elastic:
                opt.set_elastic()
            if tracer is not None:
                opt.set_tracer(tracer)
            del calls[:]
            opt.optimize()
            counts.append(len(calls))
        assert counts[0] == counts[1]

    def test_no_tracer_builds_no_span(self, monkeypatch):
        def boom(self, *a, **k):
            raise AssertionError("a span was built with no tracer attached")
        monkeypatch.setattr(SpanTracer, "span", boom)
        for opt_cls in (LocalOptimizer, DistriOptimizer):
            opt = TestOptimizerIntegration()._run(opt_cls, iters=3)
            opt.optimize()
            assert opt.optim_method.state["neval"] == 3
