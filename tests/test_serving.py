"""Serving subsystem tests (bigdl_tpu/serving).

The contracts under test are the ones docs/serving.md promises:
bucket-padded micro-batches are BIT-identical to offline
`LocalPredictor.predict`, the jitted forward compiles at most once per
shape bucket, failures and deadline lapses are isolated to their own
requests, admission control backpressures both ways, shutdown drains and
leaks no non-daemon thread (the session fixture in conftest.py is the
structural backstop), and the latency/queue telemetry flows through the
existing observability sinks.
"""

import threading
import time

import numpy as np
import pytest

import bigdl_tpu.nn as nn
from bigdl_tpu.dataset.sample import Sample
from bigdl_tpu.observability import InMemorySink, SpanTracer, Telemetry
from bigdl_tpu.optim.predictor import LocalPredictor, PredictionService
from bigdl_tpu.serving import (EngineClosedError, InferenceEngine,
                               QueueFullError, ServingError,
                               ServingTimeoutError, default_buckets)
from bigdl_tpu.serving.stats import WindowedHistogram


def _mlp():
    m = (nn.Sequential().add(nn.Linear(6, 16)).add(nn.Tanh())
         .add(nn.Linear(16, 3)).add(nn.LogSoftMax()))
    m.ensure_params()
    return m


def _conv_model():
    m = (nn.Sequential()
         .add(nn.SpatialConvolution(3, 8, 3, 3, pad_w=1, pad_h=1))
         .add(nn.ReLU()).add(nn.SpatialMaxPooling(2, 2))
         .add(nn.Reshape((8 * 4 * 4,))).add(nn.Linear(8 * 4 * 4, 5))
         .add(nn.LogSoftMax()))
    m.ensure_params()
    return m


def _samples(n, shape=(6,), seed=0):
    rs = np.random.RandomState(seed)
    return [Sample(rs.rand(*shape).astype(np.float32)) for _ in range(n)]


def _serve_one_batch(model, samples, **kw):
    """Queue `samples` against a PAUSED engine, then start it — exactly one
    gather window sees them all, so the batch size (pre-padding) is
    len(samples). Returns (results, engine stats)."""
    kw.setdefault("max_wait_ms", 25.0)
    eng = InferenceEngine(model, start=False, **kw)
    try:
        futs = [eng.submit(s) for s in samples]
        eng.start()
        results = [f.result(60) for f in futs]
        stats = eng.stats()
    finally:
        eng.close()
    return results, stats


def _settle(baseline, timeout=5.0):
    deadline = time.time() + timeout
    while threading.active_count() > baseline and time.time() < deadline:
        time.sleep(0.02)
    return threading.active_count()


class TestBuckets:
    def test_default_buckets(self):
        assert default_buckets(32) == [2, 4, 8, 16, 32]
        assert default_buckets(24) == [2, 4, 8, 16, 24]
        assert default_buckets(2) == [2]
        assert default_buckets(1) == [1]
        with pytest.raises(ValueError):
            default_buckets(0)

    def test_validation(self):
        m = _mlp()
        with pytest.raises(ValueError):
            InferenceEngine(m, queue_capacity=0, start=False)
        with pytest.raises(ValueError):
            InferenceEngine(m, admission="maybe", start=False)
        with pytest.raises(ValueError):
            InferenceEngine(m, buckets=[4, 4], start=False)
        with pytest.raises(ValueError):
            InferenceEngine(m, inflight=0, start=False)

    def test_explicit_buckets_cap_batch(self):
        eng = InferenceEngine(_mlp(), max_batch_size=32, buckets=[2, 6],
                              start=False)
        try:
            assert eng.max_batch_size == 6
            assert eng._bucket_for(1) == 2 and eng._bucket_for(5) == 6
        finally:
            eng.close()


class TestBucketPaddingParity:
    """Satellite: padded-batch outputs match the unpadded forward for
    every bucket size, bit for bit where this backend keeps them so and to
    float32 rounding in `test_multi_feature_model` — the floor-2 bucket
    default exists exactly because XLA's batch-1 gemv path is NOT
    bit-identical."""

    def test_every_batch_size_matches_offline_predict(self):
        model = _conv_model()
        samples = _samples(12, shape=(8, 8, 3))
        ref = LocalPredictor(model, batch_size=12).predict(samples)
        for n in range(1, 13):  # buckets [2,4,8,12]: every pad amount
            out, stats = _serve_one_batch(model, samples[:n],
                                          max_batch_size=12)
            assert stats["batches"] == 1
            for i in range(n):
                np.testing.assert_array_equal(out[i], ref[i])

    def test_table_output_model(self):
        # ConcatTable produces a Table; serving keeps LocalPredictor's
        # convention (first element) and stays bit-identical
        model = (nn.Sequential().add(nn.Linear(6, 8)).add(
            nn.ConcatTable().add(nn.Linear(8, 3)).add(nn.Linear(8, 2))))
        model.ensure_params()
        samples = _samples(7)
        ref = LocalPredictor(model, batch_size=7).predict(samples)
        out, _ = _serve_one_batch(model, samples, max_batch_size=8)
        for i in range(7):
            np.testing.assert_array_equal(out[i], ref[i])

    def test_multi_feature_model(self):
        # two-input model: features batch per-column into a Table input
        model = nn.ParallelTable().add(nn.Linear(4, 3)).add(nn.Linear(5, 3))
        model = nn.Sequential().add(model).add(nn.CAddTable()) \
            if hasattr(nn, "CAddTable") else model
        model.ensure_params()
        rs = np.random.RandomState(3)
        samples = [Sample([rs.rand(4).astype(np.float32),
                           rs.rand(5).astype(np.float32)])
                   for _ in range(5)]
        ref = LocalPredictor(model, batch_size=5).predict(samples)
        out, _ = _serve_one_batch(model, samples, max_batch_size=8)
        # the forward padded to bucket 8 against one at batch 5: this
        # backend's gemm promises no bit-equality across batch sizes (it
        # reads one float32 rounding apart, 3e-8); 1e-6 is what a bf16
        # product would miss a thousandfold (ROADMAP D9)
        for i in range(5):
            np.testing.assert_allclose(out[i], ref[i], rtol=0, atol=1e-6)


class TestCompileCount:
    """Satellite: many distinct request batch sizes, at most one XLA
    compile per bucket (counted via the jit cache)."""

    def test_compiles_bounded_by_buckets(self):
        model = _mlp()
        samples = _samples(12)
        eng = InferenceEngine(model, max_batch_size=12, max_wait_ms=25.0,
                              start=False)
        try:
            eng.start()
            for n in range(1, 13):  # 12 distinct batch sizes
                futs = [eng.submit(s) for s in samples[:n]]
                for f in futs:
                    f.result(60)
            assert eng.compile_count() <= len(eng.buckets) == 4
        finally:
            eng.close()

    def test_warmup_precompiles_all_buckets(self):
        model = _mlp()
        eng = InferenceEngine(model, max_batch_size=8)
        try:
            n = eng.warmup(_samples(1)[0])
            assert n == len(eng.buckets) == 3
            # traffic at every size afterwards adds NO compiles and every
            # batch is a bucket hit
            for k in range(1, 9):
                futs = [eng.submit(s) for s in _samples(k, seed=k)]
                for f in futs:
                    f.result(60)
            assert eng.compile_count() == n
            assert eng.stats()["bucket_hit_rate"] == 1.0
        finally:
            eng.close()


class TestConcurrency:
    def test_interleaved_clients_get_their_own_results(self):
        model = _mlp()
        samples = _samples(48)
        ref = LocalPredictor(model, batch_size=16).predict(samples)
        eng = InferenceEngine(model, max_batch_size=16, max_wait_ms=2.0)
        results = [None] * 48
        try:
            eng.warmup(samples[0])

            def client(i):
                results[i] = eng.predict(samples[i], timeout=60)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(48)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            eng.close()
        for i in range(48):
            np.testing.assert_array_equal(results[i], ref[i])

    def test_deadline_expired_isolated_from_batch_neighbors(self):
        # a long gather window guarantees all three land in ONE window;
        # the 5 ms deadline lapses inside it while neighbors complete
        model = _mlp()
        s = _samples(3)
        eng = InferenceEngine(model, max_batch_size=4, max_wait_ms=150.0)
        try:
            f1 = eng.submit(s[0])
            time.sleep(0.01)
            f_exp = eng.submit(s[1], deadline_ms=5)
            f2 = eng.submit(s[2])
            assert f1.result(60).shape == (3,)
            assert f2.result(60).shape == (3,)
            with pytest.raises(ServingTimeoutError):
                f_exp.result(60)
            assert eng.stats()["timed_out"] == 1
        finally:
            eng.close()

    def test_failed_batch_rejects_only_its_own_requests(self):
        # a bad feature signature is its own batch group: its trace-time
        # failure must not touch same-window neighbors, and the engine
        # keeps serving afterwards
        model = _mlp()
        good = _samples(4)
        bad = Sample(np.random.rand(9).astype(np.float32))
        eng = InferenceEngine(model, max_batch_size=8, max_wait_ms=25.0,
                              start=False)
        try:
            f_bad = eng.submit(bad)
            f_good = [eng.submit(s) for s in good]
            eng.start()
            for f in f_good:
                assert f.result(60).shape == (3,)
            with pytest.raises(ServingError):
                f_bad.result(60)
            assert eng.predict(good[0], timeout=60).shape == (3,)
            assert eng.stats()["failed"] == 1
        finally:
            eng.close()


class TestAdmission:
    def test_reject_on_full(self):
        model = _mlp()
        s = _samples(3)
        eng = InferenceEngine(model, queue_capacity=2, admission="reject",
                              start=False)
        try:
            eng.submit(s[0])
            eng.submit(s[1])
            with pytest.raises(QueueFullError):
                eng.submit(s[2])
            assert eng.stats()["rejected"] == 1
            eng.start()  # queued work still completes
        finally:
            eng.close()

    def test_client_side_timeout_raises_serving_timeout(self):
        # concurrent.futures.TimeoutError must not leak: callers handle
        # ONE exception family whether the lapse is client- or queue-side
        eng = InferenceEngine(_mlp(), start=False)  # paused: never serves
        try:
            t0 = time.perf_counter()
            with pytest.raises(ServingTimeoutError):
                eng.predict(_samples(1)[0], timeout=0.05)
            assert time.perf_counter() - t0 < 5.0
        finally:
            eng.close(drain=False)

    def test_block_admission_observes_deadline(self):
        model = _mlp()
        s = _samples(3)
        eng = InferenceEngine(model, queue_capacity=2, admission="block",
                              start=False)
        try:
            eng.submit(s[0])
            eng.submit(s[1])
            t0 = time.perf_counter()
            with pytest.raises(ServingTimeoutError):
                eng.submit(s[2], deadline_ms=50)
            assert time.perf_counter() - t0 < 5.0
            eng.start()
        finally:
            eng.close()

    def test_block_admission_unblocks_when_space_frees(self):
        model = _mlp()
        s = _samples(4)
        eng = InferenceEngine(model, queue_capacity=2, admission="block",
                              max_wait_ms=1.0, start=False)
        try:
            f0 = eng.submit(s[0])
            eng.submit(s[1])
            got = []

            def blocked_submit():
                got.append(eng.submit(s[2]))

            t = threading.Thread(target=blocked_submit)
            t.start()
            time.sleep(0.05)
            assert not got  # parked on the full queue
            eng.start()     # dispatcher drains -> space frees -> admitted
            t.join(10)
            assert got and got[0].result(60).shape == (3,)
            assert f0.result(60).shape == (3,)
        finally:
            eng.close()


class TestShutdown:
    def test_drain_close_resolves_everything(self):
        base = threading.active_count()
        model = _mlp()
        samples = _samples(24)
        eng = InferenceEngine(model, max_batch_size=8, max_wait_ms=1.0,
                              start=False)
        futs = [eng.submit(s) for s in samples]
        eng.start()
        eng.close()  # drain=True: every queued request finishes
        for f in futs:
            assert f.result(0).shape == (3,)  # already resolved
        assert _settle(base) == base
        eng.close()  # idempotent
        with pytest.raises(EngineClosedError):
            eng.submit(samples[0])

    def test_no_drain_close_fails_queued(self):
        model = _mlp()
        eng = InferenceEngine(model, start=False)
        futs = [eng.submit(s) for s in _samples(3)]
        eng.close(drain=False)
        for f in futs:
            with pytest.raises(EngineClosedError):
                f.result(0)
        # close-induced drops are 'cancelled', NOT 'failed' (an operator
        # watching serving_summary must not see a failure spike on every
        # drain-less shutdown)
        s = eng.stats()
        assert s["cancelled"] == 3 and s["failed"] == 0

    def test_interpreter_exit_without_close_does_not_hang(self):
        # legacy PredictionService callers never called close(); the
        # non-daemon dispatcher must not hang interpreter shutdown
        import subprocess
        import sys
        code = (
            "import os\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import numpy as np\n"
            "import bigdl_tpu.nn as nn\n"
            "from bigdl_tpu.dataset.sample import Sample\n"
            "from bigdl_tpu.optim.predictor import PredictionService\n"
            "m = nn.Sequential().add(nn.Linear(4, 2)).add(nn.LogSoftMax())\n"
            "svc = PredictionService(m, batch_size=8)\n"
            "print(svc.predict(Sample(np.ones(4, np.float32))).shape)\n"
            # no close(): interpreter exit must reap the dispatcher
        )
        r = subprocess.run([sys.executable, "-c", code], timeout=120,
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-500:]
        assert "(2,)" in r.stdout

    def test_close_unblocks_parked_producers(self):
        model = _mlp()
        s = _samples(3)
        eng = InferenceEngine(model, queue_capacity=1, admission="block",
                              start=False)
        eng.submit(s[0])
        errs = []

        def blocked():
            try:
                eng.submit(s[1])
            except EngineClosedError as e:
                errs.append(e)

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.05)
        eng.close(drain=False)
        t.join(10)
        assert not t.is_alive() and len(errs) == 1


class TestQuantizedServing:
    """Satellite: quantized modules (nn/quantized.py) serve through the
    engine. Both schemes quantize activations PER SAMPLE, so rows stay
    batch-independent and the engine's padded batches remain bit-identical
    to offline predict on the same quantized module."""

    @pytest.mark.parametrize("weight_only", [False, True])
    def test_quantized_parity(self, weight_only):
        from bigdl_tpu.nn.quantized import Quantizer
        model = _mlp()
        q = Quantizer.quantize(model, weight_only=weight_only)
        samples = _samples(6)
        ref = LocalPredictor(q, batch_size=6, convert=False).predict(samples)
        out, _ = _serve_one_batch(q, samples, max_batch_size=8,
                                  convert=False)
        for i in range(6):
            np.testing.assert_array_equal(out[i], ref[i])


class TestTelemetry:
    def test_stats_records_flow_through_sinks(self):
        model = _mlp()
        sink = InMemorySink()
        tracer = SpanTracer()
        eng = InferenceEngine(model, max_batch_size=8, max_wait_ms=1.0,
                              telemetry=Telemetry(sink, resources=False),
                              tracer=tracer, emit_every=1)
        try:
            eng.warmup(_samples(1)[0])
            for s in _samples(12, seed=2):
                eng.predict(s, timeout=60)
        finally:
            eng.close()
        stats = [r for r in sink.records if r["type"] == "serving_stats"]
        assert stats
        for key in ("queue_depth", "submitted", "completed", "batches",
                    "bucket_hit_rate", "latency_ms_p50", "latency_ms_p95",
                    "latency_ms_p99", "queue_wait_ms_p50", "batch_size_p50",
                    "time"):
            assert key in stats[-1], key
        summaries = [r for r in sink.records
                     if r["type"] == "serving_summary"]
        assert len(summaries) == 1
        assert summaries[0]["completed"] == 12
        names = {e["name"] for e in tracer.events}
        assert {"serve dispatch", "serve fetch"} <= names

    def test_sink_failure_does_not_kill_dispatcher(self):
        class PoisonSink(InMemorySink):
            def emit(self, record):
                raise OSError("disk full")

        eng = InferenceEngine(_mlp(), max_wait_ms=1.0, emit_every=1,
                              telemetry=Telemetry(PoisonSink(),
                                                  resources=False))
        try:
            # every batch tries to emit and fails; serving must continue
            for s in _samples(6, seed=9):
                assert eng.predict(s, timeout=60).shape == (3,)
        finally:
            eng.close()

    def test_stats_shape(self):
        eng = InferenceEngine(_mlp(), start=False)
        try:
            s = eng.stats()
            assert s["queue_depth"] == 0 and s["submitted"] == 0
            assert s["bucket_hit_rate"] is None  # no batches yet
            assert s["latency_ms_count"] == 0
        finally:
            eng.close()

    def test_windowed_histogram(self):
        h = WindowedHistogram(window=4)
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):
            h.record(v)
        q = h.quantiles()
        assert h.count == 5
        assert q["p50"] == pytest.approx(3.5)  # 1.0 fell out of the window
        snap = h.snapshot("lat", scale=1e3)
        assert snap["lat_count"] == 5 and snap["lat_p99"] > 0
        with pytest.raises(ValueError):
            WindowedHistogram(window=0)


class TestPredictionService:
    def test_facade_parity_and_single_forward_per_request(self):
        model = _mlp()
        samples = _samples(5)
        ref = LocalPredictor(model, batch_size=8).predict(samples)
        calls = []
        with PredictionService(model, batch_size=8) as svc:
            inner = svc.engine._pred._forward
            svc.engine._pred._forward = \
                lambda *a: calls.append(1) or inner(*a)
            out = svc.predict(samples[0])
            # the old cold-start path ran _forward twice for the first
            # request (compile + recompute); the engine runs it once
            assert len(calls) == 1
            # one request padded to the floor-2 bucket against the
            # offline forward at batch 5: float32 rounding apart (1e-7
            # read), not bit-equal on this backend's gemm (ROADMAP D9)
            np.testing.assert_allclose(out, ref[0], rtol=0, atol=1e-6)
            for i, s in enumerate(samples):
                np.testing.assert_allclose(svc.predict(s), ref[i],
                                           rtol=0, atol=1e-6)

    def test_facade_defaults_to_zero_gather_window(self):
        # a serial legacy caller blocked on its own future cannot feed
        # the window — the facade must not charge every call max_wait_ms
        with PredictionService(_mlp()) as svc:
            assert svc.engine.max_wait_s == 0.0
        with PredictionService(_mlp(), max_wait_ms=2.0) as svc:
            assert svc.engine.max_wait_s == pytest.approx(2e-3)

    def test_serves_from_converted_copy(self):
        # conversion must build a new module and leave the caller's intact
        model = (nn.Sequential().add(nn.Linear(6, 3)).add(nn.Dropout(0.5))
                 .add(nn.LogSoftMax()))
        model.ensure_params()
        with PredictionService(model) as svc:
            assert svc.model is not model
            assert model.training_mode  # caller's model untouched


@pytest.mark.slow
@pytest.mark.serving_stress
class TestServingStress:
    """Excluded from tier-1 (`not slow`): sustained mixed-signature,
    mixed-deadline traffic from many clients, full accounting at the end."""

    def test_sustained_mixed_traffic(self):
        base = threading.active_count()
        model = _mlp()
        samples = _samples(64)
        bad = Sample(np.random.rand(9).astype(np.float32))
        eng = InferenceEngine(model, max_batch_size=16, max_wait_ms=1.0,
                              queue_capacity=64)
        eng.warmup(samples[0])
        outcomes = {"ok": 0, "timeout": 0, "failed": 0}
        olock = threading.Lock()

        def client(k):
            rs = np.random.RandomState(k)
            for i in range(60):
                try:
                    if rs.rand() < 0.05:
                        eng.predict(bad, timeout=60)
                    else:
                        eng.predict(samples[rs.randint(64)], timeout=60,
                                    deadline_ms=float(rs.choice(
                                        [5000.0, 0.05])))
                    res = "ok"
                except ServingTimeoutError:
                    res = "timeout"
                except ServingError:
                    res = "failed"
                with olock:
                    outcomes[res] += 1

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = eng.stats()
        eng.close()
        total = 12 * 60
        assert sum(outcomes.values()) == total
        assert outcomes["ok"] > 0 and outcomes["timeout"] > 0
        assert stats["submitted"] == total
        assert stats["completed"] == outcomes["ok"]
        assert stats["timed_out"] == outcomes["timeout"]
        assert stats["failed"] == outcomes["failed"]
        assert stats["completed"] + stats["timed_out"] + \
            stats["failed"] == total
        assert eng.compile_count() <= len(eng.buckets) + 1  # +1: bad sig
        assert _settle(base) == base
