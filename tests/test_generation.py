"""Continuous-batching autoregressive serving (serving/generation.py).

Pins the PR's acceptance contracts:

- position-indexed single-step attention parity against the
  full-sequence apply at EVERY position (model layer),
- bit-exact greedy-token parity: continuous-batched decode ==
  one-request-at-a-time full-recompute decode, including requests that
  join/leave mid-flight, at >= 8 concurrent tagged streams,
- compile discipline: exactly one decode executable plus the warmed
  prefill buckets; steady-state decode emits ZERO new compile records
  under churn,
- streaming token futures, admission/deadline/close semantics shared
  with the engine, failure containment for the donated cache,
- generation telemetry (records, Prometheus gauges, kind=generate
  traces) and the fleet's restart-from-prompt exactly-once re-route.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.nn.attention import (MultiHeadAttention, TransformerBlock,
                                    rope)
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.export import PrometheusTextSink
from bigdl_tpu.observability.telemetry import validate_record
from bigdl_tpu.resilience import FaultInjector, FaultSpec
from bigdl_tpu.serving import (EngineClosedError, GenerationEngine,
                               QueueFullError, ServingError, ServingFleet,
                               ServingReroutedError, ServingTimeoutError,
                               ServingUnavailableError,
                               default_seq_buckets,
                               greedy_decode_reference)

VOCAB = 64


def small_model(max_len=32, n_layer=2, n_head=2, embed=32):
    m = TransformerLM(VOCAB, embed_dim=embed, n_layer=n_layer,
                      n_head=n_head, use_flash=False, max_len=max_len)
    m.ensure_params(jax.random.PRNGKey(0))
    return m


def prompts_for(n, rs=None, lo=3, hi=13):
    rs = rs or np.random.RandomState(7)
    return [rs.randint(1, VOCAB + 1,
                       size=rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


# --------------------------------------------------------------------------
class TestIncrementalApply:
    def test_rope_per_row_positions_match_shared(self):
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(2, 3, 5, 8).astype(np.float32))
        shared = rope(x)
        per_row = rope(x, jnp.broadcast_to(jnp.arange(5), (2, 5)))
        np.testing.assert_allclose(np.asarray(shared),
                                   np.asarray(per_row), atol=1e-6)

    def test_mha_apply_step_parity_every_position(self):
        """The satellite contract: the position-indexed single-step
        attention apply reproduces the full-sequence apply at EVERY
        position."""
        mha = MultiHeadAttention(16, 2, causal=True, use_rope=True,
                                 use_flash=False)
        params = mha.init(jax.random.PRNGKey(1))
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(2, 6, 16).astype(np.float32))
        full = np.asarray(mha.apply(params, x, None))
        kc = jnp.zeros((2, 2, 8, 8))
        vc = jnp.zeros((2, 2, 8, 8))
        for t in range(6):
            out, kc, vc = mha.apply_step(params, x[:, t:t + 1], kc, vc,
                                         jnp.full((2,), t, jnp.int32))
            np.testing.assert_allclose(np.asarray(out)[:, 0], full[:, t],
                                       atol=1e-5,
                                       err_msg=f"position {t}")

    def test_block_apply_step_parity_every_position(self):
        blk = TransformerBlock(16, 2, causal=True, use_rope=True,
                               use_flash=False)
        params = blk.init(jax.random.PRNGKey(3))
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(2, 5, 16).astype(np.float32))
        from bigdl_tpu.nn.module import ApplyContext
        full = np.asarray(blk.apply(params, x, ApplyContext()))
        kc = jnp.zeros((2, 2, 8, 8))
        vc = jnp.zeros((2, 2, 8, 8))
        for t in range(5):
            out, kc, vc = blk.apply_step(params, x[:, t:t + 1], kc, vc,
                                         jnp.full((2,), t, jnp.int32))
            np.testing.assert_allclose(np.asarray(out)[:, 0], full[:, t],
                                       atol=1e-5)

    def test_lm_apply_step_parity_every_position(self):
        m = small_model()
        params = m.ensure_params()
        rs = np.random.RandomState(5)
        toks = rs.randint(1, VOCAB + 1, size=(3, 9)).astype(np.int32)
        full = np.asarray(m.apply(params, jnp.asarray(toks), None))
        cache = m.init_cache(3, 16)
        for t in range(9):
            logp, cache = m.apply_step(params, jnp.asarray(toks[:, t]),
                                       cache,
                                       jnp.full((3,), t, jnp.int32))
            np.testing.assert_allclose(np.asarray(logp), full[:, t],
                                       atol=1e-5)

    def test_prefill_matches_full_apply_and_mixed_ages_decode(self):
        """Prefill's last-token log-probs are the full apply's (same
        math, causal mask hides right-padding), and a decode step over
        slots at MIXED positions continues each slot correctly."""
        m = small_model()
        params = m.ensure_params()
        rs = np.random.RandomState(6)
        toks = rs.randint(1, VOCAB + 1, size=(2, 9)).astype(np.int32)
        full = np.asarray(m.apply(params, jnp.asarray(toks), None))
        cache = m.init_cache(4, 16)
        lengths = np.array([5, 9], np.int32)
        padded = np.ones((2, 16), np.int32)
        padded[0, :5] = toks[0, :5]
        padded[1, :9] = toks[1, :9]
        last, cache = m.apply_prefill(params, jnp.asarray(padded), cache,
                                      jnp.array([2, 0], np.int32),
                                      jnp.asarray(lengths))
        last = np.asarray(last)
        # not bitwise: the padded prefill is [2, 16] and the full apply
        # [2, 9], and XLA orders a float32 sum by the shape (seen: 9.5e-07,
        # two ulps of log-probs between -4 and -8). 1e-5 is twenty ulps;
        # one product rounded to bf16 (2^-9 of a log-prob of 5: 1e-2)
        # would miss it a thousandfold
        np.testing.assert_allclose(last[0], full[0, 4], rtol=0, atol=1e-5)
        np.testing.assert_allclose(last[1], full[1, 8], rtol=0, atol=1e-5)
        # mixed slot ages: slot 2 decodes at position 5, slot 0 at 9
        nxt = last.argmax(-1).astype(np.int32) + 1
        step_toks = np.ones(4, np.int32)
        step_pos = np.zeros(4, np.int32)
        step_toks[2], step_pos[2] = nxt[0], 5
        step_toks[0], step_pos[0] = nxt[1], 9
        logp, cache = m.apply_step(params, jnp.asarray(step_toks), cache,
                                   jnp.asarray(step_pos))
        logp = np.asarray(logp)
        for slot, row, ln in ((2, 0, 5), (0, 1, 9)):
            ref_in = np.concatenate([toks[row, :ln],
                                     [nxt[row]]])[None]
            ref = np.asarray(m.apply(params, jnp.asarray(ref_in),
                                     None))[0, -1]
            np.testing.assert_allclose(logp[slot], ref, atol=1e-5)

    def test_init_cache_shapes_and_validation(self):
        m = small_model()
        cache = m.init_cache(4, 16)
        assert len(cache["k"]) == m.n_layer == len(cache["v"])
        assert cache["k"][0].shape == (4, 2, 16, 16)
        with pytest.raises(ValueError):
            m.init_cache(0, 16)
        with pytest.raises(ValueError):
            m.init_cache(4, 0)

    def test_default_seq_buckets(self):
        assert default_seq_buckets(64) == [8, 16, 32, 64]
        assert default_seq_buckets(48) == [8, 16, 32, 48]
        assert default_seq_buckets(8) == [8]
        assert default_seq_buckets(4) == [4]
        with pytest.raises(ValueError):
            default_seq_buckets(0)


# --------------------------------------------------------------------------
class TestGenerationEngine:
    def test_single_request_matches_reference(self):
        m = small_model()
        params = m.ensure_params()
        with GenerationEngine(m, slots=2, max_len=32,
                              max_new_tokens=6) as eng:
            prompt = np.array([3, 5, 7], np.int32)
            assert eng.generate(prompt).result(60.0) == \
                greedy_decode_reference(m, params, prompt, 6, pad_to=32)

    def test_stream_yields_same_tokens_as_result(self):
        m = small_model()
        with GenerationEngine(m, slots=2, max_len=32,
                              max_new_tokens=5) as eng:
            prompt = np.array([2, 4], np.int32)
            toks = list(eng.stream(prompt))
            assert toks == eng.generate(prompt).result(60.0)
            assert len(toks) == 5

    def test_parity_concurrent_tagged_streams(self):
        """THE acceptance contract: >= 8 concurrent tagged streams with
        different prompt lengths and token budgets — so requests join
        and leave the decode batch mid-flight — each produce EXACTLY the
        serial full-recompute reference's token sequence."""
        m = small_model()
        params = m.ensure_params()
        prompts = prompts_for(12)
        budgets = [3 + i % 7 for i in range(12)]
        fwd = jax.jit(lambda p, t: m.apply(p, t, None))
        refs = [greedy_decode_reference(m, params, prompts[i], budgets[i],
                                        pad_to=32, fwd=fwd)
                for i in range(12)]
        outs = [None] * 12
        # slots < requests forces churn: slots free mid-run and later
        # requests join while earlier neighbors still decode
        with GenerationEngine(m, slots=4, max_len=32) as eng:
            def worker(i):
                outs[i] = eng.generate(
                    prompts[i], max_new_tokens=budgets[i]).result(120.0)
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stats = eng.generation_stats()
        assert outs == refs
        assert stats["slot_joins"] == 12 and stats["slot_leaves"] == 12

    def test_compile_discipline_zero_steady_state_compiles(self):
        """Exactly one decode executable plus the warmed prefill buckets
        (asserted via PR 8 compile records); join/leave churn and token
        position NEVER add a compile record."""
        m = small_model()
        sink = InMemorySink()
        tel = Telemetry(sink, resources=False)
        with GenerationEngine(m, slots=3, max_len=32, max_new_tokens=6,
                              telemetry=tel) as eng:
            n = eng.warmup()
            expected = len(eng.buckets) * len(eng.seq_buckets) + 1
            assert n == expected
            compiles_before = [r for r in sink.records
                               if r.get("type") == "compile"]
            assert len(compiles_before) == expected
            decode_labels = [r for r in compiles_before
                             if r["label"].startswith("serving.decode/")]
            assert len(decode_labels) == 1
            prompts = prompts_for(10)
            threads = [threading.Thread(
                target=lambda i=i: eng.generate(
                    prompts[i], max_new_tokens=2 + i % 5).result(120.0))
                for i in range(10)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert eng.compile_count() == expected
        compiles_after = [r for r in sink.records
                          if r.get("type") == "compile"]
        assert len(compiles_after) == expected  # ZERO new under churn

    def test_streaming_tokens_arrive_before_completion(self):
        m = small_model(max_len=256)
        with GenerationEngine(m, slots=2, max_len=256) as eng:
            st = eng.generate(np.array([1, 2, 3], np.int32),
                              max_new_tokens=200)
            st.get(0, timeout=60.0)
            # 200 sequential decode steps cannot all have landed in the
            # time one step took: the stream is consumable mid-flight
            assert st.token_count() < 200
            assert not st.done
            assert len(st.result(120.0)) == 200

    def test_eos_stops_early_and_is_emitted(self):
        m = small_model()
        params = m.ensure_params()
        # greedy from this prompt starts [1, 61, 3, 7, ...]: the token at
        # index 2 has not come before it
        prompt = np.array([3, 5, 7], np.int32)
        ref = greedy_decode_reference(m, params, prompt, 8, pad_to=32)
        eos = ref[2]
        assert ref.index(eos) == 2
        with GenerationEngine(m, slots=2, max_len=32) as eng:
            out = eng.generate(prompt, max_new_tokens=8,
                               eos_id=eos).result(60.0)
        assert out == greedy_decode_reference(m, params, prompt, 8,
                                              eos_id=eos, pad_to=32)
        assert out == ref[:3] and out[-1] == eos

    def test_queue_deadline_timeout(self):
        m = small_model()
        eng = GenerationEngine(m, slots=2, max_len=32, start=False)
        try:
            st = eng.generate(np.array([1, 2], np.int32),
                              max_new_tokens=2, deadline_ms=1.0)
            time.sleep(0.02)
            eng.start()
            with pytest.raises(ServingTimeoutError):
                st.result(30.0)
            assert st.status == "timeout"
        finally:
            eng.close()

    def test_reject_admission_when_queue_full(self):
        m = small_model()
        eng = GenerationEngine(m, slots=2, max_len=32, queue_capacity=1,
                               admission="reject", start=False)
        try:
            eng.generate(np.array([1], np.int32), max_new_tokens=1)
            with pytest.raises(QueueFullError):
                eng.generate(np.array([1], np.int32), max_new_tokens=1)
        finally:
            eng.close(drain=False)

    def test_close_drain_finishes_queued_requests(self):
        m = small_model()
        params = m.ensure_params()
        eng = GenerationEngine(m, slots=2, max_len=32, start=False)
        prompt = np.array([5, 6], np.int32)
        streams = [eng.generate(prompt, max_new_tokens=3)
                   for _ in range(5)]
        eng.start()
        eng.close(drain=True)
        ref = greedy_decode_reference(m, params, prompt, 3, pad_to=32)
        for st in streams:
            assert st.result(1.0) == ref

    def test_close_without_drain_fails_queued(self):
        m = small_model()
        eng = GenerationEngine(m, slots=2, max_len=32, start=False)
        st = eng.generate(np.array([1, 2], np.int32), max_new_tokens=2)
        eng.close(drain=False)
        with pytest.raises(EngineClosedError):
            st.result(1.0)
        assert st.status == "cancelled"

    def test_cancel_frees_slot_keeps_emitted_tokens(self):
        m = small_model(max_len=256)
        with GenerationEngine(m, slots=2, max_len=256) as eng:
            st = eng.generate(np.array([1, 2], np.int32),
                              max_new_tokens=200)
            st.get(0, timeout=60.0)
            st.cancel()
            deadline = time.time() + 30.0
            while not st.done and time.time() < deadline:
                time.sleep(0.005)
            assert st.status == "cancelled"
            assert st.token_count() >= 1
            assert st.get(0) is not None  # emitted prefix stays readable
            # the slot is free again: a new request completes
            assert len(eng.generate(np.array([3], np.int32),
                                    max_new_tokens=2).result(60.0)) == 2

    def test_admission_validation(self):
        m = small_model()
        with GenerationEngine(m, slots=2, max_len=16) as eng:
            with pytest.raises(ValueError):
                eng.generate(np.array([], np.int32))
            with pytest.raises(ValueError):
                eng.generate(np.array([0, 1], np.int32))  # 1-based ids
            with pytest.raises(ValueError):
                eng.generate(np.array([1], np.int32), max_new_tokens=0)
            with pytest.raises(ValueError):
                # prompt + budget exceeds the cache depth
                eng.generate(np.arange(1, 13, dtype=np.int32),
                             max_new_tokens=8)
            with pytest.raises(ServingError):
                eng.submit(np.ones(4, np.float32))

    def test_requires_cache_aware_model(self):
        import bigdl_tpu.nn as nn_
        mlp = nn_.Sequential().add(nn_.Linear(4, 4))
        with pytest.raises(TypeError):
            GenerationEngine(mlp)

    def test_decode_fault_fails_active_then_recovers(self):
        """A failed decode execution cannot trust the DONATED cache: the
        active stream fails, the cache reallocates, and the next request
        still produces reference tokens."""
        m = small_model()
        params = m.ensure_params()
        prompt = np.array([2, 7, 4], np.int32)
        with GenerationEngine(m, slots=2, max_len=32) as eng:
            eng.warmup()
            with FaultInjector(FaultSpec("serve.decode", at_hit=1)):
                st = eng.generate(prompt, max_new_tokens=6)
                with pytest.raises(ServingError):
                    st.result(60.0)
                assert st.status == "error"
            out = eng.generate(prompt, max_new_tokens=6).result(60.0)
        assert out == greedy_decode_reference(m, params, prompt, 6,
                                              pad_to=32)

    def test_prefill_breaker_sheds_after_persistent_failures(self):
        m = small_model()
        with GenerationEngine(
                m, slots=2, max_len=32,
                breaker={"failure_threshold": 2,
                         "reset_timeout_s": 3600.0}) as eng:
            prompt = np.array([1, 2, 3], np.int32)
            with FaultInjector(FaultSpec("serve.forward", times=10)):
                for _ in range(2):
                    with pytest.raises(ServingError):
                        eng.generate(prompt,
                                     max_new_tokens=2).result(60.0)
                st = eng.generate(prompt, max_new_tokens=2)
                with pytest.raises(ServingUnavailableError):
                    st.result(60.0)
                assert st.status == "shed"
            health = eng.health()
            assert health["status"] == "degraded"
            assert health["open_buckets"]

    def test_generation_records_and_gauges(self):
        m = small_model()
        sink = InMemorySink()
        prom = PrometheusTextSink()
        tel = Telemetry(sink, prom, resources=False)
        with GenerationEngine(m, slots=2, max_len=32, telemetry=tel,
                              emit_every=1) as eng:
            eng.generate(np.array([1, 2, 3], np.int32),
                         max_new_tokens=4).result(60.0)
        gen = [r for r in sink.records if r.get("type") == "generation"]
        assert gen
        for r in sink.records:
            if r.get("type") in ("generation", "trace",
                                 "serving_summary", "compile"):
                validate_record(r)
        last = gen[-1]
        assert last["tokens_total"] == 4
        assert last["slot_joins"] == 1 and last["slot_leaves"] == 1
        text = prom.render()
        assert "bigdl_tpu_serving_tokens_per_sec" in text
        assert "bigdl_tpu_serving_decode_occupancy" in text
        assert "bigdl_tpu_serving_tokens_total 4" in text

    def test_trace_record_prefill_decode_critical_path(self, tmp_path):
        m = small_model()
        sink = InMemorySink()
        from bigdl_tpu.observability import JsonlSink
        path = str(tmp_path / "gen.jsonl")
        tel = Telemetry(sink, JsonlSink(path), resources=False)
        with GenerationEngine(m, slots=2, max_len=32,
                              telemetry=tel) as eng:
            eng.generate(np.array([1, 2, 3], np.int32),
                         max_new_tokens=4).result(60.0)
        traces = [r for r in sink.records if r.get("type") == "trace"]
        assert len(traces) == 1
        t = traces[0]
        assert t["kind"] == "generate" and t["status"] == "ok"
        assert t["tokens"] == 4
        names = [p["name"] for p in t["critical_path"]]
        assert names == ["queue", "prefill", "decode"]
        for f in ("queue_wait_ms", "prefill_ms", "decode_ms",
                  "latency_ms"):
            assert isinstance(t[f], (int, float))
        # metrics_cli trace renders the prefill->decode critical path
        import io
        from bigdl_tpu.tools import metrics_cli
        out = io.StringIO()
        assert metrics_cli.trace(t["trace_id"][:8], [path], out=out) == 0
        assert "prefill" in out.getvalue() and "decode" in out.getvalue()

    def test_mixed_seq_buckets_group_correctly(self):
        """Prompts in DIFFERENT pad buckets admitted together still come
        out right (per-bucket prefill groups)."""
        m = small_model()
        params = m.ensure_params()
        fwd = jax.jit(lambda p, t: m.apply(p, t, None))
        short = np.array([1, 2], np.int32)            # bucket 8
        long = np.arange(1, 15, dtype=np.int32)       # bucket 16
        eng = GenerationEngine(m, slots=4, max_len=32, start=False)
        try:
            s1 = eng.generate(short, max_new_tokens=4)
            s2 = eng.generate(long, max_new_tokens=4)
            s3 = eng.generate(short, max_new_tokens=4)
            eng.start()
            assert s1.result(60.0) == greedy_decode_reference(
                m, params, short, 4, pad_to=32, fwd=fwd)
            assert s2.result(60.0) == greedy_decode_reference(
                m, params, long, 4, pad_to=32, fwd=fwd)
            assert s3.result(60.0) == s1.result(0.0)
        finally:
            eng.close()


# --------------------------------------------------------------------------
class TestFleetGeneration:
    @staticmethod
    def _fleet(m, n=3, slots=2, max_new=6, max_len=32, **kw):
        return ServingFleet(
            engine_factory=lambda rid: GenerationEngine(
                m, slots=slots, max_len=max_len, max_new_tokens=max_new,
                replica_id=rid),
            n_replicas=n, **kw)

    def test_session_pins_stream_to_one_replica(self):
        m = small_model()
        params = m.ensure_params()
        prompt = np.array([3, 5, 7, 9], np.int32)
        ref = greedy_decode_reference(m, params, prompt, 6, pad_to=32)
        with self._fleet(m) as fleet:
            rids = set()
            for _ in range(3):
                st = fleet.generate(prompt, session="user-1")
                assert st.result(60.0) == ref
                rids.add(st.replica_id)
            assert len(rids) == 1
            assert fleet.fleet_counters()["generations_total"] == 3

    def test_replica_loss_restarts_from_prompt_exactly_once(self):
        """A decode stream is stateful: replica loss re-runs it FROM THE
        PROMPT on a survivor; greedy determinism + index-based pulls
        give the caller every token exactly once."""
        m = small_model(max_len=256)
        params = m.ensure_params()
        prompt = np.array([3, 5, 7], np.int32)
        # a long budget keeps the stream mid-flight when the crash lands
        with self._fleet(m, n=2, max_new=200, max_len=256) as fleet:
            st = fleet.generate(prompt, session="s", max_new_tokens=200)
            first = st.replica_id
            st.get(0, timeout=60.0)
            fleet.fail(first)
            out = st.result(120.0)
            assert out == greedy_decode_reference(m, params, prompt, 200,
                                                  pad_to=256)
            assert st.reroutes == 1 and st.replica_id != first
            assert fleet.fleet_counters()["stream_reroutes_total"] == 1

    def test_non_idempotent_stream_fails_fast(self):
        m = small_model(max_len=256)
        with self._fleet(m, n=2, max_new=200, max_len=256) as fleet:
            st = fleet.generate(np.array([1, 2], np.int32), session="s",
                                max_new_tokens=200, idempotent=False)
            st.get(0, timeout=60.0)
            fleet.fail(st.replica_id)
            with pytest.raises(ServingReroutedError):
                st.result(120.0)
            assert st.reroutes == 0

    def test_exactly_once_reroute_budget(self):
        """A stream that already re-routed once fails fast on the second
        loss (the router's exactly-once contract)."""
        m = small_model(max_len=256)
        with self._fleet(m, n=3, max_new=250, max_len=256) as fleet:
            st = fleet.generate(np.array([1, 2], np.int32), session="s",
                                max_new_tokens=250)
            st.get(0, timeout=60.0)
            fleet.fail(st.replica_id)
            st.get(st._stream.token_count() + 1, timeout=60.0)
            assert st.reroutes == 1
            fleet.fail(st.replica_id)
            with pytest.raises(ServingReroutedError):
                st.result(120.0)

    def test_attach_skips_full_replica(self):
        """A replica whose admission fails shed-shaped (full queue) is
        excluded and the next attempt tries another — generate() gets
        the same route_attempts discipline as submit()."""
        m = small_model()
        params = m.ensure_params()
        engines = {}

        def factory(rid):
            # replica0: queue of 1, dispatcher never started -> any
            # generate() on it rejects QueueFullError
            if rid == "replica0":
                eng = GenerationEngine(m, slots=2, max_len=32,
                                       queue_capacity=1,
                                       admission="reject", start=False,
                                       replica_id=rid)
                eng.generate(np.array([1], np.int32), max_new_tokens=1)
            else:
                eng = GenerationEngine(m, slots=2, max_len=32,
                                       replica_id=rid)
            engines[rid] = eng
            return eng

        prompt = np.array([2, 4, 6], np.int32)
        with ServingFleet(engine_factory=factory, n_replicas=2) as fleet:
            for _ in range(4):  # whatever the pick order, it must land
                st = fleet.generate(prompt, max_new_tokens=3)
                assert st.result(60.0) == greedy_decode_reference(
                    m, params, prompt, 3, pad_to=32)
                assert st.replica_id == "replica1"

    def test_total_outage_emits_fleet_generate_trace(self):
        """A generate() that fails at admission (no healthy replica)
        must burn error budget: one kind=fleet_generate trace, so the
        SLO stream cannot stay all-green through a total outage."""
        m = small_model()
        sink = InMemorySink()
        tel = Telemetry(sink, resources=False)
        with self._fleet(m, n=1, telemetry=tel) as fleet:
            fleet.fail("replica0")
            with pytest.raises(ServingUnavailableError):
                fleet.generate(np.array([1, 2], np.int32))
        traces = [r for r in sink.records if r.get("type") == "trace"
                  and r.get("kind") == "fleet_generate"]
        assert len(traces) == 1 and traces[0]["status"] == "shed"

    def test_done_false_while_recoverable(self):
        """A backing-stream failure the next get() would transparently
        restart from must NOT read as done — a non-blocking poller
        would otherwise treat a half-delivered stream as complete."""
        m = small_model(max_len=256)
        with self._fleet(m, n=2, max_new=200, max_len=256) as fleet:
            st = fleet.generate(np.array([1, 2], np.int32), session="s",
                                max_new_tokens=200)
            st.get(0, timeout=60.0)
            fleet.fail(st.replica_id)
            deadline = time.time() + 30.0
            while not st._stream.done and time.time() < deadline:
                time.sleep(0.005)
            if st._stream.status != "ok":  # crash won the race
                assert not st.done  # recoverable: get() would restart
            assert len(st.result(120.0)) == 200
            assert st.done

    def test_reroute_decrements_deadline_budget(self):
        """The stream's deadline is ONE absolute budget across its
        fleet life: a re-route passes the remainder, and a lapsed
        budget fails instead of restarting with a fresh window."""
        m = small_model(max_len=256)
        with self._fleet(m, n=2, max_new=200, max_len=256) as fleet:
            st = fleet.generate(np.array([1, 2], np.int32), session="s",
                                max_new_tokens=200, deadline_ms=1e6)
            st.get(0, timeout=60.0)
            st._deadline = time.perf_counter() - 0.1  # budget spent
            fleet.fail(st.replica_id)
            with pytest.raises((ServingReroutedError,
                                ServingTimeoutError)):
                st.result(120.0)

    def test_slo_skips_fleet_replica_generate_casualties(self):
        """A rerouted generation stream burns NO error budget: the
        replica's cancelled kind=generate record (replica_id set) is
        skipped by SloEngine, and the caller's truth is the survivor's
        ok record — same exactly-once accounting as serving_request."""
        from bigdl_tpu.observability.slo import SLO, SloEngine
        eng = SloEngine([SLO("err", kind="error_rate", objective=0.5)])
        eng.emit({"type": "trace", "trace_id": "a", "kind": "generate",
                  "status": "cancelled", "replica_id": "replica0",
                  "time": 1.0})
        eng.emit({"type": "trace", "trace_id": "a2", "kind": "generate",
                  "status": "ok", "replica_id": "replica1",
                  "latency_ms": 5.0, "time": 2.0})
        # a STANDALONE engine's cancellation (no replica_id) still counts
        eng.emit({"type": "trace", "trace_id": "b", "kind": "generate",
                  "status": "cancelled", "time": 3.0})
        s = next(s for s in eng.status() if s["slo"] == "err")
        assert s["good"] == 1 and s["bad"] == 1

    def test_decode_failure_counts_each_stream_once(self):
        m = small_model()
        with GenerationEngine(m, slots=2, max_len=32) as eng:
            eng.warmup()
            with FaultInjector(FaultSpec("serve.decode", at_hit=1)):
                st = eng.generate(np.array([1, 2], np.int32),
                                  max_new_tokens=6)
                with pytest.raises(ServingError):
                    st.result(60.0)
            assert eng.stats()["failed"] == 1

    def test_default_engines_reject_generation(self):
        import bigdl_tpu.nn as nn_
        from bigdl_tpu.dataset.sample import Sample
        mlp = (nn_.Sequential().add(nn_.Linear(4, 2))
               .add(nn_.LogSoftMax()))
        mlp.ensure_params()
        with ServingFleet(mlp, n_replicas=1,
                          warmup_sample=Sample(
                              np.ones(4, np.float32))) as fleet:
            with pytest.raises(ServingError):
                fleet.generate(np.array([1, 2], np.int32))


# --------------------------------------------------------------------------
class TestBenchContract:
    def test_generation_ab_contract(self):
        """The bench emits the documented fields and holds the parity
        gate at a tiny size (the full curve runs in CI/docs)."""
        from bigdl_tpu.tools.bench_cli import bench_generation_ab
        out = bench_generation_ab(clients=2, segments=1,
                                  streams_per_client=1,
                                  max_new_tokens=6, n_prompts=4)
        for key in ("serial_tokens_per_sec", "engine_tokens_per_sec",
                    "speedup", "parity", "decode_occupancy",
                    "compile_count"):
            assert key in out
        assert out["parity"] is True
