"""The serving loop's spans and the engine's own token clock
(serving/generation.py), on the CPU with a tiny model.

One traced run of a tiny `GenerationEngine` (more requests than slots,
so admissions land between decode steps, then an idle spell and one more
request) is looked at from every side: the span tree on the dispatcher's
lane, the names against the benchmark's trace reduction, the token
stamps against what a client reads, the records against their schemas,
the host-side counters against the wall clock. A second engine runs with
no tracer and a `SpanTracer.span` that fails.
"""

import threading
import time
import types

import numpy as np
import pytest

import jax

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.spans import SpanTracer
from bigdl_tpu.observability.telemetry import RECORD_SCHEMAS, validate_record
from bigdl_tpu.serving import GenerationEngine

VOCAB = 64

#: the serving loop's tree: child -> the span that has to hold it
PARENT = {
    "admit requests": "generate step",
    "generate prefill": "admit requests",
    "decode build": "generate step",
    "generate decode": "generate step",
    "decode dispatch": "generate decode",
    "decode fetch": "generate decode",
    "decode deliver": "generate step",
}
SERVING_SPANS = ["await request", "generate step", *PARENT]
TRAIN_SPANS = ["step prepare", "step dispatch", "loss sync",
               "step bookkeeping", "gather params", "place params"]
#: ts + dur of a child and of its parent come from different clock reads
#: added to an epoch offset in float microseconds
EPS_US = 1.0
#: turns of the loop driven by hand; six requests of 20 tokens over three
#: slots keep every one of them busy
TURNS = 30


def _model():
    m = TransformerLM(VOCAB, embed_dim=32, n_layer=2, n_head=2,
                      use_flash=False, max_len=32)
    m.ensure_params(jax.random.PRNGKey(0))
    return m


def _read(stream, into):
    """A client: block in get(i), stamp each token as it returns."""
    i = 0
    while True:
        tok = stream.get(i, timeout=60.0)
        if tok is None:
            return
        into.append((tok, time.perf_counter()))
        i += 1


@pytest.fixture(scope="module")
def run():
    rs = np.random.RandomState(3)
    asks = [(rs.randint(1, VOCAB + 1, size=n).astype(np.int32), k)
            for n, k in [(3, 6), (5, 8), (9, 4), (4, 7), (12, 5), (6, 1),
                         (7, 6)]]
    tracer, sink = SpanTracer(), InMemorySink()
    eng = GenerationEngine(_model(), slots=3, max_len=32, prefill_batch=2,
                           telemetry=Telemetry(sink, resources=False),
                           tracer=tracer, emit_every=2)
    t0 = time.perf_counter()
    try:
        eng.warmup()
        streams, reads, threads = [], [], []
        for prompt, k in asks[:-1]:
            streams.append(eng.generate(prompt, max_new_tokens=k))
            reads.append([])
            threads.append(threading.Thread(
                target=_read, args=(streams[-1], reads[-1]), daemon=True))
            threads[-1].start()
        for t in threads:
            t.join(60.0)
        time.sleep(0.05)  # the loop goes back to waiting for a request
        streams.append(eng.generate(asks[-1][0], max_new_tokens=asks[-1][1]))
        reads.append([])
        _read(streams[-1], reads[-1])
        results = [s.result(60.0) for s in streams]
        stats = eng.generation_stats()
    finally:
        eng.close()
    wall = time.perf_counter() - t0
    lane = [e for e in tracer.events
            if e["ph"] == "X" and e["name"] in SERVING_SPANS]
    assert len({e["tid"] for e in lane}) == 1  # the dispatcher's lane
    return types.SimpleNamespace(
        asks=asks, streams=streams, reads=reads, results=results,
        stats=stats, wall=wall, records=sink.records,
        spans={n: sorted((e["ts"], e["ts"] + e["dur"]) for e in lane
                         if e["name"] == n) for n in SERVING_SPANS})


def _holder(span, holders):
    return [h for h in holders
            if h[0] - EPS_US <= span[0] and span[1] <= h[1] + EPS_US]


# ------------------------------------------------------------------ spans
@pytest.mark.parametrize("child", sorted(PARENT))
def test_every_span_lies_inside_its_parent(run, child):
    assert run.spans[child], f"no `{child}` span was recorded"
    for s in run.spans[child]:
        assert len(_holder(s, run.spans[PARENT[child]])) == 1, (child, s)


def test_steps_do_not_overlap_and_the_wait_is_outside_them(run):
    steps = run.spans["generate step"]
    for a, b in zip(steps, steps[1:]):
        assert a[1] <= b[0] + EPS_US
    # the engine waited twice: before the first request, and after the
    # first wave had drained
    assert len(run.spans["await request"]) >= 2
    for w in run.spans["await request"]:
        assert not _holder(w, steps)
        assert all(w[1] <= s[0] + EPS_US or s[1] <= w[0] + EPS_US
                   for s in steps)


def test_a_steps_phases_come_in_order(run):
    """A turn of the decode pipeline: build and dispatch step n+1, then
    fetch and deliver step n. The first turn of a busy spell has no step
    to fetch, the last has none to dispatch."""
    order = ["admit requests", "decode build", "generate decode",
             "decode deliver"]
    dispatched = delivered = 0
    for step in run.spans["generate step"]:
        inside = [(s[0], n) for n in order for s in run.spans[n]
                  if _holder(s, [step])]
        names = [n for _, n in sorted(inside)]
        assert names == [n for n in order if n in names], names
        assert names.count("generate decode") <= 1
        assert ("generate decode" in names) == bool(
            {"decode build", "decode deliver"} & set(names))
        dispatched += "decode build" in names
        delivered += "decode deliver" in names
    # every step is built, dispatched, fetched and delivered once
    steps = run.stats["decode_steps"]
    assert dispatched == delivered == steps
    for name in ("decode build", "decode dispatch", "decode fetch",
                 "decode deliver"):
        assert len(run.spans[name]) == steps, name
    turns = run.spans["generate decode"]
    # inside a turn that has both, the dispatch (of step n+1) comes
    # before the fetch (of step n)
    both = 0
    for turn in turns:
        d = [s for s in run.spans["decode dispatch"] if _holder(s, [turn])]
        f = [s for s in run.spans["decode fetch"] if _holder(s, [turn])]
        assert len(d) <= 1 and len(f) <= 1 and d + f
        if d and f:
            both += 1
            assert d[0][1] <= f[0][0] + EPS_US
    # a busy spell has one turn more than it has steps (its first step
    # is dispatched alone, its last fetched alone), and there were two
    # spells, or one more where a wave drained before the next joined
    assert both == run.stats["decode_overlapped_steps"]
    assert len(turns) == 2 * steps - both
    assert steps - 3 <= both <= steps - 2


def test_children_cover_nine_tenths_of_the_steps():
    """The spans a step holds cover nine tenths of it. The loop turns by
    hand on the test's own thread, `TURNS` times, every turn with live
    slots, as tests/test_generation_overlap.py drives it, and no client
    thread reads: nothing else of the process takes the interpreter inside
    a step, so one switch interval lost to another thread cannot outweigh
    the steps' time."""
    tracer = SpanTracer()
    eng = GenerationEngine(_model(), slots=3, max_len=32, prefill_batch=2,
                           tracer=tracer, start=False)
    try:
        eng.warmup()
        rs = np.random.RandomState(5)
        for _ in range(6):  # two waves of three slots, 20 tokens each
            eng.generate(rs.randint(1, VOCAB + 1, size=rs.randint(3, 11))
                         .astype(np.int32), max_new_tokens=20)
        for _ in range(TURNS):
            assert eng._q or eng._active
            with eng._span("generate step", n_active=eng._active):
                eng._admit_into_slots()
                eng._decode_once()
    finally:
        eng.close(drain=False)
    spans = {n: sorted((e["ts"], e["ts"] + e["dur"]) for e in tracer.events
                       if e["ph"] == "X" and e["name"] == n)
             for n in SERVING_SPANS}
    steps = spans["generate step"]
    assert len(steps) == TURNS
    total = sum(b - a for a, b in steps)
    direct = sum(b - a for n, p in PARENT.items() if p == "generate step"
                 for a, b in spans[n] if _holder((a, b), steps))
    assert direct >= 0.9 * total, (direct, total)
    # dispatch and fetch are all of `generate decode` but the fault site
    decode = sum(b - a for a, b in spans["generate decode"])
    parts = sum(b - a for n in ("decode dispatch", "decode fetch")
                for a, b in spans[n])
    assert 0.8 * decode <= parts <= decode + EPS_US * len(
        spans["generate decode"])


@pytest.mark.parametrize("name", SERVING_SPANS + TRAIN_SPANS)
def test_the_trace_reduction_keeps_every_span_name(name):
    """`benchmarks/trace/reduce.py` keeps a host event as a program span
    only by the look of its name; an idle gap under a span it drops
    would read as `_host_in_no_span_`."""
    from benchmarks.trace import reduce as tr
    ev = lambda n, a, b: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=a, duration_ns=b - a)
    line = lambda n, evs: types.SimpleNamespace(name=n, events=evs)  # noqa: E731
    planes = [
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Ops", [ev("%fusion = f32[8] fusion()", 0, 1000),
                             ev("%fusion = f32[8] fusion()", 9000, 10000)])]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("dispatcher", [ev(name, 500, 9500),
                                ev("PjitFunction(_decode_fn)", 600, 700),
                                ev("tsl::profiler::Thing", 700, 800)])]),
    ]
    reduced = tr.reduce_planes(planes)
    assert list(reduced["spans"]) == [name]
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert gaps == {name.replace(" ", "_"): pytest.approx(8e-6)}


def test_no_tracer_builds_no_span(monkeypatch):
    def boom(self, *a, **k):
        raise AssertionError("a span was built with no tracer attached")
    monkeypatch.setattr(SpanTracer, "span", boom)
    with GenerationEngine(_model(), slots=2, max_len=32) as eng:
        out = eng.generate(np.array([1, 2, 3], np.int32),
                           max_new_tokens=4).result(60.0)
        stats = eng.generation_stats()
    assert len(out) == 4
    # the counters and the token clock are there without a tracer
    assert stats["decode_steps"] == 3
    assert stats["decode_fetch_s_total"] > 0
    assert stats["ttft_ms_p50"] > 0 and stats["itl_ms_p50"] > 0


# ----------------------------------------------------------- token stamps
@pytest.mark.parametrize("i", range(7))
def test_one_nondecreasing_stamp_a_token_in_get_order(run, i):
    stream, got = run.streams[i], run.reads[i]
    stamps = stream.token_times()
    assert len(stamps) == len(run.results[i]) == run.asks[i][1]
    assert all(a <= b for a, b in zip(stamps, stamps[1:]))
    # token i of get(i) carries stamp i: the engine stamped it before the
    # client could have read it
    assert [t for t, _ in got] == run.results[i]
    assert all(s <= seen for s, (_, seen) in zip(stamps, got))
    assert [stream.get(j) for j in range(len(stamps))] == run.results[i]
    # a copy: the caller cannot reach the stream's own list
    stamps.append(0.0)
    assert len(stream.token_times()) == len(run.results[i])


def test_tokens_of_one_step_share_its_one_clock_reading(run):
    stamps = [t for s in run.streams for t in s.token_times()]
    deliveries = run.stats["decode_steps"] + run.stats["prefill_batches"]
    assert len(set(stamps)) == deliveries < len(stamps)
    # and that reading is taken where `decode deliver` starts (the
    # tracer's clock is another one: compare counts, not instants)
    assert len(run.spans["decode deliver"]) == run.stats["decode_steps"]


def test_a_stream_built_alone_reads_the_clock_itself():
    from bigdl_tpu.serving import TokenStream
    s = TokenStream()
    before = time.perf_counter()
    s._put(5)
    s._put(6)
    after = time.perf_counter()
    assert before <= s.token_times()[0] <= s.token_times()[1] <= after
    assert [s.get(0), s.get(1)] == [5, 6]
    # the engine hands its streams one reading for a whole step
    step = TokenStream()
    step._t = 42.0
    step._put(7)
    step._put(8)
    assert step.token_times() == [42.0, 42.0]


# ---------------------------------------------------------------- records
def test_trace_records_carry_the_engines_token_clock(run):
    traces = [r for r in run.records if r.get("type") == "trace"]
    assert len(traces) == len(run.asks)
    for r in traces:
        validate_record(r)
        assert r["kind"] == "generate" and r["status"] == "ok"
        assert 0 < r["ttft_ms"] <= r["latency_ms"]
        # first token = queue + prefill, to the rounding of the record
        assert r["ttft_ms"] == pytest.approx(
            r["queue_wait_ms"] + r["prefill_ms"], abs=0.01)
        if r["tokens"] > 1:
            assert 0 < r["itl_p50_ms"] <= r["itl_max_ms"] <= r["decode_ms"]
        else:
            assert "itl_p50_ms" not in r and "itl_max_ms" not in r


def test_generation_records_validate_with_the_new_fields(run):
    gen = [r for r in run.records if r.get("type") == "generation"]
    assert len(gen) >= 2
    new = {"decode_dispatch_s_total", "decode_fetch_s_total",
           "decode_deliver_s_total", "decode_overlapped_steps",
           "decode_discarded_slot_steps", "ttft_ms_p50", "ttft_ms_p99",
           "ttft_ms_count", "itl_ms_p50", "itl_ms_p99", "itl_ms_count"}
    assert new <= set(RECORD_SCHEMAS["generation"]["optional"])
    assert {"ttft_ms", "itl_p50_ms", "itl_max_ms"} <= set(
        RECORD_SCHEMAS["trace"]["optional"])
    for r in gen:
        validate_record(r)
        assert new <= set(r)
    assert gen[-1]["ttft_ms_p50"] <= gen[-1]["ttft_ms_p99"]
    assert gen[-1]["itl_ms_p50"] <= gen[-1]["itl_ms_p99"]


def test_the_host_side_counters_fit_the_wall_clock(run):
    s = run.stats
    parts = [s["decode_dispatch_s_total"], s["decode_fetch_s_total"],
             s["decode_deliver_s_total"]]
    assert all(p > 0 for p in parts)
    # dispatch and fetch lie inside decode_s (4-digit rounding a field)
    assert parts[0] + parts[1] <= s["decode_s_total"] + 2e-4
    assert sum(parts) + s["prefill_s_total"] <= run.wall


def test_the_histograms_count_requests_and_steps(run):
    assert run.stats["ttft_ms_p50"] > 0 and run.stats["itl_ms_p50"] > 0
    # one first token a request; one gap a decode step that followed
    # another with a slot still live (never one a token): the first step
    # of each of the two busy spells has no step before it
    assert run.stats["ttft_ms_count"] == len(run.asks)
    assert 0 < run.stats["itl_ms_count"] <= run.stats["decode_steps"] - 2
    assert run.stats["decode_steps"] < sum(len(r) for r in run.results)
    gaps = [np.diff(st.token_times()) for st in run.streams]
    widest = max(float(g.max()) for g in gaps if g.size) * 1e3
    assert run.stats["itl_ms_p99"] <= widest + 1e-3
