"""The decode pipeline one step deep (serving/generation.py): step n+1 is
dispatched before step n is fetched, from step n's tokens on the device.

On the CPU with a tiny model. Where a test has to know which step is in
flight, it drives the loop itself, one turn at a time, on an engine whose
dispatcher thread was never started (`_admit_into_slots` and
`_decode_once` are all the thread calls); the others run the real thread.
"""

import time

import numpy as np
import pytest

import jax

from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.resilience import FaultInjector, FaultSpec
from bigdl_tpu.serving import (GenerationEngine, ServingError,
                               greedy_decode_reference)

VOCAB = 64
MAX_LEN = 64
#: few prefill programs, so that a warm-up takes a second: prompts of up
#: to 16 tokens in groups of one or two (and the bucket of `MAX_LEN`)
ENGINE = dict(max_len=MAX_LEN, seq_buckets=[16], prefill_batch=2)
#: greedy from this prompt gives six different tokens first: [1, 61, 3,
#: 7, 46, 34, ...], so an EOS can be put at any of those places
VARIED = np.array([3, 5, 7], np.int32)


@pytest.fixture(scope="module")
def lm():
    m = TransformerLM(VOCAB, embed_dim=32, n_layer=2, n_head=2,
                      use_flash=False, max_len=MAX_LEN)
    m.ensure_params(jax.random.PRNGKey(0))
    fwd = jax.jit(lambda p, t: m.apply(p, t, None))
    return m, lambda prompt, n, **kw: greedy_decode_reference(
        m, m.ensure_params(), prompt, n, pad_to=MAX_LEN, fwd=fwd, **kw)


def _prompts(n, seed=11):
    rs = np.random.RandomState(seed)
    return [rs.randint(1, VOCAB + 1, size=rs.randint(3, 13)).astype(np.int32)
            for _ in range(n)]


def _stepped(m, **kw):
    """An engine whose loop the test turns by hand."""
    eng = GenerationEngine(m, start=False, **ENGINE, **kw)
    eng.warmup()
    return eng


def _turn(eng):
    """One iteration of `_run`, on the caller's thread."""
    eng._admit_into_slots()
    eng._decode_once()


def _turns_until(eng, done, limit=200):
    for n in range(limit):
        if done():
            return n
        _turn(eng)
    raise AssertionError("the loop did not get there")


# ---------------------------------------------------------------- parity
@pytest.mark.parametrize("slots", [1, 2, 4])
def test_requests_joining_with_a_step_in_flight_match_the_reference(
        lm, slots):
    """Requests are admitted while the first one's steps are in flight,
    so every decode batch holds slots of different ages, and each slot's
    first decode input comes from the host while its neighbours' come
    from the device."""
    m, ref = lm
    prompts = _prompts(7)
    budgets = [24, 3, 9, 1, 14, 2, 6]
    with GenerationEngine(m, slots=slots, **ENGINE) as eng:
        n = eng.warmup()
        streams = [eng.generate(prompts[0], max_new_tokens=budgets[0])]
        streams[0].get(2, timeout=60.0)  # decoding, a step in flight
        for p, k in zip(prompts[1:], budgets[1:]):
            streams.append(eng.generate(p, max_new_tokens=k))
            time.sleep(0.002)
        outs = [s.result(60.0) for s in streams]
        stats = eng.generation_stats()
        assert eng.compile_count() == n
    assert outs == [ref(p, k) for p, k in zip(prompts, budgets)]
    # met exactly: nothing beyond a budget was delivered or counted, and
    # with no EOS and no cancellation nothing was computed in vain
    assert [s.token_count() for s in streams] == budgets
    assert stats["tokens_total"] == sum(budgets)
    assert stats["decode_discarded_slot_steps"] == 0
    assert stats["slot_joins"] == stats["slot_leaves"] == len(budgets)


def test_a_budget_is_met_exactly_turn_by_turn(lm):
    """The step that completes a request by count is known without its
    token: the request does not ride in the step after it."""
    m, ref = lm
    eng = _stepped(m, slots=2)
    try:
        short = eng.generate(VARIED, max_new_tokens=3)
        long = eng.generate(_prompts(1)[0], max_new_tokens=8)
        _turn(eng)  # both prefilled (token 1), step 1 dispatched, cold
        assert eng._flying is not None and len(eng._flying[1]) == 2
        assert (short.token_count(), long.token_count()) == (1, 1)
        _turn(eng)  # step 2 dispatched for both, step 1 delivered
        assert (short.token_count(), long.token_count()) == (2, 2)
        assert len(eng._flying[1]) == 2 and not short.done
        _turn(eng)  # step 2 is `short`'s last: step 3 is `long`'s alone
        assert [r.stream for r in eng._flying[1]] == [long]
        assert short.done and short.result(0) == ref(VARIED, 3)
        _turns_until(eng, lambda: long.done)
        assert eng._flying is None
        stats = eng.generation_stats()
        assert stats["tokens_total"] == 11
        assert stats["decode_steps"] == 7  # the longer one's 8 - 1
        assert stats["decode_overlapped_steps"] == 6
        assert stats["decode_discarded_slot_steps"] == 0
        assert stats["decode_occupancy"] == round((2 + 7) / (7 * 2), 4)
    finally:
        eng.close(drain=False)


# ------------------------------------------------- found out a step late
def test_eos_ends_the_stream_and_the_step_after_it_is_discarded(lm):
    m, ref = lm
    full = ref(VARIED, 8)
    eos = full[2]
    assert full.index(eos) == 2
    with GenerationEngine(m, slots=2, **ENGINE) as eng:
        out = eng.generate(VARIED, max_new_tokens=8, eos_id=eos).result(60.0)
        stats = eng.generation_stats()
    assert out == full[:3] == ref(VARIED, 8, eos_id=eos)
    # step 3 was dispatched before step 2's EOS was seen; with nobody
    # left to wait for it, it is not even fetched
    assert stats["decode_steps"] == 2
    assert stats["decode_discarded_slot_steps"] == 1
    assert stats["tokens_total"] == 3


def test_a_refilled_slot_gets_nothing_from_its_old_occupants_step(lm):
    """A request ends by EOS in step n, rides in step n+1 all the same,
    and its slot is refilled before step n+1 is delivered: the roster
    step n+1 was dispatched for decides who gets its tokens, not the
    slot table."""
    m, ref = lm
    full = ref(VARIED, 8)
    other, late = _prompts(2, seed=5)
    eng = _stepped(m, slots=2)
    try:
        long = eng.generate(other, max_new_tokens=12)
        old = eng.generate(VARIED, max_new_tokens=8, eos_id=full[2])
        _turns_until(eng, lambda: old.done)
        assert old.result(0) == full[:3]
        slot = next(r.slot for r in eng._flying[1] if r.stream is old)
        assert eng._slot_req[slot] is None  # retired, its step in flight
        new = eng.generate(late, max_new_tokens=5)
        _turn(eng)  # admits `new` into the slot, delivers the old step
        assert eng._slot_req[slot].stream is new
        assert new.token_count() == 1  # its prefill's token alone
        assert old.token_count() == 3
        assert eng.generation_stats()["decode_discarded_slot_steps"] == 1
        _turns_until(eng, lambda: new.done and long.done)
        assert new.result(0) == ref(late, 5)
        assert long.result(0) == ref(other, 12)
        assert eng.generation_stats()["decode_discarded_slot_steps"] == 1
    finally:
        eng.close(drain=False)


def test_cancel_is_honoured_within_two_steps(lm):
    m, ref = lm
    prompt = _prompts(1)[0]
    eng = _stepped(m, slots=2)
    try:
        st = eng.generate(prompt, max_new_tokens=40)
        _turns_until(eng, lambda: st.token_count() >= 4)
        had = st.token_count()
        st.cancel()
        assert _turns_until(eng, lambda: st.done) <= 2
        assert st.status == "cancelled"
        assert st.token_count() <= had + 1
        assert st.result(0) == ref(prompt, 40)[:st.token_count()]
        # the step in flight at the cancellation was computed in vain
        assert eng._flying is None and eng._active == 0
        assert eng.generation_stats()["decode_discarded_slot_steps"] == 1
        # the slot is free: the next request is served in full
        nxt = eng.generate(VARIED, max_new_tokens=4)
        _turns_until(eng, lambda: nxt.done)
        assert nxt.result(0) == ref(VARIED, 4)
    finally:
        eng.close(drain=False)


# --------------------------------------------------------------- failure
def test_a_decode_fault_with_a_step_in_flight_fails_each_stream_once(lm):
    m, ref = lm
    a, b = _prompts(2, seed=3)
    eng = GenerationEngine(m, slots=2, start=False, **ENGINE)
    try:
        eng.warmup()
        sa = eng.generate(a, max_new_tokens=20)
        sb = eng.generate(b, max_new_tokens=20)
        # the third dispatch fails: steps 1 and 2 went out, step 2 is
        # in flight and is dropped with the cache
        with FaultInjector(FaultSpec("serve.decode", at_hit=3)):
            eng.start()
            for st in (sa, sb):
                with pytest.raises(ServingError):
                    st.result(60.0)
                assert st.status == "error"
        assert eng.stats()["failed"] == 2
        assert (sa.token_count(), sb.token_count()) == (2, 2)
        assert eng.generate(a, max_new_tokens=6).result(60.0) == ref(a, 6)
        stats = eng.generation_stats()
        assert stats["slot_joins"] == stats["slot_leaves"] == 3
    finally:
        eng.close()


# -------------------------------------------------------------- counters
def test_nearly_every_step_of_a_steady_run_is_overlapped(lm):
    m, ref = lm
    prompts = _prompts(3, seed=9)
    eng = GenerationEngine(m, slots=4, start=False, **ENGINE)
    try:
        n = eng.warmup()
        assert eng._decode._cache_size() == 1
        streams = [eng.generate(p, max_new_tokens=32) for p in prompts]
        eng.start()
        outs = [s.result(60.0) for s in streams]
        stats = eng.generation_stats()
        # one decode program, whatever the traffic: the cold step's
        # `prev` is a device array of the warmed signature
        assert eng.compile_count() == n
        assert eng._decode._cache_size() == 1
    finally:
        eng.close()
    assert outs == [ref(p, 32) for p in prompts]
    assert stats["decode_steps"] == 31
    assert stats["decode_overlapped_steps"] / stats["decode_steps"] > 0.9
    assert stats["decode_discarded_slot_steps"] == 0
    assert stats["decode_occupancy"] == 0.75
