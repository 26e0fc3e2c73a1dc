"""The hybrid decoder (`models/decoder.py` with gated delta-rule layers
beside full-attention ones, a dense gated FFN, the norms on each
sub-layer's output, a norm on q and k) against the plain reference in
`hybrid_decoder_reference.py`, on seeded random weights at a small size:
hidden 64, pattern [linear x3, full] x 2, 4 heads, linear key 8 / value
16, full head 16, 4 taps, FFN 128, vocabulary 128, chunk 8, max_len 64.
And what the recurrent state asks of `GenerationEngine`, which knows
nothing of it: a step over a slot destroys what the slot held, so
everything that was harmless for K/V is shown harmless here.

Tolerances: everything here is float32 on the CPU with the matmul
precision at "highest", so program and reference differ by summation
order and the chunks' triangular solve; 2e-4 on log-probs of size 5 to 8
after eight layers whose every sub-layer's output is normed (the norm
carries a rounding of a small output on at full size; the widest gap
read was 5.4e-5), where a state lost at the prefill/decode seam moves
them by 0.1 and more. Served tokens are
compared by the reference's logit of the served token against its best
(a greedy token can only differ where two logits are within rounding of
each other).
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hybrid_decoder_reference as ref
from bigdl_tpu.models.decoder import DecoderLM, LayerSpec, SparseDecoderLM
from bigdl_tpu.nn.attention import GroupedQueryAttention, rope
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.telemetry import validate_record
from bigdl_tpu.ops.attention_kernel import causal_grouped_attention
from bigdl_tpu.serving import GenerationEngine

CFG = ref.SMALL
TOL = 2e-4
COUNTERS = ("recurrent_state_bytes", "recurrent_slot_steps",
            "recurrent_chunks_scanned", "recurrent_state_absmax")
N_LINEAR = CFG["layers"].count("linear")
# one slot's state and tail, float32, over the six linear layers
SLOT_BYTES = 4 * N_LINEAR * (4 * 8 * 16 + 3 * 128)


def build(cfg=CFG, **kw):
    layers = [LayerSpec(mixer="gated_delta" if kind == "linear"
                        else "attention", ffn="dense", norm="output")
              for kind in cfg["layers"]]
    return DecoderLM(
        cfg["vocab"], cfg["hidden"], cfg["heads"], cfg["kv_heads"],
        cfg["head_dim"], layers, eps=cfg["eps"], max_len=cfg["max_len"],
        ffn_dim=cfg["ffn"], qk_norm=True, linear_heads=cfg["lin_heads"],
        linear_key_dim=cfg["lin_key"], linear_value_dim=cfg["lin_value"],
        conv_taps=cfg["taps"], chunk=cfg["chunk"], **kw)


@pytest.fixture(scope="module")
def weights():
    return ref.init_weights(CFG, 3)


@pytest.fixture(scope="module")
def model(weights):
    m = build()
    m.set_params(ref.to_program(CFG, weights))
    return m


@jax.jit
def ref_logits(w, toks):
    return ref.logits(CFG, w, toks)


def tokens_for(seed, rows, t):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (rows, t),
                                         1, CFG["vocab"] + 1), np.int32)


@pytest.mark.parametrize("seed", [3, 11])
def test_full_apply_matches_the_reference(seed):
    w = ref.init_weights(CFG, seed)
    toks = tokens_for(seed, 2, 27)         # not a multiple of the chunk
    want = jax.nn.log_softmax(ref_logits(w, toks), axis=-1)
    got = build().apply(ref.to_program(CFG, w), jnp.asarray(toks), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_the_programs_own_initialisation_runs_and_keeps_alpha_in_reach():
    m = build()
    p = m.init(jax.random.PRNGKey(0))
    assert set(p["block0"]) == {"ln1", "ln2", "attn", "ffn"}
    assert set(p["block3"]["attn"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                        "k_norm"}
    out = m.apply(p, jnp.asarray(tokens_for(1, 2, 16)), None)
    assert out.shape == (2, 16, CFG["vocab"])
    assert np.all(np.isfinite(np.asarray(out)))
    log_alpha, beta = m.blocks[0].attn._gates(
        p["block0"]["attn"], jnp.zeros((1, 1, CFG["hidden"])))
    assert np.all(np.asarray(log_alpha) < 0) and np.all(
        np.asarray(log_alpha) > -2.0) and np.all(np.asarray(beta) == 1.0)


def test_init_cache_gives_each_layer_what_its_kind_keeps(model):
    cache = model.init_cache(5, 64)
    for i, kind in enumerate(CFG["layers"]):
        if kind == "full":
            assert cache["k"][i].shape == cache["v"][i].shape == (5, 4, 64, 16)
            assert cache["state"][i] is None and cache["tail"][i] is None
        else:
            assert cache["state"][i].shape == (5, 4, 8, 16)
            assert cache["state"][i].dtype == jnp.float32
            assert cache["tail"][i].shape == (5, 3, 128)
            assert cache["k"][i] is None and cache["v"][i] is None
    assert set(cache["counters"]) == {
        "decode_steps", "recurrent_slot_steps", "recurrent_chunks_scanned",
        "recurrent_state_absmax", "kv_positions_live", "kv_positions_read"}
    # the state is float32 whatever the cache's type; K, V and tail follow
    half = build(cache_dtype=jnp.bfloat16).init_cache(2, 64)
    assert half["state"][0].dtype == jnp.float32
    assert half["tail"][0].dtype == half["k"][3].dtype == jnp.bfloat16
    assert model.cache_stats(cache)["recurrent_state_bytes"] == 5 * SLOT_BYTES


def test_one_class_builds_both_decoders():
    assert SparseDecoderLM is DecoderLM
    with pytest.raises(ValueError):
        LayerSpec(mixer="mamba")
    # a recurrent layer keeps a state, not positions
    for positional in ({"window": 4}, {"rope_base": 1e4}):
        with pytest.raises(ValueError, match="gated_delta"):
            LayerSpec(mixer="gated_delta", **positional)
    mixed = DecoderLM(
        32, 16, 2, 1, 8, [LayerSpec(window=4, rope_base=1e4),
                          LayerSpec(mixer="gated_delta", ffn="dense",
                                    norm="output"),
                          LayerSpec(mixer="gated_delta")],
        n_experts=4, expert_dim=8, top_k=2, ffn_dim=24, linear_heads=2,
        linear_key_dim=4, linear_value_dim=8, chunk=4)
    p = mixed.init(jax.random.PRNGKey(0))
    assert "router" in p["block0"] and "ffn" in p["block1"] \
        and "router" in p["block2"]
    cache = mixed.init_cache(2, 16)
    assert {"moe_expert_load", "window_positions_skipped",
            "recurrent_slot_steps"} <= set(cache["counters"])
    assert cache["counters"]["moe_expert_load"].shape == (2, 4)
    toks = jnp.asarray(np.arange(1, 9, dtype=np.int32)[None])
    whole = mixed.apply(p, toks, None)
    logp, cache = mixed.apply_prefill(p, toks[:, :5], cache, jnp.array([1]),
                                      jnp.array([5]))
    np.testing.assert_allclose(np.asarray(logp[0]), np.asarray(whole[0, 4]),
                               atol=TOL)
    for pos in (5, 6, 7):
        logp, cache = mixed.apply_step(
            p, jnp.array([1, toks[0, pos]]), cache, jnp.array([0, pos]))
        np.testing.assert_allclose(np.asarray(logp[1]),
                                   np.asarray(whole[0, pos]), atol=TOL)
    stats = mixed.cache_stats(cache)
    assert stats["moe_pairs_routed"] == 8 * 2 * 2
    assert stats["recurrent_slot_steps"] == 3


@pytest.mark.parametrize("slots", [1, 2, 4])
def test_prefill_then_decode_is_the_full_forward_at_every_position(
        model, weights, slots):
    """Right-padded rows of mixed lengths (shorter than the taps, over a
    chunk, not a multiple of it) in a 16-wide bucket, rows in another
    order than their slots, then decode with slots at mixed ages in one
    step, finished slots riding along idle."""
    total = 30
    toks = tokens_for(5 + slots, slots, total)
    want = np.asarray(jax.nn.log_softmax(ref_logits(weights, toks), -1))
    lengths = np.array([13, 2, 9, 16][:slots], np.int32)
    params = model.ensure_params()
    cache = model.init_cache(slots, 64)
    pad = np.ones((slots, 16), np.int32)
    for j in range(slots):
        pad[j, :lengths[j]] = toks[j, :lengths[j]]
    order = np.arange(slots)[::-1].astype(np.int32)
    logp, cache = jax.jit(model.apply_prefill)(
        params, jnp.asarray(pad[order]), cache, jnp.asarray(order),
        jnp.asarray(lengths[order]))
    for row, j in enumerate(order):
        np.testing.assert_allclose(np.asarray(logp[row]),
                                   want[j, lengths[j] - 1], atol=TOL)
    step = jax.jit(model.apply_step)
    pos = lengths.copy()
    steps = 0
    while pos.min() < total:
        live = pos < total
        tok = np.where(live, toks[np.arange(slots),
                                  np.minimum(pos, total - 1)],
                       1).astype(np.int32)
        at = np.where(live, pos, 0).astype(np.int32)
        logp, cache = step(params, jnp.asarray(tok), cache, jnp.asarray(at))
        for j in np.nonzero(live)[0]:
            np.testing.assert_allclose(np.asarray(logp[j]), want[j, pos[j]],
                                       atol=TOL)
        steps += int(live.sum())
        pos = pos + live
    stats = model.cache_stats(cache)
    assert stats["recurrent_slot_steps"] == steps
    assert stats["recurrent_chunks_scanned"] == int(
        np.sum(-(-lengths // CFG["chunk"])))
    assert 0 < stats["recurrent_state_absmax"] < 50


def test_a_bucket_row_that_repeats_a_slot_id_commits_once(model):
    """The engine pads a prefill group to its batch bucket by repeating
    the last request's row, slot id included."""
    params = model.ensure_params()
    toks = tokens_for(2, 2, 16)
    lengths = np.array([11, 6], np.int32)
    prefill = jax.jit(model.apply_prefill)
    _, padded = prefill(params, jnp.asarray(toks[[0, 1, 1, 1]]),
                        model.init_cache(3, 64), jnp.array([2, 0, 0, 0]),
                        jnp.asarray(lengths[[0, 1, 1, 1]]))
    _, plain = prefill(params, jnp.asarray(toks), model.init_cache(3, 64),
                       jnp.array([2, 0]), jnp.asarray(lengths))
    # (four rows in a product sum in another order than two, and every
    # sub-layer's norm carries that on: 1e-4 on K and V of size 2 after
    # four layers, 1e-3 on a state of size 7 after seven)
    for a, b in zip(jax.tree_util.tree_leaves(padded),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=2e-3)
    assert model.cache_stats(padded)["recurrent_chunks_scanned"] == 2 + 1
    assert float(jnp.abs(padded["state"][0][1]).max()) == 0.0  # not named


def test_an_idle_slots_state_stays_finite_over_200_steps(model):
    """Slot 0 idle (position 0, token 1, every step), slot 1 live: the
    idle slot's states are replaced 200 times and stay finite and small;
    only the live slot counts."""
    params = model.ensure_params()
    cache = model.init_cache(2, 64)
    _, cache = model.apply_prefill(params, jnp.asarray(tokens_for(1, 1, 8)),
                                   cache, jnp.array([1]), jnp.array([5]))
    step = jax.jit(model.apply_step)
    for i in range(200):
        logp, cache = step(params, jnp.array([1, 7]), cache,
                           jnp.array([0, 5 + i % 50]))
    for s in (s for s in cache["state"] if s is not None):
        assert np.all(np.isfinite(np.asarray(s)))
        assert float(jnp.abs(s[0]).max()) < 50.0
    assert np.all(np.isfinite(np.asarray(logp)))
    stats = model.cache_stats(cache)
    assert stats["recurrent_slot_steps"] == 200
    live_max = max(float(jnp.abs(s[1]).max()) for s in cache["state"]
                   if s is not None)
    assert stats["recurrent_state_absmax"] == pytest.approx(live_max)


def _served_gap(weights, prompt, served):
    """Widest gap by which a served token's reference logit lies under
    the reference's best at its position."""
    seq = np.ones((1, CFG["max_len"]), np.int32)  # causal: the tail is unseen
    seq[0, :len(prompt) + len(served) - 1] = np.concatenate(
        [prompt, served[:-1]])
    lg = np.asarray(ref_logits(weights, seq))[0]
    at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
    return float(np.max(lg[at].max(axis=-1)
                        - lg[at, np.asarray(served) - 1]))


PROMPTS = (14, 3, 9, 33, 5, 10, 1, 13)
ASKED = [12, 6, 15, 9, 14, 8, 16, 10]


def _prompts():
    rs = np.random.RandomState(4)
    return [rs.randint(1, CFG["vocab"] + 1, size=n).astype(np.int32)
            for n in PROMPTS]


def test_engine_serves_the_reference_tokens_with_joins_in_flight(
        model, weights):
    """Through GenerationEngine, more requests than slots, so that
    requests join while a step is in flight, slots are reused at mixed
    ages, and a request that has ended rides one step more before its
    slot's next occupant is prefilled over what that step left."""
    prompts = _prompts()
    with GenerationEngine(model, slots=3, max_len=64, max_new_tokens=16,
                          prefill_batch=2, seq_buckets=[8, 16, 40]) as eng:
        n = eng.warmup()
        streams = [eng.generate(p, max_new_tokens=n_new)
                   for p, n_new in zip(prompts, ASKED)]
        outs = [s.result(120.0) for s in streams]
        stats = eng.generation_stats()
        assert eng.compile_count() == n      # traffic compiled nothing
    assert [len(o) for o in outs] == ASKED
    for p, o in zip(prompts, outs):
        assert _served_gap(weights, p, np.asarray(o)) < 1e-4
    assert stats["slot_joins"] == 8 and stats["decode_overlapped_steps"] > 0
    # every token but a request's first came out of a live slot-step
    # (warm-up's step ran with no slot live); steps computed for a
    # request that had ended count as well
    assert stats["recurrent_slot_steps"] >= sum(ASKED) - len(ASKED)
    assert stats["recurrent_slot_steps"] <= sum(ASKED) - len(ASKED) \
        + stats["decode_discarded_slot_steps"] + len(ASKED)
    assert stats["recurrent_state_bytes"] == 3 * SLOT_BYTES


def test_a_slot_used_twice_gives_the_second_request_what_it_gets_alone(
        model, weights):
    """One slot: every request lands on what the one before left, after
    warm-up ran every program over it. Each gets the tokens it gets from
    a fresh engine of its own."""
    prompts = _prompts()[:4]
    with GenerationEngine(model, slots=1, max_len=64, max_new_tokens=12,
                          seq_buckets=[16, 40]) as eng:
        eng.warmup()
        shared = [eng.generate(p, max_new_tokens=12).result(120.0)
                  for p in prompts]
    for p, got in zip(prompts, shared):
        with GenerationEngine(model, slots=1, max_len=64, max_new_tokens=12,
                              seq_buckets=[16, 40]) as eng:
            alone = eng.generate(p, max_new_tokens=12).result(120.0)
        assert list(got) == list(alone)
        assert _served_gap(weights, p, np.asarray(got)) < 1e-4


def test_warmup_under_traffic_waits_for_the_slots_and_harms_no_request(
        model, weights):
    prompts = _prompts()[:4]
    with GenerationEngine(model, slots=2, max_len=64, max_new_tokens=12,
                          prefill_batch=2, seq_buckets=[16, 40]) as eng:
        streams = [eng.generate(p, max_new_tokens=12) for p in prompts[:2]]
        streams[0].get(0, timeout=120.0)   # decoding now
        warm = threading.Thread(target=eng.warmup)
        warm.start()
        streams += [eng.generate(p, max_new_tokens=12) for p in prompts[2:]]
        outs = [s.result(120.0) for s in streams]
        warm.join(120.0)
        assert not warm.is_alive()
    for p, o in zip(prompts, outs):
        assert len(o) == 12
        assert _served_gap(weights, p, np.asarray(o)) < 1e-4


def test_the_four_counters_are_in_the_stats_and_in_the_generation_record(
        model):
    sink = InMemorySink()
    tel = Telemetry(sink, resources=False)
    with GenerationEngine(model, slots=2, max_len=64, max_new_tokens=8,
                          telemetry=tel, emit_every=3,
                          seq_buckets=[16]) as eng:
        eng.generate(np.arange(1, 12, dtype=np.int32),
                     max_new_tokens=8).result(120.0)
        stats = eng.generation_stats()
    for name in COUNTERS:
        assert stats[name] is not None, name
    assert stats["recurrent_state_bytes"] == 2 * SLOT_BYTES
    # 7 tokens after the first, one live slot a step; 11 prompt tokens
    # are two chunks of 8
    assert stats["recurrent_slot_steps"] == 7
    assert stats["recurrent_chunks_scanned"] == 2
    assert 0 < stats["recurrent_state_absmax"] < 50
    assert "moe_pairs_routed" not in stats \
        and "window_positions_skipped" not in stats
    records = [r for r in sink.records if r.get("type") == "generation"]
    assert len(records) >= 2
    for r in records:
        validate_record(r)
    assert all(name in records[-1] for name in COUNTERS)


# ------------------------------------------------------------- q/k norm
def _attention_before_the_norm(layer, params, x):
    """`GroupedQueryAttention.apply_prefill` as it stood before it could
    norm q and k (PR 35's text)."""
    with layer._scope():
        b, t, _ = x.shape
        x = x.astype(params["wq"].dtype)

        def heads(z, n):
            return jnp.transpose(z.reshape(b, t, n, layer.hd), (0, 2, 1, 3))
        q = heads(x @ params["wq"], layer.h)
        k = heads(x @ params["wk"], layer.hk)
        v = heads(x @ params["wv"], layer.hk)
        if layer.rope_base is not None:
            q = rope(q, None, layer.rope_base)
            k = rope(k, None, layer.rope_base)
        o = causal_grouped_attention(q, k, v, layer.window)
        return layer._finish(params, o), k, v


@pytest.mark.parametrize("window, base", [(None, None), (8, 1.5e6)])
def test_with_the_norm_off_attention_lowers_to_the_program_it_had(window,
                                                                  base):
    layer = GroupedQueryAttention(64, 4, 2, 16, window=window, rope_base=base)
    params = layer.init(jax.random.PRNGKey(0))
    assert set(params) == {"wq", "wk", "wv", "wo"}
    x = jnp.ones((2, 16, 64))
    new = jax.make_jaxpr(layer.apply_prefill)(params, x)
    old = jax.make_jaxpr(
        lambda p, x: _attention_before_the_norm(layer, p, x))(params, x)
    assert str(new) == str(old)


def test_the_norm_is_over_the_whole_projection_before_the_heads():
    layer = GroupedQueryAttention(64, 4, 4, 16, qk_norm=1e-6)
    w = ref.sub(ref.init_weights(CFG, 5), 3)
    params = ref.mixer_to_program(w, "full")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.full_attention(CFG, w, x)
    np.testing.assert_allclose(np.asarray(layer.apply(params, x, None)),
                               np.asarray(want), atol=TOL)
    # a norm a head would have given another result
    a_head = dict(params, q_norm=jnp.ones_like(params["q_norm"]),
                  k_norm=jnp.ones_like(params["k_norm"]))
    assert float(jnp.abs(layer.apply(a_head, x, None) - want).max()) > 1e-3
