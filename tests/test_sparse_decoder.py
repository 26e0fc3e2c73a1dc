"""The sparse decoder (`models/decoder.py`: grouped-query attention, a
window cache beside a full one, dropless routed experts whose router
reads the attention block's input) against the plain reference in
`sparse_decoder_reference.py`, on seeded random weights at a small size:
hidden 64, 4 query heads over 2 K/V heads of 16, 8 experts of 32 with 3
active, window 8, pattern [full, window x3], vocabulary 128, max_len 32.

Tolerances: everything here is float32 on the CPU with the matmul
precision at "highest", so program and reference differ by summation
order alone; 2e-5 on log-probs of size O(5) is some ten float32
roundings of room. Served tokens are compared by the reference's logit
of the served token against its best (a greedy token can only differ
where two logits are within rounding of each other).
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sparse_decoder_reference as ref
from bigdl_tpu.models.decoder import LayerSpec, SparseDecoderLM
from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.experts import RoutedExperts, route
from bigdl_tpu.nn.normalization import RMSNorm
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.telemetry import validate_record
from bigdl_tpu.serving import GenerationEngine

CFG = ref.SMALL
TOL = 2e-5
COUNTERS = ("moe_pairs_routed", "moe_expert_load_max_over_mean",
            "moe_experts_touched_per_step", "window_positions_skipped")


def build(cfg=CFG):
    return SparseDecoderLM(
        cfg["vocab"], cfg["hidden"], cfg["heads"], cfg["kv_heads"],
        cfg["head_dim"], [LayerSpec(w, b) for w, b in cfg["layers"]],
        cfg["experts"], cfg["expert_dim"], cfg["top_k"], cfg["eps"],
        max_len=cfg["max_len"])


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
    toks = tokens_for(seed, 2, 24)
    want = jax.nn.log_softmax(ref_logits(w, toks), axis=-1)
    got = build().apply(ref.to_program(CFG, w), jnp.asarray(toks), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_init_cache_gives_each_layer_the_depth_its_kind_needs(model):
    cache = model.init_cache(4, 32)
    assert [k.shape for k in cache["k"]] == \
        [(4, 2, 32, 16)] + [(4, 2, 8, 16)] * 3
    assert [v.shape for v in cache["v"]] == [k.shape for k in cache["k"]]
    # a cache shorter than the window is max_len deep everywhere
    assert {k.shape[2] for k in model.init_cache(2, 6)["k"]} == {6}


@pytest.mark.parametrize("slots", [1, 2, 4])
def test_prefill_then_decode_is_the_full_forward_at_every_position(
        model, weights, slots):
    """Prompts longer than the window (12 and 9 > 8, in a 16-wide bucket
    that is longer than the ring), decode across the ring's wrap, slots
    at mixed ages in one step."""
    total = 26
    toks = tokens_for(5 + slots, slots, total)
    want = np.asarray(jax.nn.log_softmax(ref_logits(weights, toks), -1))
    lengths = np.array([12, 5, 9, 3][:slots], np.int32)
    params = model.ensure_params()
    cache = model.init_cache(slots, 32)
    pad = np.ones((slots, 16), np.int32)
    for j in range(slots):
        pad[j, :lengths[j]] = toks[j, :lengths[j]]
    order = np.arange(slots)[::-1].astype(np.int32)  # rows in another order
    logp, cache = jax.jit(model.apply_prefill)(
        params, jnp.asarray(pad[order]), cache, jnp.asarray(order),
        jnp.asarray(lengths[order]))
    for row, j in enumerate(order):
        np.testing.assert_allclose(np.asarray(logp[row]),
                                   want[j, lengths[j] - 1], atol=TOL)
    step = jax.jit(model.apply_step)
    pos = lengths.copy()
    while pos.min() < total:
        live = pos < total
        tok = np.where(live, toks[np.arange(slots), np.minimum(pos, total - 1)],
                       1).astype(np.int32)
        at = np.where(live, pos, 0).astype(np.int32)
        logp, cache = step(params, jnp.asarray(tok), cache, jnp.asarray(at))
        for j in np.nonzero(live)[0]:
            np.testing.assert_allclose(np.asarray(logp[j]), want[j, pos[j]],
                                       atol=TOL)
        pos = pos + live


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


def test_engine_serves_the_reference_tokens_with_joins_in_flight(
        model, weights):
    """Through GenerationEngine, more requests than slots, so that
    requests join while a step is in flight and slots are reused at
    mixed ages; prompts up to 14 > window, outputs across the wrap."""
    rs = np.random.RandomState(4)
    prompts = [rs.randint(1, CFG["vocab"] + 1,
                          size=n).astype(np.int32)
               for n in (14, 3, 9, 12, 5, 10, 7, 13)]
    asked = [12, 6, 15, 9, 14, 8, 16, 10]
    with GenerationEngine(model, slots=3, max_len=32, max_new_tokens=16,
                          prefill_batch=2, seq_buckets=[4, 16]) as eng:
        streams = [eng.generate(p, max_new_tokens=n)
                   for p, n in zip(prompts, asked)]
        outs = [s.result(120.0) for s in streams]
        stats = eng.generation_stats()
    assert [len(o) for o in outs] == asked
    for p, o in zip(prompts, outs):
        assert _served_gap(weights, p, np.asarray(o)) < 1e-4
    assert stats["slot_joins"] == 8 and stats["decode_overlapped_steps"] > 0
    # every real token of every layer went to exactly top_k experts
    routed = sum(len(p) + n - 1 for p, n in zip(prompts, asked))
    assert stats["moe_pairs_routed"] >= routed * CFG["top_k"] * 4
    assert CFG["top_k"] <= stats["moe_experts_touched_per_step"] \
        <= CFG["experts"]
    assert stats["moe_expert_load_max_over_mean"] >= 1.0
    assert stats["window_positions_skipped"] > 0


def test_warmup_uses_the_live_cache_and_traffic_compiles_nothing(model):
    calls = []

    class Counting(SparseDecoderLM):
        def init_cache(self, *a, **k):
            calls.append(a)
            return super().init_cache(*a, **k)

    m = Counting(CFG["vocab"], CFG["hidden"], CFG["heads"], CFG["kv_heads"],
                 CFG["head_dim"], [LayerSpec(w, b) for w, b in CFG["layers"]],
                 CFG["experts"], CFG["expert_dim"], CFG["top_k"], CFG["eps"])
    m.set_params(model.ensure_params())
    rs = np.random.RandomState(1)
    with GenerationEngine(m, slots=2, max_len=32, max_new_tokens=6,
                          prefill_batch=2, seq_buckets=[8, 16]) as eng:
        n = eng.warmup()
        assert n == len(eng.buckets) * len(eng.seq_buckets) + 1
        outs = [eng.generate(rs.randint(1, 129, size=k).astype(np.int32),
                             max_new_tokens=5).result(120.0)
                for k in (3, 11, 20)]
        assert eng.compile_count() == n
        assert eng.warmup() == n  # again, between requests: nothing new
    assert len(calls) == 1, "one cache in the engine's life"
    assert [len(o) for o in outs] == [5, 5, 5]


def test_warmup_under_traffic_waits_for_the_slots_and_harms_no_request(
        model, weights):
    rs = np.random.RandomState(9)
    prompts = [rs.randint(1, 129, size=n).astype(np.int32)
               for n in (10, 4, 12, 6)]
    with GenerationEngine(model, slots=2, max_len=32, max_new_tokens=12,
                          prefill_batch=2, seq_buckets=[16]) as eng:
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
    with GenerationEngine(model, slots=2, max_len=32, max_new_tokens=8,
                          telemetry=tel, emit_every=3,
                          seq_buckets=[16]) as eng:
        eng.generate(np.arange(1, 12, dtype=np.int32),
                     max_new_tokens=8).result(120.0)
        stats = eng.generation_stats()
    for name in COUNTERS:
        assert stats[name] is not None, name
    # 11 prompt tokens and 7 decoded ones, 3 experts each, 4 layers
    assert stats["moe_pairs_routed"] == (11 + 7) * 3 * 4
    # decode positions 11..17 against a window of 8, on 3 window layers
    assert stats["window_positions_skipped"] == \
        3 * sum(p + 1 - 8 for p in range(11, 18))
    records = [r for r in sink.records if r.get("type") == "generation"]
    assert len(records) >= 2
    for r in records:
        validate_record(r)
    assert all(name in records[-1] for name in COUNTERS)


# ------------------------------------------------------------ expert layer
@pytest.mark.parametrize("rows", [48, 6])
@pytest.mark.parametrize("router", ["uniform", "skewed"])
def test_expert_layer_is_all_experts_masked_and_drops_nothing(router, rows):
    """Under a router skewed so that every token picks the same experts
    (a capacity-bound layer would drop most of them) and a uniform one;
    at 48 rows (sorted rows, grouped products, three chunks) and at 6
    (a decode step's shape: every row against every expert)."""
    layer = RoutedExperts(16, 8, 8, 3, token_chunk=16)
    params = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, 16))
    logits = jax.random.normal(jax.random.PRNGKey(2), (rows, 8))
    if router == "skewed":
        logits = logits * 0.01 + jnp.array([9., 8, 7, 0, 0, 0, 0, 0])
    cfg = {"top_k": 3, "experts": 8}
    want = ref.expert_mix(cfg, params, x, logits)
    got, chosen = jax.jit(layer.apply_routed)(params, x, logits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
    assert chosen.shape == (rows, 3)
    if router == "skewed":
        assert set(np.asarray(chosen).ravel()) == {0, 1, 2}
    # and the layer really uses every chosen expert: leaving one out of
    # the reference moves the result
    e = int(chosen[0, 2])
    short = ref.expert_mix(cfg, params, x, logits, leave_out=e)
    assert float(jnp.abs(short - got).max()) > 1e-3


def test_route_is_softmax_over_all_then_top_k_renormalised():
    logits = jax.random.normal(jax.random.PRNGKey(5), (7, 8)) * 2
    idx, w = route(logits, 3)
    full = jax.nn.softmax(logits, axis=-1)
    top = jnp.take_along_axis(full, idx, axis=-1)
    np.testing.assert_allclose(np.asarray(w),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               atol=1e-6)
    assert w.dtype == jnp.float32


def test_expert_chunks_change_no_number():
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 8))
    whole = RoutedExperts(16, 8, 8, 3)
    params = whole.init(jax.random.PRNGKey(0))
    a, ea = whole.apply_routed(params, x, logits)
    b, eb = RoutedExperts(16, 8, 8, 3, token_chunk=16).apply_routed(
        params, x, logits)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert np.array_equal(np.asarray(ea), np.asarray(eb))


def test_routing_reads_the_attention_blocks_normed_input(model, weights):
    """The chosen experts are the top-k of rmsnorm(x; g1) @ Wr with x the
    block's INPUT, not of the experts' own input."""
    blk, p = model.blocks[1], model.ensure_params()["block1"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 10, CFG["hidden"]))
    _, _, _, chosen = blk.apply_prefill(p, x)
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG["eps"]) \
        * weights["l1.ln1.g"]
    _, want = jax.lax.top_k(h @ weights["l1.router"], CFG["top_k"])
    assert np.array_equal(np.asarray(chosen), np.asarray(want))
    # the experts' own normed input would have chosen otherwise somewhere
    a = blk.attn.apply(p["attn"], h.astype(x.dtype), None)
    u = blk.ln2.apply(p["ln2"], x + a, None)
    _, other = jax.lax.top_k(u @ weights["l1.router"], CFG["top_k"])
    assert not np.array_equal(np.asarray(other), np.asarray(want))


# --------------------------------------------------------------- attention
@pytest.mark.parametrize("rope_base", [None, 1.5e6])
def test_a_layer_without_rope_does_not_see_positions(rope_base):
    """rope_layout 0: a prompt and its position-shifted (here: stretched)
    copy give the same q and k, so the same output; rope_layout 1: not."""
    attn = GroupedQueryAttention(32, 4, 2, 8, rope_base=rope_base)
    params = attn.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 6, 32))
    q0, k0, _ = attn.project_qkv(params, x, positions=jnp.arange(6))
    q1, k1, _ = attn.project_qkv(params, x, positions=jnp.arange(6) * 3 + 5)
    same = bool(jnp.allclose(q0, q1, atol=1e-6)
                and jnp.allclose(k0, k1, atol=1e-6))
    assert same == (rope_base is None)


@pytest.mark.parametrize("window", [None, 5])
def test_attention_step_matches_the_whole_sequence(window):
    attn = GroupedQueryAttention(32, 4, 2, 8, window=window, rope_base=1e4)
    params = attn.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 14, 32))
    want = np.asarray(attn.apply(params, x, None))
    k, v = attn.init_cache(2, 16)
    assert k.shape == (2, 2, 16 if window is None else 5, 8)
    step = jax.jit(attn.apply_step)
    for t in range(14):
        o, k, v = step(params, x[:, t:t + 1], k, v,
                       jnp.full((2,), t, jnp.int32))
        np.testing.assert_allclose(np.asarray(o[:, 0]), want[:, t],
                                   atol=TOL)


def test_rmsnorm_keeps_the_inputs_type_and_norms_in_float32():
    norm = RMSNorm(16, eps=1e-6)
    p = {"weight": jnp.linspace(0.5, 1.5, 16)}
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16)) * 4
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * p["weight"]
    np.testing.assert_allclose(np.asarray(norm.apply(p, x, None)),
                               np.asarray(want), atol=1e-6)
    assert norm.apply(p, x.astype(jnp.bfloat16), None).dtype == jnp.bfloat16
    assert norm.init(None)["weight"].shape == (16,)


# ------------------------------------------------------------------ cache
@pytest.mark.parametrize("length", [1, 3, 8, 9, 13, 16])
def test_ring_commit_keeps_the_last_window_of_a_longer_prompt(length):
    ring, t = 8, 16
    new = jnp.arange(t, dtype=jnp.float32)[None, None, :, None] \
        * jnp.ones((2, 1, t, 2)) + jnp.array([0., 100.])[:, None, None, None]
    cache = kv_cache.commit(
        jnp.full((3, 1, ring, 2), -1.0), new, jnp.array([2, 0]),
        jnp.array([length, 16]), window=ring)
    held = np.asarray(cache[2, 0, :, 0])
    mask = np.asarray(kv_cache.step_mask(ring, jnp.array([length - 1]),
                                         ring))[0, 0, 0]
    want = {p for p in range(length) if p > length - 1 - ring}
    assert {int(held[j]) for j in range(ring) if mask[j]} == want
    assert all(int(held[j]) % ring == j for j in range(ring) if mask[j])
    assert np.all(np.asarray(cache[1]) == -1.0)         # untouched slot
    assert sorted(np.asarray(cache[0, 0, :, 0]) - 100) == list(range(8, 16))


@pytest.mark.parametrize("position", [0, 3, 7, 8, 12, 21])
def test_ring_write_and_mask_follow_the_position(position):
    ring = 8
    cache = jnp.zeros((2, 1, ring, 1))
    new = jnp.ones((2, 1, 1, 1)) * 7
    out = kv_cache.write(cache, new, jnp.array([position, 0]), window=ring)
    assert float(out[0, 0, position % ring, 0]) == 7
    assert float(out[1, 0, 0, 0]) == 7
    mask = np.asarray(kv_cache.step_mask(ring, jnp.array([position]),
                                         ring))[0, 0, 0]
    assert mask.sum() == min(position + 1, ring)
    assert mask[position % ring]
    # a full layer's mask is the causal prefix, as it always was
    full = np.asarray(kv_cache.step_mask(32, jnp.array([position])))[0, 0, 0]
    assert full.sum() == position + 1 and full[:position + 1].all()
    assert int(kv_cache.positions_skipped(jnp.array([position, 0]),
                                          ring)) == max(0, position + 1 - ring)


def test_cache_commit_and_write_keep_their_old_names():
    from bigdl_tpu.nn import attention
    assert attention.cache_write is kv_cache.write
    assert attention.cache_commit is kv_cache.commit
    assert kv_cache.depth(32, None) == 32 and kv_cache.depth(32, 8) == 8
    with pytest.raises(ValueError):
        kv_cache.init(0, 2, 8, 4)
