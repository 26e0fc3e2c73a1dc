"""The latent-attention decoder (`models/decoder.py` with
`nn.LatentAttention` in every layer, a leading dense layer, then
sigmoid-routed SiLU experts beside a shared expert, the router reading
the feed-forward's own input) against the plain reference in
`latent_decoder_reference.py`, on seeded random weights at a small size:
hidden 64, 6 layers [dense, experts x5], 4 heads of nope 16 / rope 8 /
value 16, latent 32, 16 experts of 32 with top 3, shared 64, dense FFN
128, vocabulary 128, max_len 64. And what the latent cache asks of
`GenerationEngine`, which knows nothing of it.

Tolerances: everything here is float32 on the CPU with the matmul
precision at "highest", so program and reference differ by summation
order (the absorbed form sums over the latent where the expanded one
sums over a head's width; the experts' sorted products); 2e-4 on
log-probs of size 5 to 8 after six layers (the widest gap read was
3e-6), where a latent cached without its norm, a shared expert left out
or a choice that ignores the bias moves them by 0.05 and more.
Served tokens are compared by the reference's logit of the served token
against its best (a greedy token can only differ where two logits are
within rounding of each other).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import latent_decoder_reference as ref
from bigdl_tpu.models.decoder import (DecoderLM, ExpertsKind, LatentDims,
                                      LayerSpec)
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.telemetry import validate_record
from bigdl_tpu.serving import GenerationEngine

CFG = ref.SMALL
TOL = 2e-4
COUNTERS = ("latent_cache_bytes", "latent_positions_live",
            "latent_positions_read")
# one slot's latent and rotary keys, float32, over the six layers
SLOT_BYTES = 4 * 6 * 64 * (32 + 8)


def specs(cfg=CFG):
    return [LayerSpec(mixer="latent", rope_base=cfg["theta"], ffn="dense")
            if kind == "dense" else
            LayerSpec(mixer="latent", rope_base=cfg["theta"],
                      shared=cfg["shared"], router_reads="ffn")
            for kind in cfg["layers"]]


def build(cfg=CFG, **kw):
    return DecoderLM(
        cfg["vocab"], cfg["hidden"], cfg["heads"], cfg["heads"],
        cfg["nope"] + cfg["rope"], specs(cfg), n_experts=cfg["experts"],
        expert_dim=cfg["expert_dim"], top_k=cfg["top_k"], eps=cfg["eps"],
        max_len=cfg["max_len"], ffn_dim=cfg["ffn"],
        latent=LatentDims(cfg["nope"], cfg["rope"], cfg["value"],
                          cfg["rank"]),
        experts=ExpertsKind("silu", "sigmoid", cfg["scale"]), **kw)


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
    toks = tokens_for(seed, 2, 27)
    want = jax.nn.log_softmax(ref_logits(w, toks), axis=-1)
    got = build().apply(ref.to_program(CFG, w), jnp.asarray(toks), None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("fault", ["latent_unnormed", "no_shared",
                                   "bias_ignored"])
def test_the_reference_tells_each_part_of_the_mathematics(weights, fault):
    """What the tolerance has to catch: the program with one part of the
    layer's mathematics left out lies far outside it."""
    m = build()
    p = ref.to_program(CFG, weights)
    toks = jnp.asarray(tokens_for(4, 2, 20))
    want = np.asarray(jax.nn.log_softmax(ref_logits(weights, toks), -1))
    if fault == "latent_unnormed":
        for i in range(6):   # a scale of ones is not the layer's scale
            p[f"block{i}"]["attn"]["kv_norm"] = jnp.ones((CFG["rank"],))
    elif fault == "no_shared":
        for i in range(1, 6):
            p[f"block{i}"]["shared"]["wd"] = jnp.zeros_like(
                p[f"block{i}"]["shared"]["wd"])
    else:               # the choice by the scores alone
        for i in range(1, 6):
            p[f"block{i}"]["router_bias"] = jnp.zeros((CFG["experts"],))
    got = np.asarray(m.apply(p, toks, None))
    assert np.abs(got - want).max() > 100 * TOL


def test_the_programs_own_initialisation_runs():
    m = build()
    p = m.init(jax.random.PRNGKey(0))
    assert set(p["block0"]) == {"ln1", "ln2", "attn", "ffn"}
    assert set(p["block1"]) == {"ln1", "ln2", "attn", "router",
                                "router_bias", "experts", "shared"}
    assert set(p["block1"]["attn"]) == {"wq", "wkva", "kv_norm", "wuk",
                                        "wuv", "wo"}
    assert p["block1"]["router_bias"].dtype == jnp.float32
    assert p["block1"]["shared"]["wg"].shape == (64, 64)
    out = m.apply(p, jnp.asarray(tokens_for(1, 2, 16)), None)
    assert out.shape == (2, 16, CFG["vocab"])
    assert np.all(np.isfinite(np.asarray(out)))


def test_init_cache_gives_a_latent_layer_the_latent_and_no_k_or_v(model):
    cache = model.init_cache(5, 64)
    for i in range(6):
        assert cache["latent"][i].shape == (5, 64, 32)
        assert cache["k_pe"][i].shape == (5, 64, 8)
        for other in ("k", "v", "state", "tail"):
            assert cache[other][i] is None
    assert set(cache["counters"]) == {
        "decode_steps", "moe_expert_load", "moe_experts_touched",
        "latent_positions_live", "latent_positions_read"}
    assert cache["counters"]["moe_expert_load"].shape == (5, 16)
    half = build(cache_dtype=jnp.bfloat16).init_cache(2, 64)
    assert half["latent"][0].dtype == half["k_pe"][0].dtype == jnp.bfloat16
    assert model.cache_stats(cache)["latent_cache_bytes"] == 5 * SLOT_BYTES
    assert [b.keeps for b in model.blocks] == [("latent", "k_pe")] * 6


def test_layer_specs_say_what_belongs_to_which_kind():
    with pytest.raises(ValueError, match="latent"):
        LayerSpec(mixer="latent")                    # no rotary base
    with pytest.raises(ValueError, match="latent"):
        LayerSpec(mixer="latent", rope_base=1e4, window=4)
    with pytest.raises(ValueError, match="experts"):
        LayerSpec(ffn="dense", shared=8)
    with pytest.raises(ValueError, match="experts"):
        LayerSpec(ffn="dense", router_reads="ffn")
    with pytest.raises(ValueError):
        LayerSpec(router_reads="embedding")
    # one class mixes every kind of layer
    mixed = DecoderLM(
        32, 16, 2, 1, 8,
        [LayerSpec(window=4, rope_base=1e4),
         LayerSpec(mixer="latent", rope_base=1e4, shared=8,
                   router_reads="ffn"),
         LayerSpec(mixer="gated_delta", ffn="dense", norm="output")],
        n_experts=4, expert_dim=8, top_k=2, ffn_dim=24, linear_heads=2,
        linear_key_dim=4, linear_value_dim=8, chunk=4,
        latent=LatentDims(4, 4, 6, 8))
    p = mixed.init(jax.random.PRNGKey(0))
    assert "router_bias" not in p["block1"] and "shared" in p["block1"] \
        and "shared" not in p["block0"]
    cache = mixed.init_cache(2, 16)
    assert {"moe_expert_load", "window_positions_skipped",
            "recurrent_slot_steps", "latent_positions_read"} \
        <= set(cache["counters"])
    assert cache["latent"][1].shape == (2, 16, 8) \
        and cache["k"][1] is None and cache["latent"][0] is None
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
    assert stats["latent_positions_live"] == 6 + 7 + 8
    assert stats["latent_positions_read"] == 3 * 2 * 16


@pytest.mark.parametrize("slots", [1, 2, 4])
def test_prefill_then_decode_is_the_full_forward_at_every_position(
        model, weights, slots):
    """The expanded prefill of right-padded rows of mixed lengths in a
    16-wide bucket, rows in another order than their slots, then the
    absorbed step through the latent cache with slots at mixed ages,
    finished slots riding along idle: both against the reference's full
    forward, at every position of every row."""
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
    steps = live_positions = 0
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
        steps += 1
        live_positions += int((pos + 1)[live].sum())
        pos = pos + live
    stats = model.cache_stats(cache)
    assert stats["latent_positions_live"] == live_positions
    assert stats["latent_positions_read"] == steps * slots * 64
    assert stats["latent_positions_live"] <= stats["latent_positions_read"]
    # every real token of the prompts and of the live steps chose 3
    # experts in each of the 5 expert layers
    assert stats["moe_pairs_routed"] == 5 * 3 * (
        int(lengths.sum()) + int((total - lengths).sum()))


def test_a_layers_two_paths_agree_and_keep_no_key_or_value():
    """The layer alone: the absorbed step over the cache the expanded
    prefill filled gives the expanded form's rows, and what the prefill
    hands the cache is the latent and the rotary key, one a position."""
    layer = LatentAttention(64, 4, 16, 8, 16, 32, rope_base=1e6)
    w = ref.sub(ref.init_weights(CFG, 5), 2)
    params = ref.mixer_to_program(CFG, w)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64))
    with jax.default_matmul_precision("highest"):
        want = ref.latent_attention(CFG, w, x)
        out, c, k_pe = layer.apply_prefill(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=TOL)
        assert c.shape == (2, 12, 32) and k_pe.shape == (2, 12, 8)
        c_cache, pe_cache = layer.init_cache(2, 16)
        assert c_cache.shape == (2, 16, 32) and pe_cache.shape == (2, 16, 8)
        c_cache = c_cache.at[:, :8].set(c[:, :8])
        pe_cache = pe_cache.at[:, :8].set(k_pe[:, :8])
        for pos in range(8, 12):
            got, c_cache, pe_cache = layer.apply_step(
                params, x[:, pos:pos + 1], c_cache, pe_cache,
                jnp.array([pos, pos]))
            np.testing.assert_allclose(np.asarray(got[:, 0]),
                                       np.asarray(want[:, pos]), atol=TOL)
        # the step wrote what the prefill would have handed over
        np.testing.assert_allclose(np.asarray(c_cache[:, :12]),
                                   np.asarray(c), atol=1e-5)
        np.testing.assert_allclose(np.asarray(pe_cache[:, :12]),
                                   np.asarray(k_pe), atol=1e-5)


def test_the_decode_step_multiplies_no_key_or_value_a_head():
    """A step never rebuilds per-head K or V from the latent: no product
    of its jaxpr has the cache's depth beside a head's key or value
    width, and a prefill never takes the absorbed form: none of its
    products contracts a query with the latent."""
    layer = LatentAttention(64, 4, 16, 8, 16, 32, rope_base=1e6)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: layer.init_cache(3, 48))
    step = jax.make_jaxpr(layer.apply_step)(
        params, jax.ShapeDtypeStruct((3, 1, 64), jnp.float32), *cache,
        jax.ShapeDtypeStruct((3,), jnp.int32))
    dots = [e for e in step.jaxpr.eqns if e.primitive.name == "dot_general"]
    deep = [e.outvars[0].aval.shape for e in dots
            if any(48 in v.aval.shape for v in e.invars)]
    # the two score products [3, 4, 48] and the values' [3, 4, 32]
    assert sorted(deep) == [(3, 4, 32), (3, 4, 48), (3, 4, 48)]
    prefill = jax.make_jaxpr(lambda p, x: layer.apply_prefill(p, x))(
        params, jax.ShapeDtypeStruct((3, 48, 64), jnp.float32))
    assert "mla absorb" not in str(prefill.pretty_print(name_stack=True))
    assert "mla expand" in str(prefill.pretty_print(name_stack=True))
    assert "mla absorb" in str(step.pretty_print(name_stack=True))


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
    for a, b in zip(jax.tree_util.tree_leaves(padded),
                    jax.tree_util.tree_leaves(plain)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)
    assert model.cache_stats(padded)["moe_pairs_routed"] == 5 * 3 * 17
    assert float(jnp.abs(padded["latent"][0][1]).max()) == 0.0  # not named


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
    """Through GenerationEngine with `prefill_batch=1` (one row a
    prefill, the cell's setting), more requests than slots, so that
    requests join while a step is in flight and slots are reused at
    mixed ages."""
    prompts = _prompts()
    with GenerationEngine(model, slots=3, max_len=64, max_new_tokens=16,
                          prefill_batch=1, seq_buckets=[8, 16, 40]) as eng:
        assert eng.buckets == [1]
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
    assert stats["prefill_batches"] == stats["prefill_requests"] == 8
    assert stats["latent_cache_bytes"] == 3 * SLOT_BYTES
    assert 0 < stats["latent_positions_live"] \
        <= stats["latent_positions_read"]
    assert stats["latent_positions_read"] % (3 * 64) == 0
    assert stats["moe_pairs_routed"] > 0


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


def test_the_three_counters_are_in_the_stats_and_in_the_generation_record(
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
    assert stats["latent_cache_bytes"] == 2 * SLOT_BYTES
    # 7 tokens after the first, one live slot a step at positions 11..17
    # (a step computed for the request once it had ended counts too)
    assert stats["latent_positions_live"] >= sum(range(12, 19))
    assert stats["latent_positions_read"] >= 7 * 2 * 64
    assert stats["latent_positions_live"] <= stats["latent_positions_read"]
    assert "moe_pairs_routed" in stats \
        and "recurrent_slot_steps" not in stats \
        and "window_positions_skipped" not in stats
    records = [r for r in sink.records if r.get("type") == "generation"]
    assert len(records) >= 2
    for r in records:
        validate_record(r)
    assert all(name in records[-1] for name in COUNTERS)


# ------------------------------------------- the grouped flash forward
def _grouped_before(q, k, v, sm_scale, block_q, block_k, interpret, window):
    """`_flash_forward_grouped`'s call as it stood while q, k and v had
    one width (PR 37's text), over the kernel that is there."""
    import functools
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from bigdl_tpu.ops import attention_kernel as ak
    b, h, t, d = q.shape
    hk = k.shape[1]
    group = h // hk
    sm_scale = sm_scale or d ** -0.5
    block_q = min(block_q, t, max(128, 1024 // group // 128 * 128))
    block_k = min(block_k, t)
    n_kb = t // block_k
    steps = n_kb if window is None else min(
        n_kb, (window - 1 + block_q - 1) // block_k + 2)

    def kv_map(i, j, s):
        first, last = ak._grouped_kv_range(j, block_q, block_k, window)
        return i, jnp.minimum(first + s, last), 0
    kernel = functools.partial(ak._flash_fwd_grouped_kernel, block_q=block_q,
                               block_k=block_k, sm_scale=sm_scale,
                               window=window)
    rows = group * block_q
    out = pl.pallas_call(
        kernel, grid=(b * hk, t // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d), lambda i, j, s: (i, 0, j, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map)],
        out_specs=pl.BlockSpec((1, group, block_q, d),
                               lambda i, j, s: (i, 0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hk, group, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, d), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd_gqa" if window is None else "flash_fwd_window",
    )(q.reshape(b * hk, group, t, d), k.reshape(b * hk, t, d),
      v.reshape(b * hk, t, d))
    return out.reshape(b, h, t, d)


@pytest.mark.parametrize("hk, window", [(2, None), (4, 128), (1, 128)])
def test_with_one_width_the_grouped_forward_lowers_to_the_jaxpr_it_had(
        hk, window):
    from bigdl_tpu.ops.attention_kernel import _flash_forward_grouped
    q = jnp.ones((1, 4, 256, 32))
    k = jnp.ones((1, hk, 256, 32))
    new = jax.make_jaxpr(lambda q, k, v: _flash_forward_grouped(
        q, k, v, None, 256, 512, True, window))(q, k, k)
    old = jax.make_jaxpr(lambda q, k, v: _grouped_before(
        q, k, v, None, 256, 512, True, window))(q, k, k)
    assert str(new) == str(old)


@pytest.mark.parametrize("hk, dv, t", [(4, 16, 256), (4, 64, 512),
                                       (2, 16, 256)])
def test_the_grouped_forward_takes_a_value_width_of_its_own(hk, dv, t):
    """Interpret mode: q and k 24 or 48 wide, v another width, against
    the plain-XLA attention under the written-out causal mask; the scale
    is the query's width's, and the result the value's."""
    from bigdl_tpu.ops.attention_kernel import (flash_attention_forward,
                                                grouped_attention)
    d = 48 if dv == 64 else 24
    ks = jax.random.split(jax.random.PRNGKey(dv + t), 3)
    q = jax.random.normal(ks[0], (2, 4, t, d))
    k = jax.random.normal(ks[1], (2, hk, t, d))
    v = jax.random.normal(ks[2], (2, hk, t, dv))
    with jax.default_matmul_precision("highest"):
        got = flash_attention_forward(q, k, v, causal=True, interpret=True)
        keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        want = grouped_attention(q, k, v, keep)
    assert got.shape == want.shape == (2, 4, t, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="against"):
        flash_attention_forward(q, k, v[:, :, :128], causal=True,
                                interpret=True)
