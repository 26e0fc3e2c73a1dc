"""How deep a decode step reads the cache (nn/kv_cache.py's ladder,
`MultiHeadAttention.apply_step`'s `lax.switch` over it, and the engine's
counters of it). On the CPU at tiny widths, with `max_len` 1024 so that
the ladder has its four rungs: 128 / 256 / 512 / 1024.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import sparse_decoder_reference as sparse_ref
from bigdl_tpu.models.decoder import LayerSpec, SparseDecoderLM
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import MultiHeadAttention
from bigdl_tpu.observability import InMemorySink, Telemetry
from bigdl_tpu.observability.telemetry import validate_record
from bigdl_tpu.ops.attention_kernel import naive_attention
from bigdl_tpu.serving import GenerationEngine, greedy_decode_reference

MAX_LEN = 1024
RUNGS = {2048: (256, 512, 1024, 2048), 1024: (128, 256, 512, 1024),
         1000: (128, 256, 512, 1000), 100: (100,), 64: (64,), 1: (1,)}


def has_cond(jaxpr) -> bool:
    return "cond[" in str(jaxpr)


# ------------------------------------------------------------ the ladder
@pytest.mark.parametrize("max_len", sorted(RUNGS))
def test_rungs(max_len):
    assert kv_cache.depth_rungs(max_len) == RUNGS[max_len]


@pytest.mark.parametrize("max_len", sorted(RUNGS))
def test_rung_index_at_every_edge_on_the_host_and_traced(max_len):
    rungs = kv_cache.depth_rungs(max_len)
    traced = jax.jit(lambda p: kv_cache.rung_index(rungs, p))
    # (deepest position, the rung it takes): d - 1 is the last position
    # rung d covers, d the first of the next; idle slots ride at 0
    cases = [(0, rungs[0]), (max_len - 1, max_len)]
    for d, nxt in zip(rungs, rungs[1:]):
        cases += [(d - 1, d), (d, nxt)]
    for deepest, want in cases:
        positions = np.zeros((5,), np.int32)
        positions[3] = deepest
        positions[1] = deepest // 2
        on_host = kv_cache.rung_index(rungs, positions)
        assert rungs[int(on_host)] == want, (deepest, want)
        assert int(traced(positions)) == int(on_host)


# ------------------------------------------------------- the layer's step
def _whole_depth_step(mha, params, x, k_cache, v_cache, positions):
    """`apply_step` as it was before the ladder: the whole depth read."""
    q, k, v = mha.project_qkv(params, x, positions=positions[:, None])
    k_cache = kv_cache.write(k_cache, k, positions)
    v_cache = kv_cache.write(v_cache, v, positions)
    mask = kv_cache.step_mask(k_cache.shape[2], positions)
    o = naive_attention(q, k_cache, v_cache, mask=mask)
    return mha._finish(params, o), k_cache, v_cache


#: deepest slot on both sides of every rung's edge, the other slots
#: younger (one idle at 0): every branch of the switch is taken
STRADDLES = [(0, 3, 60, 127), (0, 128, 5, 90), (255, 0, 130, 17),
             (9, 256, 0, 200), (300, 2, 511, 0), (512, 0, 44, 380),
             (0, 1023, 700, 128), (0, 0, 0, 0)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_step_matches_the_whole_depth_read_across_every_rung(dtype):
    mha = MultiHeadAttention(32, 2, causal=True, use_rope=True,
                             use_flash=False)
    params = jax.tree_util.tree_map(
        lambda w: w.astype(dtype), mha.init(jax.random.PRNGKey(1)))
    rs = np.random.RandomState(4)
    # a cache that holds something everywhere: what lies beyond a slot's
    # position (a former occupant's K/V) has to be masked, not absent
    k0 = jnp.asarray(rs.randn(4, 2, MAX_LEN, 16), dtype)
    v0 = jnp.asarray(rs.randn(4, 2, MAX_LEN, 16), dtype)
    x = jnp.asarray(rs.randn(4, 1, 32), dtype)
    step = jax.jit(mha.apply_step)
    whole = jax.jit(lambda *a: _whole_depth_step(mha, *a))
    # float32: both sum the same non-zero terms (a masked position weighs
    # exp(-1e30 - m) = 0 exactly), in another order: 1e-6 is some ten
    # roundings of outputs of size O(1). bf16: the probabilities, their
    # sum and the output are rounded to bf16, so the two orders may land
    # one bf16 rounding apart: 2^-8 of the largest output
    taken = set()
    for positions in STRADDLES:
        positions = jnp.asarray(positions, jnp.int32)
        taken.add(int(kv_cache.rung_index(RUNGS[MAX_LEN], positions)))
        got, k1, v1 = step(params, x, k0, v0, positions)
        want, k2, v2 = whole(params, x, k0, v0, positions)
        want = np.asarray(want, np.float32)
        tol = 1e-6 if dtype == jnp.float32 else np.abs(want).max() * 2 ** -8
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=0, atol=tol)
        # the write is the whole buffer's, as before: the same bytes, and
        # nothing but each slot's own position touched
        for new, ref, old in ((k1, k2, k0), (v1, v2, v0)):
            new, old = np.asarray(new, np.float32), np.array(old, np.float32)
            np.testing.assert_array_equal(new, np.asarray(ref, np.float32))
            for b, p in enumerate(np.asarray(positions)):
                old[b, :, p] = new[b, :, p]
            np.testing.assert_array_equal(new, old)
    assert taken == {0, 1, 2, 3}


def test_the_rung_the_host_counts_is_the_depth_the_device_reads():
    """The engine counts `rung_index(rungs, positions)` on the host; the
    device takes a branch by the same function, traced. Tie them: NaN in
    K and V from the host's rung on. Were the device's branch deeper, a
    masked NaN in V would give 0 * NaN = NaN; were it shallower than the
    deepest slot, the result would not be the whole depth's."""
    mha = MultiHeadAttention(32, 2, causal=True, use_rope=True,
                             use_flash=False)
    params = mha.init(jax.random.PRNGKey(1))
    rs = np.random.RandomState(7)
    k0 = rs.randn(4, 2, MAX_LEN, 16).astype(np.float32)
    v0 = rs.randn(4, 2, MAX_LEN, 16).astype(np.float32)
    x = jnp.asarray(rs.randn(4, 1, 32), jnp.float32)
    step = jax.jit(mha.apply_step)
    whole = jax.jit(lambda *a: _whole_depth_step(mha, *a))
    for positions in STRADDLES:
        positions = np.asarray(positions, np.int32)
        d = RUNGS[MAX_LEN][int(kv_cache.rung_index(RUNGS[MAX_LEN],
                                                   positions))]
        k_nan, v_nan = k0.copy(), v0.copy()
        k_nan[:, :, d:] = v_nan[:, :, d:] = np.nan
        got = np.asarray(step(params, x, k_nan, v_nan, positions)[0])
        assert np.isfinite(got).all(), (positions, d)
        np.testing.assert_allclose(
            got, np.asarray(whole(params, x, k0, v0, positions)[0]),
            rtol=0, atol=1e-6)
        if d < MAX_LEN:  # the whole depth's read does meet the NaN
            assert np.isnan(np.asarray(
                whole(params, x, k_nan, v_nan, positions)[0])).any()


def test_one_rung_means_no_switch():
    mha = MultiHeadAttention(32, 2, causal=True, use_rope=True,
                             use_flash=False)
    params = mha.init(jax.random.PRNGKey(1))
    x, positions = jnp.ones((3, 1, 32)), jnp.array([0, 5, 40])
    for depth, laddered in ((64, False), (100, False), (MAX_LEN, True)):
        cache = jnp.zeros((3, 2, depth, 16))
        new = jax.make_jaxpr(mha.apply_step)(params, x, cache, cache,
                                             positions)
        assert has_cond(new) == laddered
        if not laddered:  # today's jaxpr, equation for equation
            old = jax.make_jaxpr(
                lambda *a: _whole_depth_step(mha, *a))(
                    params, x, cache, cache, positions)
            assert str(new) == str(old)


# --------------------------------------------------------- the bypass
def sparse_model():
    cfg = sparse_ref.SMALL
    m = SparseDecoderLM(
        cfg["vocab"], cfg["hidden"], cfg["heads"], cfg["kv_heads"],
        cfg["head_dim"], [LayerSpec(w, b) for w, b in cfg["layers"]],
        cfg["experts"], cfg["expert_dim"], cfg["top_k"], cfg["eps"],
        max_len=MAX_LEN)
    m.set_params(sparse_ref.to_program(cfg, sparse_ref.init_weights(cfg, 3)))
    return m


def test_the_sparse_decoders_step_has_no_ladder():
    """`GroupedQueryAttention.apply_step` reads its whole cache, at a
    depth where `MultiHeadAttention` would switch: `kv_cache.write` and
    `step_mask` give it what they gave."""
    m = sparse_model()
    cache = m.init_cache(2, MAX_LEN)
    jaxpr = jax.make_jaxpr(m.apply_step)(
        m.ensure_params(), jnp.ones((2,), jnp.int32), cache,
        jnp.array([3, 600], jnp.int32))
    assert not has_cond(jaxpr)
    assert not hasattr(m, "decode_depths")
    with GenerationEngine(m, slots=2, max_len=MAX_LEN, seq_buckets=[16],
                          prefill_batch=1) as eng:
        eng.generate(np.array([5, 9, 2], np.int32),
                     max_new_tokens=4).result(120.0)
        stats = eng.generation_stats()
    assert stats["decode_depth_share"] == 1.0
    assert stats["decode_steps_by_depth"] == {
        str(MAX_LEN): stats["decode_steps"]}


# ------------------------------------------------------------ the engine
@pytest.fixture(scope="module")
def lm():
    m = TransformerLM(64, embed_dim=32, n_layer=2, n_head=2,
                      use_flash=False, max_len=MAX_LEN)
    m.ensure_params(jax.random.PRNGKey(0))
    fwd = jax.jit(lambda p, t: m.apply(p, t, None))
    return m, lambda prompt, n: greedy_decode_reference(
        m, m.ensure_params(), prompt, n, pad_to=640, fwd=fwd)


def test_engine_under_churn_reads_to_the_deepest_slots_rung(lm):
    m, ref = lm
    rs = np.random.RandomState(5)
    # prompts on both sides of the 128 and the 256 rung, with budgets
    # that carry two of them over an edge while younger slots decode
    lengths = [120, 20, 131, 250, 60, 259, 9]
    budgets = [14, 9, 5, 12, 20, 4, 11]
    prompts = [rs.randint(1, 65, size=n).astype(np.int32) for n in lengths]
    sink = InMemorySink()
    with GenerationEngine(m, slots=3, max_len=MAX_LEN, emit_every=5,
                          seq_buckets=[32, 128, 512, 640], prefill_batch=1,
                          telemetry=Telemetry(sink, resources=False)) as eng:
        n = eng.warmup()
        streams = []
        for p, k in zip(prompts, budgets):
            streams.append(eng.generate(p, max_new_tokens=k))
            time.sleep(0.002)
        outs = [s.result(120.0) for s in streams]
        short = eng.generation_stats()
        # one slot past max_len / 2: every step reads the whole depth
        deep = rs.randint(1, 65, size=530).astype(np.int32)
        out_deep = eng.generate(deep, max_new_tokens=6).result(120.0)
        after = eng.generation_stats()
        assert eng.compile_count() == n
    decodes = [r for r in sink.records if r.get("type") == "compile"
               and r["label"].startswith("serving.decode/")]
    assert len(decodes) == 1  # ONE decode program, whatever the depth
    assert outs == [ref(p, k) for p, k in zip(prompts, budgets)]
    assert out_deep == ref(deep, 6)

    by_depth = short["decode_steps_by_depth"]
    assert list(by_depth) == ["128", "256", "512", "1024"]
    assert sum(by_depth.values()) == short["decode_steps"]
    assert by_depth["128"] > 0 and by_depth["256"] > 0
    assert by_depth["512"] > 0 and by_depth["1024"] == 0
    assert short["decode_read_depth_total"] == sum(
        int(d) * k for d, k in by_depth.items())
    assert short["decode_depth_share"] == round(
        short["decode_read_depth_total"]
        / (short["decode_steps"] * MAX_LEN), 4)
    assert 0.125 <= short["decode_depth_share"] < 0.5

    steps = after["decode_steps"] - short["decode_steps"]
    assert steps == 5  # six tokens: the first is the prefill's
    assert after["decode_steps_by_depth"]["1024"] == steps
    assert after["decode_read_depth_total"] \
        - short["decode_read_depth_total"] == steps * MAX_LEN
    assert sum(after["decode_steps_by_depth"].values()) \
        == after["decode_steps"]
    records = [r for r in sink.records if r.get("type") == "generation"]
    assert records and "decode_depth_share" in records[-1]
    for r in records:
        validate_record(r)


def test_a_deep_slot_alone_gives_a_share_of_one(lm):
    m, ref = lm
    prompt = np.random.RandomState(8).randint(1, 65, size=513) \
        .astype(np.int32)
    with GenerationEngine(m, slots=2, max_len=MAX_LEN, seq_buckets=[640],
                          prefill_batch=1) as eng:
        assert eng.generation_stats()["decode_depth_share"] is None
        out = eng.generate(prompt, max_new_tokens=4).result(120.0)
        stats = eng.generation_stats()
    assert out == ref(prompt, 4)
    assert stats["decode_depth_share"] == 1.0
    assert stats["decode_steps_by_depth"] == {
        "128": 0, "256": 0, "512": 0, "1024": stats["decode_steps"]}


def test_a_step_dropped_unfetched_is_counted_where_it_was_dispatched(lm):
    """The rung is counted at dispatch: the step that rides once more
    after EOS ended its only request is never fetched, so it is in
    `decode_steps_by_depth` and not in `decode_steps`; the share stays
    one over the steps dispatched."""
    m, ref = lm
    prompt = np.random.RandomState(9).randint(1, 65, size=300) \
        .astype(np.int32)
    want = ref(prompt, 8)
    eos = want[3]
    stop = want.index(eos)  # 1 + `stop` tokens come, the last the EOS
    assert stop > 0  # a decode step's token, not the prefill's
    with GenerationEngine(m, slots=2, max_len=MAX_LEN, seq_buckets=[512],
                          prefill_batch=1) as eng:
        out = eng.generate(prompt, max_new_tokens=8,
                           eos_id=eos).result(120.0)
        stats = eng.generation_stats()
    assert out == want[:stop + 1]
    assert stats["decode_steps"] == stop
    by_depth = stats["decode_steps_by_depth"]
    assert sum(by_depth.values()) == by_depth["512"] == stop + 1
    assert stats["decode_read_depth_total"] == 512 * by_depth["512"]
    assert stats["decode_depth_share"] == 0.5
