"""`gqa_decode` (ops/gqa_decode_kernel.py), grouped-query attention's
decode step as one Pallas kernel on the block loop `mla_decode` runs
(ops/ragged_decode.py), in the interpreter on the CPU
(`attention_kernel.INTERPRET`), against the plain-XLA form the CPU keeps
and the kernel replaces on a TPU: `grouped_attention` under
`kv_cache.step_mask`, over a full cache and over a window's ring.

Tolerances: float32 operands differ by summation order alone (the
online softmax rescales per block), 1e-5 on results of size 1 to 3;
bf16 operands round the probabilities before the values' product at
another point (the XLA form normalises first, the kernel divides by the
sum last), a few units of bf16's 2^-8 on the same results.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hybrid_decoder_reference as href
import sparse_decoder_reference as sref
from bigdl_tpu.models.decoder import DecoderLM, LayerSpec
from bigdl_tpu.nn import kv_cache
from bigdl_tpu.ops import attention_kernel
from bigdl_tpu.ops import gqa_decode_kernel as gdk
from bigdl_tpu.ops import ragged_decode
from bigdl_tpu.ops.attention_kernel import grouped_attention
from bigdl_tpu.serving import GenerationEngine

#: 4 slots, 2 K/V heads of 16, a cache 256 deep (or a ring of 256) in
#: blocks of 128
B, HK, HD, DEPTH, BLOCK = 4, 2, 16, 256, 128
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 3e-2}
POSITIONS = {
    "idle": [0, 0, 0, 0],
    "edges": [0, BLOCK - 1, BLOCK, DEPTH - 1],
    "mixed_ages": [0, 37, 0, 200],
    # a ring past its window: every index live, the newest at p % depth
    "wrapped": [DEPTH, DEPTH + BLOCK - 1, 3 * DEPTH + 5, 0],
}
CASES = [(kind, case) for kind in ("full", "window") for case in POSITIONS
         if not (kind == "full" and case == "wrapped")]
#: the cache in two blocks, and in one
BLOCKS = [BLOCK, DEPTH]


def _operands(group, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, HK * group, 1, HD)).astype(dtype),
            jax.random.normal(ks[1], (B, HK, DEPTH, HD)).astype(dtype),
            jax.random.normal(ks[2], (B, HK, DEPTH, HD)).astype(dtype))


def _xla(q, k, v, pos, kind):
    mask = kv_cache.step_mask(DEPTH, pos, DEPTH if kind == "window" else None)
    return grouped_attention(q, k, v, mask[:, :, None])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("kind,case", CASES,
                         ids=[f"{k}-{c}" for k, c in CASES])
def test_the_kernel_gives_the_masked_grouped_form(kind, case, group, block,
                                                  dtype):
    q, k, v = _operands(group, dtype)
    pos = jnp.asarray(POSITIONS[case], jnp.int32)
    got = gdk.gqa_decode(q, k, v, pos, block, interpret=True)
    want = _xla(q, k, v, pos, kind)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("group", [1, 7])
@pytest.mark.parametrize("case", ["idle", "edges", "mixed_ages"])
def test_nothing_past_a_slots_last_live_block_is_read(case, group):
    """Past each slot's last live block K and V hold NaN: the kernel,
    which never fetches those blocks, gives what the XLA form gives over
    a clean cache; the XLA form itself, which reads the whole depth and
    multiplies NaN by a probability of 0, does not."""
    q, k, v = _operands(group, jnp.float32, seed=1)
    pos = jnp.asarray(POSITIONS[case], jnp.int32)
    past = (jnp.arange(DEPTH)[None, :]
            >= ((pos // BLOCK + 1) * BLOCK)[:, None])[:, None, :, None]
    dirty_k, dirty_v = jnp.where(past, jnp.nan, k), jnp.where(past, jnp.nan, v)
    got = gdk.gqa_decode(q, dirty_k, dirty_v, pos, BLOCK, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_xla(q, k, v, pos, "full")),
                               atol=1e-5, rtol=1e-5)
    assert not np.isfinite(np.asarray(
        _xla(q, dirty_k, dirty_v, pos, "full"))).all()


def test_the_cache_must_be_whole_blocks_of_one_query_row():
    q, k, v = _operands(1, jnp.float32)
    pos = jnp.zeros((B,), jnp.int32)
    with pytest.raises(ValueError, match="blocks of 512"):
        gdk.gqa_decode(q, k, v, pos, 512, interpret=True)
    with pytest.raises(ValueError, match="q "):
        gdk.gqa_decode(jnp.concatenate([q, q], axis=2), k, v, pos, BLOCK,
                       interpret=True)


def test_the_block_follows_the_backend_and_the_shapes(monkeypatch):
    """The smallest block whose K and V carry 512 KiB together, else the
    largest that divides the depth: the hybrid cell's 30 K/V heads of
    128 in blocks of 128 (1.9 MiB), the mixed cell's 4 in blocks of 256
    (512 KiB), over a full cache or a ring."""
    assert gdk.block_for(16384, 4, 128, 2) is None    # the CPU: plain XLA
    monkeypatch.setattr(attention_kernel, "INTERPRET", True)
    assert gdk.block_for(100, 4, 128, 2) is None
    assert gdk.block_for(64, 2, 16, 4) is None
    assert [gdk.block_for(d, 4, 128, 2) for d in (16384, 4096, 384)] == \
        [256, 256, 128]
    assert gdk.block_for(2048, 30, 128, 2) == 128
    assert gdk.block_for(1024, 2, 16, 4) == 512       # tiny: the largest
    monkeypatch.setattr(attention_kernel, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gdk.block_for(16384, 4, 128, 2) == 256
    assert gdk.block_for(2000, 4, 128, 2) is None


# ------------------------------------------------- the decoders' decode step

#: the tiny sparse and hybrid decoders' widths, a ring of 128 beside
#: full layers 384 deep, so that every attention layer takes the kernel
L, WINDOW = 384, 128


def _sparse():
    c = sref.SMALL
    layers = [LayerSpec(None if w is None else WINDOW, b)
              for w, b in c["layers"]]
    model = DecoderLM(c["vocab"], c["hidden"], c["heads"], c["kv_heads"],
                      c["head_dim"], layers, c["experts"], c["expert_dim"],
                      c["top_k"], c["eps"], max_len=L)
    model.set_params(sref.to_program(c, sref.init_weights(c, 3)))
    return model


def _hybrid():
    c = href.SMALL
    layers = [LayerSpec(mixer="gated_delta" if kind == "linear"
                        else "attention", ffn="dense", norm="output")
              for kind in c["layers"]]
    model = DecoderLM(
        c["vocab"], c["hidden"], c["heads"], c["kv_heads"], c["head_dim"],
        layers, eps=c["eps"], max_len=L, ffn_dim=c["ffn"], qk_norm=True,
        linear_heads=c["lin_heads"], linear_key_dim=c["lin_key"],
        linear_value_dim=c["lin_value"], conv_taps=c["taps"],
        chunk=c["chunk"])
    model.set_params(href.to_program(c, href.init_weights(c, 3)))
    return model


MODELS = {"sparse": _sparse, "hybrid": _hybrid}


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_the_counters_are_what_the_steps_path_read(monkeypatch, kind):
    """One step with slots at 0, 127, 128 and 383: blocks of 128 read on
    the kernel's path (the only block 384 and 128 take), a full layer
    1 + 1 + 2 + 3 of them, a ring of 128 one a slot; the whole depth of
    every slot on the XLA one. The live count is the same on both, and
    both give the same log-probs."""
    model = MODELS[kind]()
    params = model.ensure_params()
    pos = jnp.asarray([0, 127, 128, 383], jnp.int32)
    toks = jnp.asarray([5, 17, 33, 90], jnp.int32)
    depths = [model.blocks[i].window or L for i in model._grouped]
    out = {}
    for interpret in (False, True):
        monkeypatch.setattr(attention_kernel, "INTERPRET", interpret)
        cache = model.init_cache(B, L)
        logp, cache = jax.jit(model.apply_step)(params, toks, cache, pos)
        out[interpret] = np.asarray(logp), model.cache_stats(cache)
    (xla, xla_stats), (kern, kern_stats) = out[False], out[True]
    full, ring = depths.count(L), depths.count(WINDOW)
    assert full + ring == len(depths) and full >= 1
    assert xla_stats["kv_positions_live"] == kern_stats["kv_positions_live"] \
        == full * (128 + 129 + 384) + ring * 3 * 128
    assert xla_stats["kv_positions_read"] == B * sum(depths)
    assert kern_stats["kv_positions_read"] == \
        full * (1 + 1 + 2 + 3) * BLOCK + ring * B * BLOCK
    np.testing.assert_allclose(kern, xla, atol=1e-4)


def test_the_read_counter_follows_the_ring():
    """A ring `depth` deep past its window reads every block; before it,
    the blocks up to the position."""
    pos = jnp.asarray([0, 5, 300, 1000], jnp.int32)
    assert float(ragged_decode.positions_read(pos, 256, 128)) == \
        128 + 128 + 256 + 256
    assert float(ragged_decode.positions_read(pos, 256, None)) == 4 * 256


@pytest.mark.parametrize("kind", sorted(MODELS))
def test_the_engine_serves_the_same_greedy_tokens_on_both_paths(
        monkeypatch, kind):
    """`GenerationEngine` over a tiny decoder in 3 slots of 384, prompts
    that cross the first block edge and (the sparse one's rings of 128)
    the window while answers run: the kernel's path (under the
    interpreter) and the plain-XLA one serve the same tokens; only the
    read counter differs."""
    model = MODELS[kind]()
    vocab = model.vocab
    rs = np.random.RandomState(6)
    prompts = [rs.randint(1, vocab + 1, size=n).astype(np.int32)
               for n in (120, 9, 131, 60)]
    served = {}
    for interpret in (False, True):
        monkeypatch.setattr(attention_kernel, "INTERPRET", interpret)
        with GenerationEngine(model, slots=3, max_len=L, max_new_tokens=16,
                              prefill_batch=1,
                              seq_buckets=[16, 72, 136]) as eng:
            streams = [eng.generate(p, max_new_tokens=16) for p in prompts]
            outs = [list(s.result(300.0)) for s in streams]
            stats = eng.generation_stats()
        served[interpret] = outs, stats
    (xla, xla_stats), (kern, kern_stats) = served[False], served[True]
    assert kern == xla and all(len(o) == 16 for o in kern)
    assert kern_stats["kv_positions_read"] % BLOCK == 0
    assert kern_stats["kv_positions_live"] \
        <= kern_stats["kv_positions_read"] \
        < xla_stats["kv_positions_read"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("kind,case", CASES,
                         ids=[f"{k}-{c}" for k, c in CASES])
def test_heads_folded_in_lanes_read_as_the_unfolded_form(kind, case, group,
                                                         dtype):
    """Heads of 64 as the cache folds them, two a row of 128 lanes
    (`kv_cache.lane_fold`): each query row reads its own head's lanes
    alone, so the kernel over the folded buffers gives the masked
    grouped form over the heads apart, to the tolerances above."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    hk, hd = 4, 64
    q = jax.random.normal(ks[0], (B, hk * group, 1, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, hk, DEPTH, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, hk, DEPTH, hd)).astype(dtype)
    fold = kv_cache.lane_fold(hk, hd)
    assert fold == 2
    pos = jnp.asarray(POSITIONS[case], jnp.int32)
    got = gdk.gqa_decode(q, kv_cache.fold_heads(k, fold),
                         kv_cache.fold_heads(v, fold), pos, BLOCK,
                         interpret=True)
    want = _xla(q, k, v, pos, kind)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])
