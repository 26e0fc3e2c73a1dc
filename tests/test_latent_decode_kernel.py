"""`mla_decode` (ops/latent_decode_kernel.py), latent attention's decode
step as one Pallas kernel, in the interpreter on the CPU
(`attention_kernel.INTERPRET`), against the plain-XLA absorbed form
(`LatentAttention.attend_absorbed`) that the CPU keeps and that the
kernel replaces on a TPU.

Tolerances: float32 operands differ by summation order alone (the
online softmax rescales per block), 1e-5 on results of size 1 to 3;
bf16 operands round the probabilities before the values' product at
another point (the XLA form normalises first, the kernel divides by
the sum last), a few units of bf16's 2^-8 on the same results.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import latent_decoder_reference as ref
from bigdl_tpu.models.decoder import (DecoderLM, ExpertsKind, LatentDims,
                                      LayerSpec)
from bigdl_tpu.ops import attention_kernel
from bigdl_tpu.ops import latent_decode_kernel as ldk
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.serving import GenerationEngine

CFG = ref.SMALL
#: tiny widths: 4 heads, latent 32, rotary 8; 4 slots of 384 positions
#: in blocks of 128
B, H, RANK, ROPE, L, BLOCK = 4, 4, 32, 8, 384, 128
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 3e-2}
POSITIONS = {
    "idle": [0, 0, 0, 0],
    "block_edges": [BLOCK - 2, BLOCK - 1, BLOCK, 2 * BLOCK - 1],
    "last": [L - 1, 0, L - 1, 0],
    "full_depth": [L - 1] * 4,
    "mixed": [0, BLOCK - 1, BLOCK, L - 1],
}


def _model():
    """The tests' tiny latent decoder ([dense, experts x5]) on the
    reference's seeded weights."""
    layers = [LayerSpec(mixer="latent", rope_base=CFG["theta"], ffn="dense")
              if kind == "dense" else
              LayerSpec(mixer="latent", rope_base=CFG["theta"],
                        shared=CFG["shared"], router_reads="ffn")
              for kind in CFG["layers"]]
    model = DecoderLM(
        CFG["vocab"], CFG["hidden"], CFG["heads"], CFG["heads"],
        CFG["nope"] + CFG["rope"], layers, n_experts=CFG["experts"],
        expert_dim=CFG["expert_dim"], top_k=CFG["top_k"], eps=CFG["eps"],
        ffn_dim=CFG["ffn"],
        latent=LatentDims(CFG["nope"], CFG["rope"], CFG["value"],
                          CFG["rank"]),
        experts=ExpertsKind("silu", "sigmoid", CFG["scale"]))
    model.set_params(ref.to_program(CFG, ref.init_weights(CFG, 3)))
    return model


def _layer():
    return LatentAttention(64, H, 16, ROPE, 16, RANK, rope_base=1e6)


def _operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, H, RANK)).astype(dtype),
            jax.random.normal(ks[1], (B, H, ROPE)).astype(dtype),
            jax.random.normal(ks[2], (B, L, RANK)).astype(dtype),
            jax.random.normal(ks[3], (B, L, ROPE)).astype(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(POSITIONS))
def test_the_kernel_gives_the_absorbed_form(dtype, case):
    layer = _layer()
    q, qpe, c, pe = _operands(dtype)
    pos = jnp.asarray(POSITIONS[case], jnp.int32)
    got = ldk.mla_decode(q, qpe, c, pe, pos, layer.sm_scale, BLOCK,
                         interpret=True)
    want = layer.attend_absorbed(q, qpe, c, pe, pos)
    assert got.shape == (B, H, RANK) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("case", ["idle", "block_edges", "mixed"])
def test_nothing_past_a_slots_last_live_block_is_read(case):
    """Past each slot's last live block the cache holds NaN: the kernel,
    which never fetches those blocks, gives what the absorbed form gives
    over a clean cache; the absorbed form itself, which reads the whole
    depth and multiplies NaN by a probability of 0, does not."""
    layer = _layer()
    q, qpe, c, pe = _operands(jnp.float32, seed=1)
    pos = jnp.asarray(POSITIONS[case], jnp.int32)
    past = (jnp.arange(L)[None, :] >= ((pos // BLOCK + 1) * BLOCK)[:, None])
    dirty_c = jnp.where(past[..., None], jnp.nan, c)
    dirty_pe = jnp.where(past[..., None], jnp.nan, pe)
    got = ldk.mla_decode(q, qpe, dirty_c, dirty_pe, pos, layer.sm_scale,
                         BLOCK, interpret=True)
    want = layer.attend_absorbed(q, qpe, c, pe, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert not np.isfinite(np.asarray(layer.attend_absorbed(
        q, qpe, dirty_c, dirty_pe, pos))).all()


def test_positions_inside_a_live_block_but_past_the_slot_are_masked():
    """Large values at the positions after a slot's own, inside its last
    block, change nothing."""
    layer = _layer()
    q, qpe, c, pe = _operands(jnp.float32, seed=2)
    pos = jnp.asarray([3, BLOCK + 5, 2 * BLOCK + 1, 0], jnp.int32)
    after = jnp.arange(L)[None, :] > pos[:, None]
    noisy_c = jnp.where(after[..., None], 1e3, c)
    noisy_pe = jnp.where(after[..., None], 1e3, pe)
    got = ldk.mla_decode(q, qpe, noisy_c, noisy_pe, pos, layer.sm_scale,
                         BLOCK, interpret=True)
    want = layer.attend_absorbed(q, qpe, c, pe, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_the_cache_must_be_whole_blocks():
    q, qpe, c, pe = _operands(jnp.float32)
    with pytest.raises(ValueError, match="blocks of 256"):
        ldk.mla_decode(q, qpe, c, pe, jnp.zeros((B,), jnp.int32), 0.1, 256,
                       interpret=True)


def test_the_path_follows_the_backend_and_the_depth(monkeypatch):
    assert ldk.block_for(16384) is None       # the CPU: plain XLA
    monkeypatch.setattr(attention_kernel, "INTERPRET", True)
    assert [ldk.block_for(n) for n in (16384, 1024, 768, 384, 128)] == \
        [512, 512, 256, 128, 128]
    assert ldk.block_for(100) is None and ldk.block_for(64) is None
    monkeypatch.setattr(attention_kernel, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ldk.block_for(16384) == 512 and ldk.block_for(2000) is None


def test_the_counter_is_what_the_steps_path_read(monkeypatch):
    """`latent_positions_read` over one step with slots at 0, 127, 128
    and 383 of 384: blocks of 128 read on the kernel's path (1 + 1 + 2
    + 3), the whole depth of each slot on the XLA one; the live count is
    the same on both, and both give the same log-probs."""
    model = _model()
    params = model.ensure_params()
    pos = jnp.asarray([0, 127, 128, 383], jnp.int32)
    toks = jnp.asarray([5, 17, 33, 90], jnp.int32)
    out = {}
    for interpret in (False, True):
        monkeypatch.setattr(attention_kernel, "INTERPRET", interpret)
        cache = model.init_cache(B, L)
        logp, cache = jax.jit(model.apply_step)(params, toks, cache, pos)
        out[interpret] = np.asarray(logp), model.cache_stats(cache)
    (xla, xla_stats), (kern, kern_stats) = out[False], out[True]
    assert xla_stats["latent_positions_read"] == B * L
    assert kern_stats["latent_positions_read"] == (1 + 1 + 2 + 3) * BLOCK
    assert xla_stats["latent_positions_live"] == \
        kern_stats["latent_positions_live"] == 128 + 129 + 384
    np.testing.assert_allclose(kern, xla, atol=1e-4)


def test_the_engine_serves_the_same_greedy_tokens_on_both_paths(
        monkeypatch):
    """`GenerationEngine` over a tiny latent decoder (tiny-latent's
    widths) in 3 slots of 384, prompts that cross the first block edge
    while answers run: the kernel's path (under the interpreter) and the
    plain-XLA one serve the same tokens; only the read counter differs."""
    model = _model()
    rs = np.random.RandomState(6)
    prompts = [rs.randint(1, CFG["vocab"] + 1, size=n).astype(np.int32)
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
    assert xla_stats["latent_positions_read"] % (3 * L) == 0
    assert kern_stats["latent_positions_read"] % BLOCK == 0
    assert kern_stats["latent_positions_live"] \
        <= kern_stats["latent_positions_read"] \
        < xla_stats["latent_positions_read"]
