"""Each analytic count against `jaxpr_flops` (exact for matmuls and
convolutions, one operation an element for the rest) on a small model."""

from functools import partial

import jax
import pytest

from benchmarks.files import Manifest, load_py
from bigdl_tpu.observability.costs import jaxpr_flops


def test_resnet50_forward_count_matches_the_jaxpr():
    cfg = dict(Manifest().config("resnet50"), image_size=64)
    ref, counts = load_py("reference", "resnet50"), load_py("counts", "resnet50")
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    x, y = jax.eval_shape(lambda: ref.train_batch(
        cfg, {"per_chip_batch": 2}, 0, 1))
    traced = jaxpr_flops(jax.make_jaxpr(partial(ref.loss, cfg))(w, x, y)) / 2
    counted = counts.forward_flops_per_item(cfg)
    # the jaxpr adds the elementwise work (normalisation, ReLU, pooling)
    assert counted < traced < 1.12 * counted
    assert counts.train_flops_per_item(cfg, {}) == 3 * counted


def test_resnet50_at_224_is_the_published_size():
    cfg = Manifest().config("resnet50")
    gflops = load_py("counts", "resnet50").train_flops_per_item(cfg, {}) / 1e9
    assert gflops == pytest.approx(24.6, abs=0.3)  # 3 x 2 x 4.1 GMACs


def test_neox_counts_match_the_jaxpr():
    m = Manifest()
    cfg = dict(m.config("neox-3.6b"), hidden_size=128, intermediate_size=512,
               num_attention_heads=4, num_hidden_layers=2, vocab_size=512)
    mix = {"per_chip_batch": 2, "sequence": 64}
    ref, counts = load_py("reference", "neox-3.6b"), load_py("counts", "neox-3.6b")
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    x, y = jax.eval_shape(lambda: ref.train_batch(cfg, mix, 0, 1))
    traced = jaxpr_flops(jax.make_jaxpr(partial(ref.loss, cfg))(w, x, y)) \
        / (2 * 64)
    counted = counts.train_flops_per_item(cfg, mix) / 3
    # the plain reference multiplies the whole T x T square, the count
    # takes the causal half: 2 * T * hidden a layer and token more
    square = 2 * 2 * 64 * 128
    assert counted + square < traced < 1.1 * (counted + square)
    # serving: a prompt of p tokens and n generated ones
    p, n = 40, 8
    attn = p * (p + 1) // 2 + sum(range(p + 1, p + n))
    c = {"prompt_tokens": p, "tokens_out": n, "attention_positions": attn}
    dense = 2 * (p + n) * 2 * (4 * 128 * 128 + 2 * 128 * 512)
    assert counts.serve_flops(cfg, c) == pytest.approx(
        dense + 2 * n * 128 * 512 + 4 * 2 * 128 * attn)


def test_neox_at_depth_4_is_2_96_gflop_a_token():
    m = Manifest()
    cfg, mix = m.config("neox-3.6b"), m.traffic("train-t2048")
    counts = load_py("counts", "neox-3.6b")
    assert counts.train_flops_per_item(cfg, mix) / 1e9 == pytest.approx(
        2.96, abs=0.02)
    # bf16: 95 M parameters a layer and the head, 45 KB of cache a position
    assert counts.decode_weight_bytes(cfg, 2) == pytest.approx(
        2 * (4 * 95.2e6 + 90.1e6), rel=0.01)
    assert counts.cache_bytes_per_position(cfg, 2) == 45056


def test_flash_kernel_count():
    flash = load_py("counts", "flash_attention")
    q = ("bf16", (4, 22, 2048, 128))
    flops, nbytes = flash.work([q, ("f32", (4, 22, 2048, 1))], [q, q, q])
    assert flops == 2 * 4 * 22 * 2048 * 2048 * 128  # two matmuls, causal half
    assert nbytes == 4 * 4 * 22 * 2048 * 128 * 2 + 4 * 22 * 2048 * 4
