"""Every costly operation of the serving and training programs lies under
one of the program's named scopes (PERF.md section 3), so that a device
trace can say which part of the model spent its time
(`benchmarks/readers/scope_device_ms.py`).

Each case compiles one program of a tiny model on the CPU and reads its
HLO text: every dot, convolution, custom call, dynamic-update-slice,
scatter, gather and reduce that carries a `metadata={op_name=...}` has a
component of the vocabulary in it, and in a decode program every write
into a K/V or latent cache buffer lies under `kv write`. An instruction
with no metadata at all is one the compiler made (the CPU's rewrite of a
strided convolution's weight gradient, `convolution-window-dilated`):
the program cannot name it, and a trace reads it as unscoped; such
instructions stay a small share of the costly ones.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_decoder_reference as href
import latent_decoder_reference as lref
import sparse_decoder_reference as sref
from benchmarks.files import load_py
from bigdl_tpu.models.decoder import (DecoderLM, ExpertsKind, LatentDims,
                                      LayerSpec)
from bigdl_tpu.models.resnet import ResNet
from bigdl_tpu.models.transformer import TransformerLM
from bigdl_tpu.serving.generation import _next_token

SCOPES = load_py("readers", "scope_device_ms")
#: the program's scopes that the reader's `VOCABULARY` (the benchmark's
#: file) does not list yet: the gated short convolution's
PROGRAM_SCOPES = {"short conv", "conv project", "conv taps"}
COSTLY = ("dot", "convolution", "custom-call", "dynamic-update-slice",
          "scatter", "gather", "reduce")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+?)(?:\{[^}]*\})? ([\w\-]+)\(")
SLOTS = 4


def _dense():
    return DecoderLM(128, 64, 4, 2, 16,
                     [LayerSpec(rope_base=1e4, ffn="dense"),
                      LayerSpec(window=8, rope_base=1e4, ffn="dense")],
                     ffn_dim=96, max_len=32)


def _sparse():
    c = sref.SMALL
    return DecoderLM(c["vocab"], c["hidden"], c["heads"], c["kv_heads"],
                     c["head_dim"], [LayerSpec(w, b) for w, b in c["layers"]],
                     c["experts"], c["expert_dim"], c["top_k"], c["eps"],
                     max_len=c["max_len"])


def _hybrid():
    c = href.SMALL
    layers = [LayerSpec(mixer="gated_delta" if kind == "linear"
                        else "attention", ffn="dense", norm="output")
              for kind in c["layers"]]
    return DecoderLM(
        c["vocab"], c["hidden"], c["heads"], c["kv_heads"], c["head_dim"],
        layers, eps=c["eps"], max_len=c["max_len"], ffn_dim=c["ffn"],
        qk_norm=True, linear_heads=c["lin_heads"],
        linear_key_dim=c["lin_key"], linear_value_dim=c["lin_value"],
        conv_taps=c["taps"], chunk=c["chunk"])


def _latent():
    c = lref.SMALL
    specs = [LayerSpec(mixer="latent", rope_base=c["theta"], ffn="dense")
             if kind == "dense" else
             LayerSpec(mixer="latent", rope_base=c["theta"],
                       shared=c["shared"], router_reads="ffn")
             for kind in c["layers"]]
    return DecoderLM(
        c["vocab"], c["hidden"], c["heads"], c["heads"],
        c["nope"] + c["rope"], specs, n_experts=c["experts"],
        expert_dim=c["expert_dim"], top_k=c["top_k"], eps=c["eps"],
        max_len=c["max_len"], ffn_dim=c["ffn"],
        latent=LatentDims(c["nope"], c["rope"], c["value"], c["rank"]),
        experts=ExpertsKind("silu", "sigmoid", c["scale"]))


def _lfm2():
    """LFM2's block at a tiny size: short convolutions beside attention
    of 4 query heads over 2 K/V heads of 64 (two a row of the cache's
    128 lanes) with an RMSNorm a head on q and k, two dense layers then
    sigmoid-routed experts, the head tied to the embedding."""
    kinds = ("conv", "conv", "attention", "conv")
    layers = [LayerSpec(mixer="short_conv" if k == "conv" else "attention",
                        rope_base=None if k == "conv" else 1e6,
                        ffn="dense" if i < 2 else "experts",
                        router_reads="attention" if i < 2 else "ffn")
              for i, k in enumerate(kinds)]
    return DecoderLM(128, 64, 4, 2, 64, layers, n_experts=8, expert_dim=32,
                     top_k=4, eps=1e-5, max_len=256, ffn_dim=96,
                     qk_norm=True, qk_norm_per_head=True, conv_taps=3,
                     tie_head=True,
                     experts=ExpertsKind("silu", "sigmoid", 1.0))


def _lm():
    return TransformerLM(64, embed_dim=32, n_layer=2, n_head=2,
                         use_flash=False, max_len=32)


SERVED = {"dense": _dense, "sparse": _sparse, "hybrid": _hybrid,
          "latent": _latent, "lfm2": _lfm2, "transformer": _lm}


def _serving(kind, program):
    """(HLO text, shapes of the cache's K/V and latent buffers) of the
    decode or prefill program the engine compiles for `kind`."""
    model = SERVED[kind]()
    params = model.ensure_params(jax.random.PRNGKey(0))
    cache = model.init_cache(SLOTS, 32)
    kept = {k: v for k, v in cache.items()
            if k in ("k", "v", "latent", "k_pe")}
    shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(kept)}
    if program == "decode":
        def fn(params, cache, tokens, positions):
            logp, cache = model.apply_step(params, tokens, cache, positions)
            return _next_token(logp), cache
        args = (jnp.ones((SLOTS,), jnp.int32),
                jnp.arange(SLOTS, dtype=jnp.int32))
    else:
        def fn(params, cache, tokens, slot_ids, lengths):
            logp, cache = model.apply_prefill(params, tokens, cache,
                                              slot_ids, lengths)
            return _next_token(logp), cache
        args = (jnp.ones((2, 16), jnp.int32), jnp.array([0, 1], jnp.int32),
                jnp.array([5, 16], jnp.int32))
    text = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile().as_text()
    return text, shapes


def _training(kind):
    """HLO text of `BaseOptimizer._step_body`'s step for `kind`, bf16
    over float32 masters as the benchmark trains."""
    import bigdl_tpu.nn as nn
    from bigdl_tpu.optim import SGD, Adam
    from bigdl_tpu.optim.local_optimizer import LocalOptimizer
    if kind == "resnet":
        model = ResNet(10, depth=18, s2d_stem=True)
        x = jnp.ones((2, 32, 32, 3), jnp.float32)
        y = jnp.array([1, 2], jnp.int32)
        crit, method = nn.ClassNLLCriterion(), SGD(0.01, momentum=0.9)
    else:
        model = TransformerLM(64, embed_dim=32, n_layer=2, n_head=2,
                              max_len=32)
        x = jnp.ones((2, 16), jnp.int32)
        y = jnp.ones((2, 16), jnp.int32)
        crit = nn.TimeDistributedMaskCriterion(nn.ClassNLLCriterion())
        method = Adam()
    opt = LocalOptimizer(model, None, crit)
    opt.set_optim_method(method)
    opt.set_compute_precision("bfloat16")
    params = model.ensure_params(jax.random.PRNGKey(0))
    state = opt.optim_method.init_state_with_masters(params)
    return jax.jit(opt._step_body()).lower(
        params, state, model._state, x, y, jnp.float32(0.01),
        jax.random.PRNGKey(1)).compile().as_text()


def _costly(text):
    """(opcode, result shape, op_name components or None) of every costly
    instruction, in every computation of the program."""
    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m or m.group(3) not in COSTLY:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        dims = re.match(r"\w+\[([\d,]*)\]", m.group(2))
        shape = tuple(int(d) for d in dims.group(1).split(",") if d) \
            if dims else None
        out.append((m.group(3), shape,
                    SCOPES.components(name.group(1)) if name else None))
    return out


CASES = [(kind, program) for kind in SERVED
         for program in ("decode", "prefill")] \
    + [("resnet", "train"), ("transformer", "train")]


@pytest.mark.parametrize("kind,program", CASES,
                         ids=[f"{k}-{p}" for k, p in CASES])
def test_every_costly_operation_lies_under_a_scope(kind, program):
    if program == "train":
        text, cache_shapes = _training(kind), set()
    else:
        text, cache_shapes = _serving(kind, program)
    ops = _costly(text)
    assert ops, "the program holds no costly operation"
    vocabulary = set(SCOPES.VOCABULARY) | PROGRAM_SCOPES
    unscoped = [(op, shape, comps) for op, shape, comps in ops
                if comps and not vocabulary.intersection(comps)]
    assert not unscoped, unscoped[:10]
    nameless = [(op, shape) for op, shape, comps in ops if not comps]
    assert len(nameless) <= len(ops) // 20, nameless
    if program == "decode":
        writes = [comps for op, shape, comps in ops
                  if op in ("dynamic-update-slice", "scatter")
                  and shape in cache_shapes]
        assert writes, "no write into the cache was found"
        assert all("kv write" in comps for comps in writes), writes
    if program == "train":
        # the update is elementwise: no costly operation, but it is named
        named = {c for n in re.findall(r'op_name="([^"]*)"', text)
                 for c in SCOPES.components(n)}
        assert {"loss", "optimizer update"} <= named


def test_resnet_stages_are_scoped_where_the_model_is_built():
    """The stage scopes ride on the one Sequential that `ResNet` fills:
    the parameter tree and the children are what they were."""
    model = ResNet(10, depth=18)
    assert model._scopes == {0: "stem", 4: "stage 1", 6: "stage 2",
                             8: "stage 3", 10: "stage 4", 12: "classifier"}
    assert len(model.children) == 15
    x = jnp.ones((1, 32, 32, 3), jnp.float32)
    out = model.forward(x)
    assert out.shape == (1, 10) and np.isfinite(np.asarray(out)).all()


#: a tiny model of each kind whose decode step takes a kernel on a TPU
#: (caches of 256, rings of 128: whole blocks), and the scope each of its
#: kernel calls must read under, in the order of the layers
_KERNEL_CASES = {
    "sparse": (lambda: DecoderLM(
        128, 64, 4, 2, 16,
        [LayerSpec(window=128 if i % 4 else None,
                   rope_base=1e6 if i % 4 else None) for i in range(4)],
        8, 32, 2), ["full attention"] + ["window attention"] * 3),
    "hybrid": (_hybrid, ["full attention"] * 2),
    "latent": (_latent, ["mla attend"] * 6),
    "lfm2": (_lfm2, ["full attention"]),
}


@pytest.mark.parametrize("kind", sorted(_KERNEL_CASES))
def test_the_decode_kernels_read_under_their_layers_scope(kind, monkeypatch):
    """Lowered for a TPU under its routing, each `gqa_decode` and
    `mla_decode` call of a decode step carries the path of the layer it
    reads for: a device trace reads it under `full attention`,
    `window attention` or `latent attention` > `mla attend`."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    build, scopes = _KERNEL_CASES[kind]
    model = build()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(SLOTS, 256))
    ids = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    text = jax.jit(model.apply_step).trace(params, ids, cache, ids).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = re.findall(r"custom_call @tpu_custom_call\(.*loc\((#loc\d+)\)$",
                       text, re.M)
    paths = [SCOPES.components(locs[c]) for c in calls]
    assert len(paths) == len(scopes)
    kernel = "mla_decode" if kind == "latent" else "gqa_decode"
    for comps, scope in zip(paths, scopes):
        assert scope in comps and kernel in comps, comps
        if kind == "latent":
            assert "latent attention" in comps


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_short_convolution_reads_under_its_own_scopes(program):
    """Its projections under `short conv` > `conv project`, its taps and
    gates (elementwise: fused, and named) under `short conv` > `conv
    taps`, the prefill's tail gathered under `short conv`; the q/k norms
    a head (a reduce over each head's 64 values) under `full
    attention`."""
    text, _ = _serving("lfm2", program)
    ops = _costly(text)
    conv = [comps for _, _, comps in ops if comps and "short conv" in comps]
    assert any("conv project" in c for c in conv)
    named = {tuple(SCOPES.components(n))
             for n in re.findall(r'op_name="([^"]*)"', text)}
    assert any("short conv" in c and "conv taps" in c for c in named)
    norms = [comps for op, shape, comps in ops if op == "reduce" and comps
             and "full attention" in comps]
    assert norms
