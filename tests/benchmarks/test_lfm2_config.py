"""The `lfm2-8b-a1b` configuration's benchmark files at a size a test run
can hold (benchmarks/testdata/tiny-lfm2: the same block at hidden 64, 8
query heads over 2 K/V heads of 8, a dense FFN of 128, 8 experts of 32
with top 4, the first eight of the published `layer_types` (conv, conv,
attention, conv x3, attention, conv), 12 slots): the benchmark's own
reference against the program, through the harness and directly in
float32; its counts against the reference's shapes; the control and the
planted faults that have to come out as not correct; and the cell's
place in the manifest, appended after the accepted ones."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.files import HERE, Manifest, load_json, load_py
from bigdl_tpu.observability.costs import jaxpr_flops

SEEDS = (5, 6, 7)
CELL = "lfm2-8b-a1b.serve-wide"
BASE = os.path.join(HERE, "testdata", "tiny-lfm2")


@pytest.fixture(scope="module")
def tiny_manifest():
    return Manifest(os.path.join(BASE, "BENCHMARK.json"), BASE)


@pytest.fixture(scope="module")
def runs(tiny_manifest, tmp_path_factory):
    """One run of the tiny serving cell a seed, kept for the controls."""
    from benchmarks.harness import run_cell
    out = {}
    for seed in SEEDS:
        keep = {}
        result = run_cell("tiny-lfm2.serve", seed, 1.0, False,
                          manifest=tiny_manifest, require_chip=False,
                          scratch=str(tmp_path_factory.mktemp("bench")),
                          keep=keep)
        out[seed] = (result, keep["ctx"], keep["out"])
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_served_what_the_benchmarks_reference_computes(
        runs, seed):
    result, ctx, out = runs[seed]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 20
    assert set(result["compared"]) == {"answers_short", "logit_gap_max"}
    assert set(result["metrics"]) == {"itl_p50_ms", "setup_s"}
    assert out["counters"]["prefill_requests"] == 20
    assert any(len(s["tokens"]) > len(s["prompt"]) for s in out["served"])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control, times", [
    ("fp8", 3), ("qk_whole", 1), ("no_conv_history", 3)])
def test_a_lower_precision_and_each_planted_fault_are_not_correct(
        runs, tiny_manifest, seed, control, times):
    """fp8: every matmul operand rounded (1.3 to 2.0 against the limit
    0.3); qk_whole: the q/k norms over the whole projection, not a head
    at a time; no_conv_history: each position convolves itself alone,
    as a decode step that lost its tail would. The token each puts
    first lies further under the float32 reference's best than the
    limit allows; the configuration's own precision, emulated alike,
    passes."""
    _, ctx, out = runs[seed]
    limit = tiny_manifest.limits("tiny-lfm2.serve")["logit_gap_max"]
    driver = load_py("drivers", "serve_open_loop")
    ref = ctx.reference
    w = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, seed))
    gap, n = driver.served_gaps(ctx, w, out["served"], control=control)
    assert n > 100 and gap > times * limit, (gap, limit)
    own, _ = driver.served_gaps(ctx, w, out["served"], control="bf16")
    assert own <= limit


@pytest.mark.parametrize("seed", SEEDS)
def test_the_comparison_sees_experts_chosen_without_their_bias(runs, seed):
    """The experts chosen by p alone, not p + b: the routed experts'
    down projection is drawn small (the reference's ROUTED_DOWN, so that
    an expert a rounding turns over moves a token by little) and the
    bias narrow (so that the load stays near balanced), and with them
    the whole choice's fault reads 0.11 to 0.16 here, under the limit;
    it lies more than three times as far from the reference as the
    program's own served tokens do. At the cell's widths, on a v5e, it
    read 0.289 and 0.340 against the cell's limit of 0.25, where the
    program read 0.079 to 0.150 over 16 seeds."""
    _, ctx, out = runs[seed]
    driver = load_py("drivers", "serve_open_loop")
    ref = ctx.reference
    w = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, seed))
    gap, _ = driver.served_gaps(ctx, w, out["served"],
                                control="unbiased_choice")
    own, _ = driver.served_gaps(ctx, w, out["served"])
    assert gap > 3 * own, (gap, own)


# ------------------------------------------- the program in float32
def _f32_cfg(manifest):
    return dict(manifest.config("tiny-lfm2"),
                serving={"weight_dtype": "float32",
                         "cache_dtype": "float32"})


#: float32 weights and cache on both sides: what is left is the order of
#: float sums (some 1e-6 of a logit); a bfloat16 program (below) lies
#: 1e-2 and more away, far outside
TOL = 2e-4


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        tiny_manifest, seed):
    """Right-padded prompts of unequal lengths (1, 3, 6, 9: the tail is
    taken at each row's last real token, and at positions 0 and 1 holds
    zeros) prefilled into 4 of 12 slots, then one decode step a token
    over all 12 slots (more slots than the 8 experts: the sorted
    grouped-product path), idle slots riding at position 0: every
    log-prob is the float32 reference's full forward's."""
    cfg = _f32_cfg(tiny_manifest)
    ref = load_py("reference", cfg["reference"])
    adapter = load_py("models", cfg["model"]).Adapter(cfg, {})
    model = adapter.model
    w = ref.init_weights(cfg, seed)
    params = adapter.served_params(w)
    lengths = np.array([1, 3, 6, 9])
    steps, t_all = 5, 14
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (4, t_all), 1, cfg["vocab_size"] + 1))
    slots = np.array([7, 2, 11, 0])
    with jax.default_matmul_precision("highest"):
        want = jax.nn.log_softmax(ref.logits_at(
            cfg, w, jnp.asarray(toks),
            jnp.broadcast_to(jnp.arange(t_all), (4, t_all))), axis=-1)
        full = model.apply(params, jnp.asarray(toks), None)
        cache = model.init_cache(12, 32)
        padded = np.where(np.arange(9)[None] < lengths[:, None],
                          toks[:, :9], 1)
        got, cache = model.apply_prefill(params, jnp.asarray(padded), cache,
                                         jnp.asarray(slots),
                                         jnp.asarray(lengths))
        rows = np.arange(4)
        np.testing.assert_allclose(got, want[rows, lengths - 1], atol=TOL)
        np.testing.assert_allclose(full, want, atol=TOL)
        pos = lengths.copy()
        for _ in range(steps):
            tokens = np.ones(12, np.int32)
            positions = np.zeros(12, np.int32)
            tokens[slots], positions[slots] = toks[rows, pos], pos
            logp, cache = model.apply_step(params, jnp.asarray(tokens), cache,
                                           jnp.asarray(positions))
            np.testing.assert_allclose(logp[slots], want[rows, pos],
                                       atol=TOL)
            pos = pos + 1
    assert model.cache_stats(cache)["conv_slot_steps"] == 4 * steps
    # the same prompts in the served precision lie outside the tolerance
    half = dict(cfg, serving={"weight_dtype": "bfloat16",
                              "cache_dtype": "bfloat16"})
    served = load_py("models", cfg["model"]).Adapter(half, {})
    low = served.model.apply(served.served_params(
        ref.init_weights(half, seed)), jnp.asarray(toks), None)
    assert float(jnp.max(jnp.abs(low - want))) > 20 * TOL


def test_weights_are_kept_as_served_and_drawn_from_the_seed(tiny_manifest):
    cfg = tiny_manifest.config("tiny-lfm2")
    ref = load_py("reference", cfg["reference"])
    w, again, other = (ref.init_weights(cfg, s) for s in (2 ** 31 + 5,
                                                          2 ** 31 + 5, 6))
    assert w["l0.win"].dtype == "bfloat16" and w["l2.wq"].dtype == "bfloat16"
    for name in ("l2.router", "l2.router_bias", "l0.ln1.g", "l2.qn.g",
                 "norm.g"):
        assert w[name].dtype == "float32", name
    assert "head" not in w                       # tied to the embedding
    assert "l0.router" not in w and "l1.router" not in w   # dense layers
    assert w["l0.win"].shape == (64, 192) and w["l0.conv"].shape == (3, 64)
    assert w["l2.qn.g"].shape == w["l2.kn.g"].shape == (8,)
    assert w["l2.wk"].shape == (64, 16) and w["l3.wg"].shape == (8, 64, 32)
    assert bool((w["l2.wq"] == again["l2.wq"]).all())
    assert not bool((w["l2.wq"] == other["l2.wq"]).all())
    assert ref.served_weights(cfg, w) is w
    # the bias is a quarter of the scores' spread: choosing by p + b is
    # another choice than by p for a share of the tokens (a third here,
    # at 8 experts; two in three at the cell's 32)
    assert 0.03 < float(w["l2.router_bias"].std()) < 0.08
    u = jax.random.normal(jax.random.PRNGKey(0), (256, 64))
    p = jax.nn.sigmoid(u @ w["l2.router"])
    by_p = jax.lax.top_k(p, 4)[1]
    by_pb = jax.lax.top_k(p + w["l2.router_bias"], 4)[1]
    differ = jnp.any(jnp.sort(by_p, -1) != jnp.sort(by_pb, -1), axis=-1)
    assert float(differ.mean()) > 0.2
    adapter = load_py("models", cfg["model"]).Adapter(cfg, {})
    tree = adapter.served_params(w)
    assert tree["block0"]["attn"]["in_proj"] is w["l0.win"]
    assert tree["block2"]["attn"]["q_norm"] is w["l2.qn.g"]
    assert tree["block3"]["experts"]["wd"] is w["l3.wd"]
    assert tree["block3"]["router_bias"] is w["l3.router_bias"]
    assert tree["block1"]["ffn"]["wd"] is w["l1.wd"]
    blocks = adapter.model.blocks
    assert [b.keeps for b in blocks] == [
        ("conv_tail",) if k == "conv" else ("k", "v")
        for k in cfg["layer_types"][:8]]
    assert blocks[0].ffn is not None and blocks[1].ffn is not None
    assert all(b.experts.gate == "silu" and b.experts.scoring == "sigmoid"
               and b.experts.scale == 1.0 and not b.route_early
               for b in blocks[2:])
    assert blocks[2].attn.qk_norm_per_head and adapter.model.tie_head
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in leaves) == sum(x.size for x in w.values())


def test_counts_match_the_references_jaxpr(tiny_manifest):
    """Both from the same shapes: the count's matmuls with EVERY expert
    and attention's square against what the plain reference's jaxpr
    multiplies (it applies all experts to every token)."""
    cfg = tiny_manifest.config("tiny-lfm2")
    ref = load_py("reference", cfg["reference"])
    counts = load_py("counts", cfg["counts"])
    t, e = 32, cfg["hidden_size"]
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    assert counts.parameters(cfg) == sum(x.size for x in w.values())
    toks = jax.ShapeDtypeStruct((1, t), "int32")
    pos = jax.ShapeDtypeStruct((1, t), "int32")
    traced = jaxpr_flops(jax.make_jaxpr(
        lambda w, a, b: ref.logits_at(cfg, w, a, b))(w, toks, pos))
    every = counts.matmul_params(cfg, cfg["num_experts"])
    matmuls = 2 * t * (every + e * cfg["vocab_size"])
    square = 2 * 8 * (8 + 8) * 2 * t * t        # two attention layers
    assert matmuls + square <= traced < 1.1 * (matmuls + square)
    prompt, out = 24, 8
    attn = prompt * (prompt + 1) // 2 + sum(range(prompt + 1, prompt + out))
    c = {"prompt_tokens": prompt, "tokens_out": out,
         "attention_positions": attn}
    active = counts.matmul_params(cfg, 4)
    assert counts.serve_flops(cfg, c) == pytest.approx(
        2 * (prompt + out) * active + 2 * out * e * cfg["vocab_size"]
        + 2 * 8 * (8 + 8) * 2 * attn)
    p = counts.layer_params(cfg)
    assert active == 6 * p["conv"] + 2 * p["attention"] \
        + 2 * p["dense_ffn"] + 6 * (p["router"] + 4 * p["expert"])
    assert counts.cache_bytes_per_position(cfg, 2) == 2 * 2 * 2 * 8 * 2


def test_twelve_layers_at_the_published_widths_are_3_93_b_parameters():
    cfg = Manifest().config("lfm2-8b-a1b")
    counts = load_py("counts", "lfm2-8b-a1b")
    assert counts.parameters(cfg) == 3_928_728_256
    assert counts.parameters(dict(cfg, num_hidden_layers=24)) == \
        8_339_930_560
    p = counts.layer_params(cfg)
    assert p["conv"] + p["conv_taps"] == 16_783_360
    assert p["attention"] + p["qk_norms"] == 10_485_888
    assert p["dense_ffn"] == 44_040_192
    assert 32 * p["expert"] + p["router"] + p["router_other"] == 352_387_104
    ref = load_py("reference", cfg["reference"])
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    assert counts.parameters(cfg) == sum(x.size for x in w.values())
    # a decode step at 128 slots reads every expert: 7.86 GB in bf16
    assert counts.decode_weight_bytes(cfg, 2) / 1e9 == \
        pytest.approx(7.86, abs=0.01)
    assert counts.cache_bytes_per_position(cfg, 2) == 3 * 2048


def test_the_slots_cache_holds_what_the_configuration_counts():
    """3.22 GB of K/V over 3 attention layers, two heads of 64 a row of
    128 lanes (nothing padded), and 9 tails of 2 rows: 9.4 MB."""
    m = Manifest()
    cfg = m.config("lfm2-8b-a1b")
    mix = m.traffic("wide-chat-0.8")["engine"]
    model = load_py("models", cfg["model"]).Adapter(cfg, {}).model
    cache = jax.eval_shape(
        lambda: model.init_cache(mix["slots"], mix["max_len"]))
    kv = [c for c in cache["k"] + cache["v"] if c is not None]
    assert [c.shape for c in kv] == [(128, 4, 4096, 128)] * 6
    tails = [c for c in cache["conv_tail"] if c is not None]
    assert [c.shape for c in tails] == [(128, 2, 2048)] * 9
    assert all(c.dtype == "bfloat16" for c in kv + tails)
    for other in ("state", "tail", "latent", "k_pe"):
        assert cache[other] == [None] * 12
    held = sum(c.size * 2 for c in kv)
    counts = load_py("counts", "lfm2-8b-a1b")
    assert held == 128 * 4096 * counts.cache_bytes_per_position(cfg, 2)
    assert held / 1e9 == pytest.approx(3.22, abs=0.005)
    assert sum(c.size * 2 for c in tails) / 1e6 == pytest.approx(9.44,
                                                                 abs=0.01)


#: LiquidAI/LFM2-8B-A1B's `config.json` as published, the keys that
#: say something about its shape
PUBLISHED = {
    "conv_L_cache": 3,
    "conv_bias": False,
    "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv",
        "conv",
        "full_attention",
        "conv",
        "conv",
        "conv",
        "full_attention",
        "conv",
        "conv",
        "conv",
        "full_attention",
        "conv",
        "conv",
        "conv",
        "full_attention",
        "conv",
        "conv",
        "conv",
        "full_attention",
        "conv",
        "conv",
        "full_attention",
        "conv",
        "conv"
    ],
    "max_position_embeddings": 128000,
    "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_dense_layers": 2,
    "num_experts": 32,
    "num_experts_per_tok": 4,
    "num_hidden_layers": 24,
    "num_key_value_heads": 8,
    "rope_theta": 1000000,
    "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "vocab_size": 65536
}


def test_the_configuration_keeps_every_published_number():
    cfg = Manifest().config("lfm2-8b-a1b")
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 12 \
        and cfg["published"]["num_hidden_layers"] == 24
    assert cfg["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert (cfg["hidden_size"], cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["vocab_size"],
            cfg["moe_intermediate_size"], cfg["intermediate_size"]) == \
        (2048, 32, 4, 65536, 1792, 7168)
    assert cfg["serving"] == {"weight_dtype": "bfloat16",
                              "cache_dtype": "bfloat16"}
    assert "two pipeline stages" in cfg["deployment"]
    assert {"tied_head", "rotary", "weight_sum_epsilon", "weights",
            "tails", "router_dtype", "stream"} <= set(cfg["assumed"])


def test_the_mix_is_the_cells_wide_chat():
    m = Manifest()
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("lfm2-8b-a1b", "wide-chat-0.8", 1)
    mix = m.traffic("wide-chat-0.8")
    assert mix["driver"] == "serve_open_loop" and mix["loop"] == "open"
    assert mix["arrivals"] == {"kind": "exponential"}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 1.0, "min": 16, "max": 2048}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 32, "max": 1024}
    assert mix["shared_prefix_len"] == 0 and mix["sampling"] == "greedy"
    e = mix["engine"]
    assert (e["slots"], e["max_len"], e["prefill_batch"]) == (128, 4096, 4)
    assert e["seq_buckets"] == [64, 128, 256, 512, 1024, 2048]
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert mix["knee_found"] and mix["check_requests"] == 8
    assert set(m.limits(CELL)) == {"answers_short", "logit_gap_max"}
    assert m.limits(CELL)["answers_short"] == 0


#: the shared per-layer lists the cell is appended to: not
#: `decode_experts_ms`, which reads the scope alone and so misses XLA's
#: `ragged-dot` kernels (`decode_experts_ms.lfm2` reads them)
SHARED = ("decode_step_ms", "decode_kv_write_ms", "decode_attention_ms",
          "decode_hbm_share", "engine_step_ms_p50", "engine_step_ms_p95",
          "decode_dispatch_ms_p50", "decode_fetch_ms_p50",
          "decode_deliver_ms_p50", "decode_depth_share",
          "compiles_in_window.serve", "gen_late_ms_p99")
#: the metrics that list this cell alone
OWN = ("decode_conv_ms", "serve_mfu.lfm2", "decode_experts_ms.lfm2")
CONFIGS_AT_ARRIVAL = ["resnet50", "neox-3.6b", "smallthinker-21b",
                      "olmo-hybrid-7b", "kanana-2-30b-a3b", "lfm2-8b-a1b"]
CELLS_AT_ARRIVAL = ["resnet50.train-b128", "neox-3.6b.serve-chat",
                    "neox-3.6b.train-t2048", "smallthinker-21b.serve-mixed",
                    "olmo-hybrid-7b.serve-decode",
                    "kanana-2-30b-a3b.serve-docs", CELL]


def test_the_cell_is_appended_and_reports_its_layers_metrics():
    m = Manifest()
    configs = [c["name"] for c in m.doc["configs"]]
    cells = [w["name"] for w in m.doc["workloads"]]
    assert configs[:len(CONFIGS_AT_ARRIVAL)] == CONFIGS_AT_ARRIVAL
    assert cells[:len(CELLS_AT_ARRIVAL)] == CELLS_AT_ARRIVAL
    names = {x["name"] for x in m.doc["end_to_end"]}
    assert {x["name"] for x in m.metrics("end_to_end", CELL, names)} == \
        {"itl_p50_ms", "setup_s"}
    mine = {x["name"] for x in m.metrics("per_layer", CELL,
                                         {"itl_p50_ms", "setup_s"})}
    assert mine == set(SHARED) | set(OWN)
    for x in m.doc["per_layer"]:
        if x["name"] in SHARED:
            assert x["workloads"][-1] == CELL
        if x["name"] in OWN:
            assert x["workloads"] == [CELL] and x["moves"] == "itl_p50_ms"
    conv = m.metric_file("decode_conv_ms")
    assert conv["reader"] == "scope_device_ms"
    assert conv["args"] == {"modules": ["jit__decode_fn"],
                            "scopes": ["short conv"]}
    experts = m.metric_file("decode_experts_ms.lfm2")
    assert experts["reader"] == "scope_device_ms_ragged"
    assert experts["args"] == {"modules": ["jit__decode_fn"],
                               "scopes": ["moe experts"],
                               "ragged_dot_scope": "moe experts"}
    assert CELL not in next(x for x in m.doc["per_layer"]
                            if x["name"] == "decode_experts_ms")["workloads"]
    mfu = m.metric_file("serve_mfu.lfm2")
    assert (mfu["reader"], mfu["args"]) == ("mfu", {"kind": "serve"})


def _ragged_decode_ops(at, ragged_path):
    """One decode run from `at`: the router's top-k under `moe experts`,
    two grouped products XLA wrote for `ragged_dot` (carrying
    `ragged_path`), and attention."""
    experts = "jit(_decode_fn)/moe experts/top_k"
    attention = "jit(_decode_fn)/full attention/dot_general"
    return [("%fusion.3 = f32[8] fusion()", at + 1, at + 5, experts),
            ("%ragged-dot-none.4 = bf16[512,1792] custom-call()",
             at + 10, at + 40, ragged_path),
            ("%ragged-dot-none.5 = bf16[512,2048] custom-call()",
             at + 41, at + 61, ragged_path),
            ("%fusion.1 = f32[8] fusion()", at + 62, at + 72, attention)]


@pytest.mark.parametrize("ragged_path", ["ragged-dot-none", None])
def test_the_experts_metric_reads_xlas_grouped_products(tmp_path,
                                                        ragged_path):
    """`decode_experts_ms.lfm2` counts the `ragged-dot` kernels, whether
    they carry XLA's own name or none, under `moe experts`, where the
    accepted `decode_experts_ms` reads the top-k alone; attention stays
    out of both, and a program with no such kernel reads as before."""
    from test_scope_reader import _trace
    runs = [("jit__decode_fn(7)", 0, 100), ("jit__decode_fn(7)", 200, 300),
            ("jit__decode_fn(7)", 400, 500)]
    ops = [op for at in (0, 200, 400)
           for op in _ragged_decode_ops(at, ragged_path)]
    trace = _trace(tmp_path, runs, ops)
    assert _read("decode_experts_ms.lfm2", trace, None, None) == \
        pytest.approx(0.054)
    assert _read("decode_experts_ms", trace, None, None) == \
        pytest.approx(0.004)
    assert _read("decode_attention_ms", trace, None, None) == \
        pytest.approx(0.010)
    plain = tmp_path / "plain"
    plain.mkdir()
    no_ragged = [op for op in ops if not op[0].startswith("%ragged")]
    trace = _trace(plain, runs, no_ragged)
    assert _read("decode_experts_ms.lfm2", trace, None, None) == \
        pytest.approx(0.004)
    assert load_py("readers", "scope_device_ms_ragged").read(
        types.SimpleNamespace(trace_file=lambda: None), None, None,
        Manifest().metric_file("decode_experts_ms.lfm2")["args"]) is None


@pytest.fixture(scope="module")
def ctx():
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    return types.SimpleNamespace(cfg=Manifest().config("lfm2-8b-a1b"),
                                 chips=1, peaks=peaks["TPU v5 lite"])


def _read(name, ctx, out, reduced):
    spec = Manifest().metric_file(name)
    return load_py("readers", spec["reader"]).read(ctx, out, reduced,
                                                   spec.get("args", {}))


def test_the_whole_steps_share_reads_100_at_the_peak_and_no_more(ctx):
    counts = load_py("counts", "lfm2-8b-a1b")
    c = {"prompt_tokens": 120000, "tokens_out": 90000,
         "attention_positions": 400e6}
    flops = counts.serve_flops(ctx.cfg, c)
    at_peak = {"counters": c, "window_s": flops / ctx.peaks["bf16_flops"]}
    assert _read("serve_mfu.lfm2", ctx, at_peak, None) == \
        pytest.approx(100.0)
    assert 0 < _read("serve_mfu.lfm2", ctx,
                     {"counters": c, "window_s": 30.0}, None) < 30
    assert flops == pytest.approx(
        2 * 210000 * counts.matmul_params(ctx.cfg, 4)
        + 2 * 90000 * 2048 * 65536 + 2 * 32 * 128 * 3 * 400e6)
    nothing = {"counters": dict(c, prompt_tokens=0, tokens_out=0,
                                attention_positions=0), "window_s": 30.0}
    assert _read("serve_mfu.lfm2", ctx, nothing, None) is None


def test_the_decode_steps_share_counts_every_expert(ctx):
    """A step that took just the time its counted bytes need reads 100:
    every expert and the live K/V prefix (the 9.4 MB of tails are left
    out by the accepted reader: under 0.2% of the step)."""
    counts = load_py("counts", "lfm2-8b-a1b")
    steps, positions = 1000, 1000 * 100 * 600
    out = {"counters": {"decode_steps": steps,
                        "cache_positions_read": positions}}
    least = (counts.decode_weight_bytes(ctx.cfg, 2)
             + 6144 * positions / steps) / ctx.peaks["hbm_bytes_per_s"]
    at_pace = {"modules": {"jit__decode_fn": [steps, steps * least]}}
    assert _read("decode_hbm_share", ctx, out, at_pace) == \
        pytest.approx(100.0)
    tails = 9 * 128 * 2 * 2048 * 2
    assert tails / (least * ctx.peaks["hbm_bytes_per_s"]) < 0.002
