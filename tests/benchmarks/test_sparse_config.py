"""The `smallthinker-21b` configuration's benchmark files at a size a
test run can hold (benchmarks/testdata/tiny-sparse: the same block at
hidden 64, 4 query heads over 2 K/V heads of 16, 8 experts of 32 with 3
active, window 8, [full, window x3]): the benchmark's own reference
against the program through the harness, its counts against the
reference's jaxpr, the named-kernel reader on hand-made `custom-call`
text, and the controls that have to come out as not correct."""

import os
import types

import jax
import pytest

from benchmarks.files import HERE, Manifest, load_json, load_py
from bigdl_tpu.observability.costs import jaxpr_flops

SEEDS = (5, 6, 7)


@pytest.fixture(scope="module")
def sparse_manifest():
    base = os.path.join(HERE, "testdata", "tiny-sparse")
    return Manifest(os.path.join(base, "BENCHMARK.json"), base)


@pytest.fixture(scope="module")
def runs(sparse_manifest, tmp_path_factory):
    """One run of the tiny serving cell a seed, kept for the controls."""
    from benchmarks.harness import run_cell
    out = {}
    for seed in SEEDS:
        keep = {}
        result = run_cell("tiny-sparse.serve", seed, 1.0, False,
                          manifest=sparse_manifest, require_chip=False,
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
    assert set(result["metrics"]) == {"itl_p50_ms", "itl_p99_ms", "setup_s"}
    # prompts longer than the window were served, and decoded past it
    assert max(len(s["prompt"]) for s in out["served"]) \
        > ctx.cfg["sliding_window_size"]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", ["fp8", "drop_expert", "no_window"])
def test_a_lower_precision_and_each_planted_fault_are_not_correct(
        runs, sparse_manifest, seed, control):
    """fp8: every matmul operand rounded (the float32 router's to
    bfloat16); drop_expert: each token's last expert left out of the
    sum; no_window: the window ignored. The token each puts first lies
    further under the float32 reference's best than the limit allows."""
    _, ctx, out = runs[seed]
    limit = sparse_manifest.limits("tiny-sparse.serve")["logit_gap_max"]
    driver = load_py("drivers", "serve_open_loop")
    ref = ctx.reference
    w = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, seed))
    gap, n = driver.served_gaps(ctx, w, out["served"], control=control)
    assert n > 100 and gap > limit, (gap, limit)
    # the configuration's own precision, emulated the same way, passes
    own, _ = driver.served_gaps(ctx, w, out["served"], control="bf16")
    assert own <= limit


def test_weights_are_kept_as_served_and_drawn_from_the_seed(sparse_manifest):
    cfg = sparse_manifest.config("tiny-sparse")
    ref = load_py("reference", cfg["reference"])
    w, again, other = (ref.init_weights(cfg, s) for s in (2 ** 31 + 5,
                                                          2 ** 31 + 5, 6))
    assert w["l0.wg"].dtype == "bfloat16" and w["l0.router"].dtype == "float32"
    assert w["l0.ln1.g"].dtype == "float32" and w["embed"].dtype == "bfloat16"
    assert bool((w["l1.wq"] == again["l1.wq"]).all())
    assert not bool((w["l1.wq"] == other["l1.wq"]).all())
    assert ref.served_weights(cfg, w) is w
    adapter = load_py("models", cfg["model"]).Adapter(cfg, {})
    tree = adapter.served_params(w)
    assert tree["block2"]["experts"]["wd"] is w["l2.wd"]
    assert [b.attn.window for b in adapter.model.blocks] == [None, 8, 8, 8]
    assert [b.attn.rope_base for b in adapter.model.blocks] == \
        [None, 1.5e6, 1.5e6, 1.5e6]


def test_counts_match_the_references_jaxpr(sparse_manifest):
    """The plain reference applies ALL experts to every token and the
    whole T x T square; the count takes top_k experts and the positions
    a layer's kind attends to. Both from the same shapes."""
    cfg = dict(sparse_manifest.config("tiny-sparse"), vocab_size=512)
    ref = load_py("reference", cfg["reference"])
    counts = load_py("counts", cfg["counts"])
    t, e = 32, cfg["hidden_size"]
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    toks = jax.ShapeDtypeStruct((1, t), "int32")
    pos = jax.ShapeDtypeStruct((1, t), "int32")
    traced = jaxpr_flops(jax.make_jaxpr(
        lambda w, a, b: ref.logits_at(cfg, w, a, b))(w, toks, pos))
    p = counts.layer_params(cfg)
    layers, n = cfg["num_hidden_layers"], cfg["moe_num_primary_experts"]
    matmuls = 2 * t * (layers * (p["attention"] + p["router"]
                                 + n * p["expert"]) + e * cfg["vocab_size"])
    square = layers * 4 * cfg["num_attention_heads"] * cfg["head_dim"] * t * t
    assert matmuls + square < traced < 1.25 * (matmuls + square)
    # serving: a prompt of 24 tokens and 8 generated ones; all in reach
    # of a full layer, min(position, window) of a window layer
    prompt, out = 24, 8
    attn = prompt * (prompt + 1) // 2 + sum(range(prompt + 1, prompt + out))
    c = {"prompt_tokens": prompt, "tokens_out": out,
         "attention_positions": attn}
    k = cfg["moe_num_active_primary_experts"]
    dense = 2 * (prompt + out) * layers * (p["attention"] + p["router"]
                                           + k * p["expert"])
    per_position = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    share = cfg["sliding_window_size"] / cfg["max_position_embeddings"]
    assert counts.serve_flops(cfg, c) == pytest.approx(
        dense + 2 * out * e * cfg["vocab_size"]
        + per_position * attn * (1 + 3 * share))
    # never more than the true count of a window layer's positions
    true_window = sum(min(q + 1, cfg["sliding_window_size"])
                      for q in range(prompt + out - 1))
    assert attn * share <= true_window


def test_eight_layers_at_the_published_widths_are_3_97_b_parameters():
    cfg = Manifest().config("smallthinker-21b")
    counts = load_py("counts", "smallthinker-21b")
    assert counts.parameters(cfg) / 1e9 == pytest.approx(3.967, abs=0.002)
    p = counts.layer_params(cfg)
    assert (p["attention"], p["router"], p["expert"]) == \
        (20971520, 163840, 5898240)
    assert dict(cfg, num_hidden_layers=52) and counts.parameters(
        dict(cfg, num_hidden_layers=52)) / 1e9 == pytest.approx(21.5, abs=0.1)
    # six experts, the router and attention: 56.5 M matmul parameters a
    # token and layer of the 398.6 M a layer holds
    assert counts.active_matmul_params(cfg) == 20971520 + 163840 + 6 * 5898240


def test_the_configuration_keeps_every_published_number():
    cfg = Manifest().config("smallthinker-21b")
    row = None
    import json
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    for line in open(catalog):
        r = json.loads(line)
        if r["name"] == "SmallThinker-21BA3B-Instruct":
            row = r
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 8 \
        and cfg["published"]["num_hidden_layers"] == 52
    assert cfg["source"] == row["source_url"]
    assert cfg["sliding_window_layout"][:8] == [0, 1, 1, 1, 0, 1, 1, 1]


# ------------------------------------------------- the named-kernel reader
@pytest.fixture(scope="module")
def ctx():
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]["TPU v5 lite"]
    return types.SimpleNamespace(cfg=Manifest().config("smallthinker-21b"),
                                 chips=1, peaks=peaks)


def _call(name, t, group=7, heads=8):
    q = f"bf16[{heads},{group},{t},128]{{3,2,1,0}}"
    kv = f"bf16[{heads},{t},128]{{2,1,0}}"
    return (f"%{name} = {q} custom-call({q} %q, {kv} %k, {kv} %v), "
            "custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{{q}, {kv}, {kv}}}, "
            "frontend_attributes={kernel_metadata={}}")


def _mha_call(t):
    q = f"bf16[44,{t},128]{{2,1,0}}"
    return (f"%flash_fwd.2 = ({q}, f32[44,1,{t}]{{2,1,0}}) custom-call("
            f"{q} %q, {q} %k, {q} %v), custom_call_target=\"tpu_custom_call\""
            f", operand_layout_constraints={{{q}, {q}, {q}}}, "
            "frontend_attributes={kernel_metadata={}}")


def _read(ctx, reduced):
    spec = Manifest().metric_file("gqa_flash_roofline.serve")
    return load_py("readers", spec["reader"]).read(ctx, None, reduced,
                                                   spec["args"])


def test_named_kernel_reader_counts_windowed_and_full_calls_by_name(ctx):
    count = load_py("counts", "gqa_flash_attention")
    t, d = 8192, 128
    full = 2 * 2 * d * 8 * 7 * (t * (t + 1) // 2)
    windowed = 2 * 2 * d * 8 * 7 * (4096 * 4097 // 2 + (t - 4096) * 4096)
    assert count.pairs(t, None) == t * (t + 1) // 2
    assert count.pairs(100, 4096) == 100 * 101 // 2
    texts = [_call("flash_fwd_gqa.1", t), _call("flash_fwd_window.3", t)]
    nbytes = 2 * (2 * 8 * 7 * t * d + 2 * 8 * t * d)  # q, out; k, v once
    for text, flops in zip(texts, (full, windowed)):
        from benchmarks.trace.reduce import shapes
        got = count.work(ctx.cfg, text.split(" = ")[0][1:].split(".")[0],
                         shapes(text.split(" custom-call(")[0]),
                         shapes(text.split(" custom-call(")[1]
                                .split("custom_call_target")[0]))
        assert got == (flops, nbytes)
    least = (full + windowed) / 197e12     # both are compute-bound
    reduced = {"kernels": [(texts[0], full / 197e12 * 2),
                           (texts[1], windowed / 197e12 * 2),
                           (_mha_call(1024), 1.0)]}   # left out: MHA flash
    assert _read(ctx, reduced) == pytest.approx(50.0)
    # the share cannot pass 100: a call that took the least time reads 100
    at_peak = {"kernels": [(texts[0], full / 197e12),
                           (texts[1], windowed / 197e12)]}
    assert _read(ctx, at_peak) == pytest.approx(100.0)
    assert least > 0


def test_named_kernel_reader_is_silent_where_there_is_nothing_to_read(ctx):
    assert _read(ctx, {"kernels": []}) is None
    assert _read(ctx, {"kernels": [(_mha_call(2048), 0.5)]}) is None
    # the same shapes under another kernel's name are not taken
    assert _read(ctx, {"kernels": [(_call("decode_attention.1", 4096),
                                    0.5)]}) is None
    # and the accepted flash reader's metric file is not this one's
    reader = load_py("readers", "named_kernel_roofline")
    assert reader.kernel_name("%flash_fwd_window.12 = bf16[1]") == \
        "flash_fwd_window"
    assert reader.kernel_name("%flash_fwd_gqa = bf16[1]") == "flash_fwd_gqa"
    assert reader.kernel_name("%fusion.3 = bf16[1]") == "fusion"


def test_the_mix_is_the_one_the_issue_names():
    mix = Manifest().traffic("mixed-open-0.8")
    assert mix["driver"] == "serve_open_loop"
    assert mix["arrivals"] == {"kind": "exponential"}
    # the issue's one fallback (8.d): prompts up to 8192, buckets to match
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                 "sigma": 1.2, "min": 64, "max": 8192}
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 512}
    e = mix["engine"]
    assert (e["slots"], e["max_len"], e["prefill_batch"]) == (32, 16384, 2)
    assert e["seq_buckets"] == [128, 256, 512, 1024, 2048, 4096, 8192]
    assert "fallback" in mix
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert (mix["check_requests"], mix["check_block"]) == (8, 1)
