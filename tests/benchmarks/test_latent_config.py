"""The `kanana-2-30b-a3b` configuration's benchmark files at a size a
test run can hold (benchmarks/testdata/tiny-latent: the same block at
hidden 64, 4 heads of nope 16 / rope 8 / value 16, latent 32, 16 experts
of 32 with top 3, two shared experts of 32, dense FFN 128, [dense,
experts x5]): the benchmark's own reference against the program through
the harness with `prefill_batch` 1, its counts against the reference's
shapes, the control and the planted faults that have to come out as not
correct, and the new count files under the readers so that no share of
them can pass 100."""

import json
import os
import types

import jax
import pytest

from benchmarks.files import HERE, Manifest, load_json, load_py
from benchmarks.trace.reduce import shapes
from bigdl_tpu.observability.costs import jaxpr_flops

SEEDS = (5, 6, 7)
CELL = "kanana-2-30b-a3b.serve-docs"


@pytest.fixture(scope="module")
def latent_manifest():
    base = os.path.join(HERE, "testdata", "tiny-latent")
    return Manifest(os.path.join(base, "BENCHMARK.json"), base)


@pytest.fixture(scope="module")
def runs(latent_manifest, tmp_path_factory):
    """One run of the tiny serving cell a seed, kept for the controls."""
    from benchmarks.harness import run_cell
    out = {}
    for seed in SEEDS:
        keep = {}
        result = run_cell("tiny-latent.serve", seed, 1.0, False,
                          manifest=latent_manifest, require_chip=False,
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
    # one prompt a prefill: as many prefills as requests
    assert out["counters"]["prefill_batches"] == \
        out["counters"]["prefill_requests"] == 20
    assert any(len(s["tokens"]) > len(s["prompt"]) for s in out["served"])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control, times", [
    ("fp8", 2), ("no_shared", 3), ("latent_unnormed", 3),
    ("biased_weights", 3)])
def test_a_lower_precision_and_each_planted_fault_are_not_correct(
        runs, latent_manifest, seed, control, times):
    """fp8: every matmul operand rounded; no_shared: the shared expert
    left out; latent_unnormed: the latent used without its RMSNorm;
    biased_weights: the experts' weights taken from score + bias. The
    token each puts first lies further under the float32 reference's
    best than the limit allows (fp8 by the least, 0.30 to 0.34 against
    0.1; the biased weights divide by a sum near zero: the bias's mean
    is drawn for that)."""
    _, ctx, out = runs[seed]
    limit = latent_manifest.limits("tiny-latent.serve")["logit_gap_max"]
    driver = load_py("drivers", "serve_open_loop")
    ref = ctx.reference
    w = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, seed))
    gap, n = driver.served_gaps(ctx, w, out["served"], control=control)
    assert n > 100 and gap > times * limit, (gap, limit)
    # the configuration's own precision, emulated the same way, passes
    own, _ = driver.served_gaps(ctx, w, out["served"], control="bf16")
    assert own <= limit


def test_weights_are_kept_as_served_and_drawn_from_the_seed(latent_manifest):
    cfg = latent_manifest.config("tiny-latent")
    ref = load_py("reference", cfg["reference"])
    w, again, other = (ref.init_weights(cfg, s) for s in (2 ** 31 + 5,
                                                          2 ** 31 + 5, 6))
    assert w["l0.wq"].dtype == "bfloat16" and w["l1.wsd"].dtype == "bfloat16"
    for name in ("l1.router", "l1.router_bias", "l0.ln1.g", "l0.kvn.g",
                 "norm.g"):
        assert w[name].dtype == "float32", name
    assert "l0.router" not in w and "l0.wsg" not in w   # the dense layer
    assert w["l0.wg"].shape == (64, 128) and w["l1.wg"].shape == (16, 64, 32)
    assert w["l1.wsg"].shape == (64, 64) and w["l1.wkvb"].shape == (32, 128)
    assert bool((w["l1.wq"] == again["l1.wq"]).all())
    assert not bool((w["l1.wq"] == other["l1.wq"]).all())
    assert ref.served_weights(cfg, w) is w
    assert 0.3 < float(w["l1.router_bias"].std()) < 0.8
    assert -2.0 < float(w["l1.router_bias"].mean()) < -1.2
    adapter = load_py("models", cfg["model"]).Adapter(cfg, {})
    tree = adapter.served_params(w)
    assert tree["block2"]["experts"]["wd"] is w["l2.wd"]
    assert tree["block2"]["shared"]["wd"] is w["l2.wsd"]
    assert tree["block2"]["router_bias"] is w["l2.router_bias"]
    assert tree["block0"]["ffn"]["wd"] is w["l0.wd"]
    assert tree["block3"]["attn"]["kv_norm"] is w["l3.kvn.g"]
    # kv_b_proj's columns, head by head: 16 of key then 16 of value
    per_head = w["l1.wkvb"].reshape(32, 4, 32)
    attn = tree["block1"]["attn"]
    assert attn["wuk"].shape == attn["wuv"].shape == (32, 64)
    assert bool((attn["wuk"].reshape(32, 4, 16) == per_head[..., :16]).all())
    assert bool((attn["wuv"].reshape(32, 4, 16) == per_head[..., 16:]).all())
    blocks = adapter.model.blocks
    assert [b.keeps for b in blocks] == [("latent", "k_pe")] * 6
    assert blocks[0].ffn is not None and blocks[0].experts is None
    assert all(b.experts.gate == "silu" and b.experts.scoring == "sigmoid"
               and b.experts.scale == 2.448 and not b.route_early
               and b.shared.hidden == 64 for b in blocks[1:])
    # every leaf of the reference reaches the program's tree
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in leaves) == sum(x.size for x in w.values())


def test_counts_match_the_references_jaxpr(latent_manifest):
    """Both from the same shapes: the count's matmuls with EVERY expert
    and the expanded attention against what the plain reference's jaxpr
    multiplies (it applies all experts to every token)."""
    cfg = dict(latent_manifest.config("tiny-latent"), vocab_size=512)
    ref = load_py("reference", cfg["reference"])
    counts = load_py("counts", cfg["counts"])
    t, e = 32, cfg["hidden_size"]
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    assert counts.parameters(cfg) == sum(x.size for x in w.values())
    toks = jax.ShapeDtypeStruct((1, t), "int32")
    pos = jax.ShapeDtypeStruct((1, t), "int32")
    traced = jaxpr_flops(jax.make_jaxpr(
        lambda w, a, b: ref.logits_at(cfg, w, a, b))(w, toks, pos))
    every = counts.matmul_params(cfg, cfg["n_routed_experts"])
    matmuls = 2 * t * (every + e * cfg["vocab_size"])
    square = 2 * 4 * (24 + 16) * 6 * t * t      # six layers, whole square
    assert matmuls + square <= traced < 1.1 * (matmuls + square)
    # serving: a prompt of 24 tokens and 8 generated ones, 3 experts each
    prompt, out = 24, 8
    attn = prompt * (prompt + 1) // 2 + sum(range(prompt + 1, prompt + out))
    c = {"prompt_tokens": prompt, "tokens_out": out,
         "attention_positions": attn}
    active = counts.matmul_params(cfg, 3)
    assert counts.serve_flops(cfg, c) == pytest.approx(
        2 * (prompt + out) * active + 2 * out * e * cfg["vocab_size"]
        + 2 * 4 * (24 + 16) * 6 * attn)
    p = counts.layer_params(cfg)
    assert active == 6 * p["attention"] + p["dense_ffn"] + 5 * (
        p["router"] + p["shared"] + 3 * p["expert"])
    assert counts.cache_bytes_per_position(cfg, 2) == 6 * (32 + 8) * 2
    # the floor: the matrices in bf16, the router in float32
    assert counts.decode_weight_bytes(cfg, 2) > 2 * (
        active - 5 * p["router"] + e * cfg["vocab_size"]) + 4 * 5 * p["router"]


def test_six_layers_at_the_published_widths_are_3_79_b_parameters():
    cfg = Manifest().config("kanana-2-30b-a3b")
    counts = load_py("counts", "kanana-2-30b-a3b")
    assert counts.parameters(cfg) / 1e9 == pytest.approx(3.79, abs=0.005)
    assert counts.parameters(dict(cfg, num_hidden_layers=48)) / 1e9 == \
        pytest.approx(30.67, abs=0.01)
    p = counts.layer_params(cfg)
    assert p["attention"] == 2048 * 6144 + 2048 * 576 + 512 * 8192 \
        + 4096 * 2048
    assert (p["expert"], p["shared"], p["dense_ffn"], p["router"]) == \
        (3 * 2048 * 768, 3 * 2048 * 1536, 3 * 2048 * 6144, 2048 * 128)
    ref = load_py("reference", cfg["reference"])
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    assert counts.parameters(cfg) == sum(x.size for x in w.values())
    # a decode step's floor: 1.30 GB of 7.58 (six experts a layer of 128)
    assert counts.decode_weight_bytes(cfg, 2) / 1e9 == \
        pytest.approx(1.30, abs=0.01)
    assert counts.cache_bytes_per_position(cfg, 2) == 6 * 576 * 2 == 6912


def test_the_slots_latent_is_what_the_issue_counted():
    """`latent_cache_bytes` of the cell's cache: 3.62 GB, and no K or V
    leaf anywhere."""
    cfg = Manifest().config("kanana-2-30b-a3b")
    mix = Manifest().traffic("docs-open-0.8")["engine"]
    model = load_py("models", cfg["model"]).Adapter(cfg, {}).model
    cache = jax.eval_shape(
        lambda: model.init_cache(mix["slots"], mix["max_len"]))
    assert [c.shape for c in cache["latent"]] == [(32, 16384, 512)] * 6
    assert [c.shape for c in cache["k_pe"]] == [(32, 16384, 64)] * 6
    assert all(c.dtype == "bfloat16" for c in cache["latent"] + cache["k_pe"])
    for other in ("k", "v", "state", "tail"):
        assert cache[other] == [None] * 6
    held = sum(c.size * 2 for c in cache["latent"] + cache["k_pe"])
    assert held == 32 * 16384 * 6912 and held / 1e9 == \
        pytest.approx(3.62, abs=0.005)
    counts = load_py("counts", "kanana-2-30b-a3b")
    assert held == 32 * 16384 * counts.cache_bytes_per_position(cfg, 2)


def test_the_configuration_keeps_every_published_number():
    cfg = Manifest().config("kanana-2-30b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    row = None
    for line in open(catalog):
        r = json.loads(line)
        if r["name"] == "kanana-2-30b-a3b-instruct-2601":
            row = r
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 6 \
        and cfg["published"]["num_hidden_layers"] == 48
    assert cfg["source"] == row["source_url"]
    assert (cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["first_k_dense_replace"]) == (128, 128256, 1)
    assert cfg["serving"] == {"weight_dtype": "bfloat16",
                              "cache_dtype": "bfloat16"}
    assert {"token_ids", "weights", "router_input", "router_dtype",
            "shared_experts", "weight_sum_epsilon", "latent_norm",
            "rotary"} <= set(cfg["assumed"])


def test_the_mix_is_the_one_the_issue_names():
    m = Manifest()
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kanana-2-30b-a3b", "docs-open-0.8", 1)
    mix = m.traffic("docs-open-0.8")
    assert mix["driver"] == "serve_open_loop" and mix["loop"] == "open"
    assert mix["arrivals"] == {"kind": "exponential"}
    assert mix["prompt_len"]["dist"] == "lognormal" and \
        (mix["prompt_len"]["median"], mix["prompt_len"]["sigma"],
         mix["prompt_len"]["min"]) == (4096, 0.8, 512)
    # 15360, or the one fallback the issue allows (step 5), stated
    assert mix["prompt_len"]["max"] == 15360 or (
        mix["prompt_len"]["max"] == 8192 and "fallback" in mix)
    assert mix["output_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.7, "min": 16, "max": 512}
    assert mix["shared_prefix_len"] == 0 and mix["sampling"] == "greedy"
    e = mix["engine"]
    assert (e["max_len"], e["prefill_batch"], e["queue_capacity"]) == \
        (16384, 1, 4096)
    assert e["slots"] == 32 or (e["slots"] == 24 and "fallback" in mix)
    assert e["seq_buckets"] == [512, 1024, 2048, 4096, 8192]
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert mix["knee_found"]
    assert (mix["check_requests"], mix["check_block"]) == (8, 1)
    assert set(m.limits(CELL)) == {"answers_short", "logit_gap_max"}
    assert m.limits(CELL)["answers_short"] == 0


MLA_TWINS = {"prefill_ms_p50.mla": "prefill_ms_p50.chat",
             "itl_p99_ms.mla": "itl_p99_ms.chat",
             "ttft_p95_ms.mla": "ttft_p95_ms.chat",
             "compiles_in_window.mla": "compiles_in_window.serve",
             "gen_late_ms_p99.mla": "gen_late_ms_p99",
             "engine_step_ms_p50.mla": "engine_step_ms_p50",
             "serve_mfu.mla": "serve_mfu.chat",
             "decode_hbm_share.mla": "decode_hbm_share"}


def test_the_cell_reports_the_median_gap_and_its_layers_metrics():
    m = Manifest()
    names = {x["name"] for x in m.doc["end_to_end"]}
    assert {x["name"] for x in m.metrics("end_to_end", CELL, names)} == \
        {"itl_p50_ms", "setup_s"}
    assert {x["name"] for x in m.metrics("per_layer", CELL,
                                         {"itl_p50_ms", "setup_s"})} == \
        {"decode_step_ms", "mla_flash_roofline.serve"} | set(MLA_TWINS)
    for x in m.doc["per_layer"]:
        if x["name"].endswith(".mla") or x["name"].startswith("mla_"):
            assert x["workloads"] == [CELL] and x["moves"] == "itl_p50_ms"
    # decode is plain XLA: no kernel of its own, so no roofline of one
    assert not [x for x in m.doc["per_layer"]
                if x["name"].startswith("mla_decode")]
    # new entries stand at the end of their lists
    assert m.doc["configs"][-1]["name"] == "kanana-2-30b-a3b"
    assert CELL in [w["name"] for w in m.doc["workloads"][-2:]]


@pytest.mark.parametrize("twin", sorted(MLA_TWINS))
def test_a_twin_reads_what_the_accepted_metric_reads(twin):
    m = Manifest()
    mine, theirs = m.metric_file(twin), m.metric_file(MLA_TWINS[twin])
    for key in ("reader", "args", "unit", "source", "layer"):
        assert mine[key] == theirs[key], key
    assert mine["moves"] == "itl_p50_ms"
    entry = [x for x in m.doc["per_layer"] if x["name"] == twin][0]
    assert (entry["unit"], entry["source"], entry["layer"]) == \
        (mine["unit"], mine["source"], mine["layer"])


# ------------------------------------------------ no share can pass 100
@pytest.fixture(scope="module")
def ctx():
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    peaks = peaks["TPU v5 lite"]
    return types.SimpleNamespace(cfg=Manifest().config("kanana-2-30b-a3b"),
                                 chips=1, peaks=peaks)


def _read(name, ctx, out, reduced):
    spec = Manifest().metric_file(name)
    return load_py("readers", spec["reader"]).read(ctx, out, reduced,
                                                   spec.get("args", {}))


def test_the_whole_steps_share_reads_100_at_the_peak_and_no_more(ctx):
    """A window just long enough for the counted operations at the bf16
    peak reads 100; the count holds six experts a token and attention's
    expanded form, which a decode step's absorbed form exceeds 3.4
    times over, so a real window reads under it."""
    counts = load_py("counts", "kanana-2-30b-a3b")
    c = {"prompt_tokens": 150000, "tokens_out": 4000,
         "attention_positions": 600e6}
    flops = counts.serve_flops(ctx.cfg, c)
    at_peak = {"counters": c, "window_s": flops / ctx.peaks["bf16_flops"]}
    assert _read("serve_mfu.mla", ctx, at_peak, None) == pytest.approx(100.0)
    assert 0 < _read("serve_mfu.mla", ctx,
                     {"counters": c, "window_s": 30.0}, None) < 30
    per_position = 2 * 32 * (192 + 128) * 6
    assert flops == pytest.approx(
        2 * 154000 * counts.matmul_params(ctx.cfg, 6)
        + 2 * 4000 * 2048 * 128256 + per_position * 600e6)
    absorbed = 2 * 32 * (2 * 512 + 64) * 6
    assert absorbed / per_position == pytest.approx(3.4)
    nothing = {"counters": dict(c, prompt_tokens=0, tokens_out=0,
                                attention_positions=0), "window_s": 30.0}
    assert _read("serve_mfu.mla", ctx, nothing, None) is None


def test_the_decode_steps_share_is_a_floor_under_the_streamed_experts(ctx):
    """A step that took just the time its counted bytes need reads 100;
    the program streams all 128 experts a layer and the whole padded
    latent, so a real step at the memory's full pace reads under 20."""
    counts = load_py("counts", "kanana-2-30b-a3b")
    steps, live = 1000, 20
    positions = steps * live * 5000
    out = {"counters": {"decode_steps": steps,
                        "cache_positions_read": positions}}
    floor = counts.decode_weight_bytes(ctx.cfg, 2) \
        + 6912 * positions / steps
    least = floor / ctx.peaks["hbm_bytes_per_s"]
    at_pace = {"modules": {"jit__decode_fn": [steps, steps * least]}}
    assert _read("decode_hbm_share.mla", ctx, out, at_pace) == \
        pytest.approx(100.0)
    streamed = 2 * counts.parameters(ctx.cfg) - 2 * 2048 * 128256 \
        + 32 * 16384 * 6912
    real = {"modules": {"jit__decode_fn": [
        steps, steps * streamed / ctx.peaks["hbm_bytes_per_s"]]}}
    assert 10 < _read("decode_hbm_share.mla", ctx, out, real) < 20
    assert _read("decode_hbm_share.mla", ctx, out, {"modules": {}}) is None


def _call(name, t, dq=192, dv=128, heads=32):
    q = f"bf16[{heads},1,{t},{dq}]{{3,2,1,0}}"
    k = f"bf16[{heads},{t},{dq}]{{2,1,0}}"
    v = f"bf16[{heads},{t},{dv}]{{2,1,0}}"
    o = f"bf16[{heads},1,{t},{dv}]{{3,2,1,0}}"
    return (f"%{name} = {o} custom-call({q} %q, {k} %k, {v} %v), "
            "custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{{q}, {k}, {v}}}")


def test_the_prefill_kernels_share_counts_its_least_form_even_when_padded(
        ctx):
    count = load_py("counts", "mla_flash_attention")
    assert count.KERNELS == ("flash_fwd_gqa",)
    t = 16384
    flops = 2.0 * (192 + 128) * 32 * (t * (t + 1) // 2)
    nbytes = 2.0 * 32 * t * 2 * (192 + 128)

    def work(text):
        head, tail = text.split(" custom-call(")
        return count.work(ctx.cfg, "flash_fwd_gqa", shapes(head),
                          shapes(tail.split("custom_call_target")[0]))
    assert work(_call("flash_fwd_gqa.2", t)) == (flops, nbytes)
    # q, k and v padded to 256 lanes: the same work, not a third more
    assert work(_call("flash_fwd_gqa.2", t, 256, 256)) == (flops, nbytes)
    reduced = {"kernels": [(_call("flash_fwd_gqa.1", t), flops / 197e12),
                           (_call("fusion.7", t), 1.0)]}
    assert _read("mla_flash_roofline.serve", ctx, None, reduced) == \
        pytest.approx(100.0)
    padded = {"kernels": [(_call("flash_fwd_gqa.1", t, 256, 256),
                           2 * flops / 197e12)]}
    assert _read("mla_flash_roofline.serve", ctx, None, padded) == \
        pytest.approx(50.0)
    assert _read("mla_flash_roofline.serve", ctx, None,
                 {"kernels": [(_call("flash_fwd_window.1", t), 1.0)]}) is None
    assert _read("mla_flash_roofline.serve", ctx, None,
                 {"kernels": []}) is None
