"""The `olmo-hybrid-7b` configuration's benchmark files at a size a test
run can hold (benchmarks/testdata/tiny-hybrid: the same block at hidden
64, 4 heads, linear key 8 / value 16, full head 16, 4 taps, FFN 128,
[linear x3, full] x 2): the benchmark's own reference against the
program through the harness, its counts against the reference's shapes,
the controls that have to come out as not correct, and the new count
file under the readers that no share of it can pass 100."""

import json
import os
import types

import jax
import pytest

from benchmarks.files import HERE, Manifest, load_json, load_py
from bigdl_tpu.observability.costs import jaxpr_flops

SEEDS = (5, 6, 7)
CELL = "olmo-hybrid-7b.serve-decode"


@pytest.fixture(scope="module")
def hybrid_manifest():
    base = os.path.join(HERE, "testdata", "tiny-hybrid")
    return Manifest(os.path.join(base, "BENCHMARK.json"), base)


@pytest.fixture(scope="module")
def runs(hybrid_manifest, tmp_path_factory):
    """One run of the tiny serving cell a seed, kept for the controls."""
    from benchmarks.harness import run_cell
    out = {}
    for seed in SEEDS:
        keep = {}
        result = run_cell("tiny-hybrid.serve", seed, 1.0, False,
                          manifest=hybrid_manifest, require_chip=False,
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
    # prompts shorter than the convolution's taps were served, and
    # answers that outran their prompts
    lengths = [len(s["prompt"]) for s in out["served"]]
    assert min(lengths) < 4 < max(lengths)
    assert any(len(s["tokens"]) > len(s["prompt"]) for s in out["served"])


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", ["fp8", "state_lost", "beta_single"])
def test_a_lower_precision_and_each_planted_fault_are_not_correct(
        runs, hybrid_manifest, seed, control):
    """fp8: every matmul operand rounded; state_lost: every linear
    layer's state zeroed at the prefill/decode seam; beta_single: beta
    not doubled. The token each puts first lies further under the
    float32 reference's best than the limit allows."""
    _, ctx, out = runs[seed]
    limit = hybrid_manifest.limits("tiny-hybrid.serve")["logit_gap_max"]
    driver = load_py("drivers", "serve_open_loop")
    ref = ctx.reference
    w = ref.served_weights(ctx.cfg, ref.init_weights(ctx.cfg, seed))
    gap, n = driver.served_gaps(ctx, w, out["served"], control=control)
    assert n > 100 and gap > 3 * limit, (gap, limit)
    # the configuration's own precision, emulated the same way, passes
    own, _ = driver.served_gaps(ctx, w, out["served"], control="bf16")
    assert own <= limit


def test_the_planted_faults_leave_the_prompts_own_logits_alone(
        hybrid_manifest):
    """`state_lost` strikes after the first position asked for (a
    prompt's last) and nowhere before it."""
    cfg = hybrid_manifest.config("tiny-hybrid")
    ref = load_py("reference", cfg["reference"])
    w = ref.init_weights(cfg, 5)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 1, 129)
    pos = jax.numpy.asarray([[6, 7, 12], [9, 10, 19]])
    good = ref.logits_at(cfg, w, toks, pos)
    lost = ref.logits_at(cfg, w, toks, pos, "state_lost")
    assert float(abs(good[:, 0] - lost[:, 0]).max()) == 0.0
    assert float(abs(good[:, 1:] - lost[:, 1:]).max()) > 0.1


def test_weights_are_kept_as_served_and_drawn_from_the_seed(hybrid_manifest):
    cfg = hybrid_manifest.config("tiny-hybrid")
    ref = load_py("reference", cfg["reference"])
    w, again, other = (ref.init_weights(cfg, s) for s in (2 ** 31 + 5,
                                                          2 ** 31 + 5, 6))
    assert w["l0.wq"].dtype == "bfloat16" and w["l0.cq"].dtype == "bfloat16"
    for name in ("l0.a_log", "l0.dt_bias", "l0.n1.g", "l0.gn.g", "l3.qn.g"):
        assert w[name].dtype == "float32", name
    assert bool((w["l1.wq"] == again["l1.wq"]).all())
    assert not bool((w["l1.wq"] == other["l1.wq"]).all())
    assert ref.served_weights(cfg, w) is w
    # alpha's parametrisation as the layer is usually started
    assert float(w["l0.a_log"].max()) <= 2.78 and \
        float(jax.nn.softplus(w["l0.dt_bias"]).max()) <= 0.1001
    adapter = load_py("models", cfg["model"]).Adapter(cfg, {})
    tree = adapter.served_params(w)
    assert tree["block2"]["ffn"]["wd"] is w["l2.wd"]
    assert tree["block3"]["attn"]["q_norm"] is w["l3.qn.g"]
    assert tree["block0"]["attn"]["conv"].shape == (4, 4 * (8 + 8 + 16))
    assert bool((tree["block0"]["attn"]["conv"][:, 32:64]
                 == w["l0.ck"]).all())
    assert [b.keeps for b in adapter.model.blocks] == \
        [("state", "tail")] * 3 + [("k", "v")] + [("state", "tail")] * 3 \
        + [("k", "v")]
    assert all(b.norm_output and b.experts is None
               for b in adapter.model.blocks)
    assert adapter.model.blocks[3].attn.qk_norm == cfg["rms_norm_eps"]
    assert adapter.model.blocks[3].attn.rope_base is None
    # every leaf of the reference reaches the program's tree
    leaves = jax.tree_util.tree_leaves(tree)
    assert sum(x.size for x in leaves) == sum(x.size for x in w.values())


def test_counts_match_the_references_jaxpr(hybrid_manifest):
    """Both from the same shapes: the count's matmuls, attention and
    recurrence against what the plain reference's jaxpr multiplies."""
    cfg = dict(hybrid_manifest.config("tiny-hybrid"), vocab_size=512)
    ref = load_py("reference", cfg["reference"])
    counts = load_py("counts", cfg["counts"])
    t, e = 32, cfg["hidden_size"]
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    assert counts.parameters(cfg) == sum(x.size for x in w.values())
    toks = jax.ShapeDtypeStruct((1, t), "int32")
    pos = jax.ShapeDtypeStruct((1, t), "int32")
    traced = jaxpr_flops(jax.make_jaxpr(
        lambda w, a, b: ref.logits_at(cfg, w, a, b))(w, toks, pos))
    matmuls = 2 * t * (counts.matmul_params(cfg) + e * cfg["vocab_size"])
    square = 2 * 4 * e * t * t          # two full layers, the whole square
    assert matmuls + square <= traced < 1.25 * (matmuls + square)
    # serving: a prompt of 24 tokens and 8 generated ones
    prompt, out = 24, 8
    attn = prompt * (prompt + 1) // 2 + sum(range(prompt + 1, prompt + out))
    c = {"prompt_tokens": prompt, "tokens_out": out,
         "attention_positions": attn}
    recurrence = 2 * 3 * 4 * 8 * 16 * 6 * (prompt + out)
    assert counts.serve_flops(cfg, c) == pytest.approx(
        2 * (prompt + out) * counts.matmul_params(cfg)
        + 2 * out * e * cfg["vocab_size"] + 4 * e * 2 * attn + recurrence)
    p = counts.layer_params(cfg)
    assert counts.matmul_params(cfg) == 6 * p["linear"] + 2 * p["full"] \
        + 8 * p["ffn"]
    assert counts.cache_bytes_per_position(cfg, 2) == 2 * 2 * e * 2
    assert counts.decode_weight_bytes(cfg, 2) > 2 * counts.matmul_params(cfg)


def test_sixteen_layers_at_the_published_widths_are_4_10_b_parameters():
    cfg = Manifest().config("olmo-hybrid-7b")
    counts = load_py("counts", "olmo-hybrid-7b")
    assert counts.parameters(cfg) / 1e9 == pytest.approx(4.10, abs=0.005)
    p = counts.layer_params(cfg)
    assert (p["linear"], p["full"], p["ffn"]) == \
        (3840 * (2880 + 2880 + 3 * 5760) + 2 * 3840 * 30, 58982400,
         126812160)
    assert counts.parameters(dict(cfg, num_hidden_layers=32)) / 1e9 == \
        pytest.approx(7.43, abs=0.01)
    ref = load_py("reference", cfg["reference"])
    w = jax.eval_shape(lambda: ref.init_weights(cfg, 0))
    assert counts.parameters(cfg) == sum(x.size for x in w.values())
    # a decode step's weights: 6.66 GB of layers and 0.77 GB of head
    assert counts.decode_weight_bytes(cfg, 2) / 1e9 == \
        pytest.approx(7.43, abs=0.01)
    assert counts.cache_bytes_per_position(cfg, 2) == 4 * 15360


def test_the_slots_state_is_what_the_issue_counted():
    """`recurrent_state_bytes` of the cell's cache: 0.85 GB of states
    and 0.03 GB of tails; K/V 4.03 GB."""
    cfg = Manifest().config("olmo-hybrid-7b")
    model = load_py("models", cfg["model"]).Adapter(cfg, {}).model
    cache = jax.eval_shape(lambda: model.init_cache(32, 2048))
    state = sum(s.size * 4 for s in cache["state"] if s is not None)
    tail = sum(s.size * 2 for s in cache["tail"] if s is not None)
    kv = sum(s.size * 2 for name in "kv" for s in cache[name]
             if s is not None)
    assert (state, tail) == (12 * 32 * 30 * 96 * 192 * 4,
                             12 * 32 * 3 * 11520 * 2)
    assert state / 1e9 == pytest.approx(0.85, abs=0.005)
    assert tail / 1e9 == pytest.approx(0.027, abs=0.001)
    assert kv / 1e9 == pytest.approx(4.03, abs=0.005)
    assert cache["state"][0].dtype == "float32"
    assert cache["tail"][0].dtype == cache["k"][3].dtype == "bfloat16"
    assert [c is None for c in cache["k"]] == [True, True, True, False] * 4


def test_the_configuration_keeps_every_published_number():
    cfg = Manifest().config("olmo-hybrid-7b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    row = None
    for line in open(catalog):
        r = json.loads(line)
        if r["name"] == "Olmo-Hybrid-7B":
            row = r
    changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert changed == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["num_hidden_layers"] == 16 \
        and cfg["published"]["num_hidden_layers"] == 32
    assert cfg["source"] == row["source_url"]
    assert cfg["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"] and len(cfg["layer_types"]) == 32
    assert cfg["serving"] == {"weight_dtype": "bfloat16",
                              "cache_dtype": "bfloat16",
                              "state_dtype": "float32"}
    assert {"norm_placement", "qk_norm", "positional_encoding",
            "linear_layer", "beta", "alpha", "token_ids", "weights"} \
        <= set(cfg["assumed"])


def test_the_mix_is_the_one_the_issue_names():
    m = Manifest()
    cell = m.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("olmo-hybrid-7b", "chat-decode-0.8", 1)
    mix = m.traffic("chat-decode-0.8")
    assert mix["driver"] == "serve_open_loop" and mix["loop"] == "open"
    assert mix["arrivals"] == {"kind": "exponential"}
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 1.0, "min": 16, "max": 1024}
    assert mix["output_len"] == {"dist": "lognormal", "median": 256,
                                 "sigma": 0.6, "min": 32, "max": 768}
    assert mix["shared_prefix_len"] == 0 and mix["sampling"] == "greedy"
    e = mix["engine"]
    assert (e["slots"], e["max_len"], e["prefill_batch"],
            e["queue_capacity"]) == (32, 2048, 4, 4096)
    assert e["seq_buckets"] == [64, 128, 256, 512, 1024]  # whole chunks
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    assert mix["knee_found"]
    assert (mix["check_requests"], mix["check_block"]) == (8, 1)
    assert set(m.limits(CELL)) == {"answers_short", "logit_gap_max"}


HYBRID_TWINS = {"prefill_ms_p50.hybrid": "prefill_ms_p50.chat",
                "itl_p99_ms.hybrid": "itl_p99_ms.chat",
                "ttft_p95_ms.hybrid": "ttft_p95_ms.chat",
                "compiles_in_window.hybrid": "compiles_in_window.serve",
                "gen_late_ms_p99.hybrid": "gen_late_ms_p99",
                "engine_step_ms_p50.hybrid": "engine_step_ms_p50"}


def cell_pins(m):
    """What the cell has, in `m`; nothing about entries appended after it."""
    names = {x["name"] for x in m.doc["end_to_end"]}
    assert {x["name"] for x in m.metrics("end_to_end", CELL, names)} == \
        {"itl_p50_ms", "setup_s"}
    assert {x["name"] for x in m.metrics("per_layer", CELL,
                                         {"itl_p50_ms", "setup_s"})} >= \
        {"decode_step_ms", "decode_hbm_share.hybrid", "serve_mfu.hybrid",
         "decode_kv_write_ms", "decode_attention_ms"} | set(HYBRID_TWINS)
    for x in m.doc["per_layer"]:
        if x["name"].endswith(".hybrid"):
            assert x["workloads"] == [CELL] and x["moves"] == "itl_p50_ms"
    # `decode_hbm_share`'s reader has no term for the state: not this cell's
    share = [x for x in m.doc["per_layer"] if x["name"] == "decode_hbm_share"]
    assert CELL not in share[0]["workloads"]
    # no kernel was written for the layer, so no roofline of one
    assert not [x for x in m.doc["per_layer"] if x["name"].startswith("gdn")]
    assert not os.path.exists(os.path.join(HERE, "counts",
                                           "gated_delta_rule.py"))


def test_the_cell_reports_the_median_gap_and_its_layers_metrics():
    cell_pins(Manifest())


@pytest.mark.parametrize("twin", sorted(HYBRID_TWINS))
def test_a_twin_reads_what_the_accepted_metric_reads(twin):
    """The cell's tail, first-token, prefill, compile, sender and engine
    step metrics are the accepted readers with the accepted arguments
    under a name of the cell's own."""
    m = Manifest()
    mine, theirs = m.metric_file(twin), m.metric_file(HYBRID_TWINS[twin])
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
    return types.SimpleNamespace(cfg=Manifest().config("olmo-hybrid-7b"),
                                 chips=1, peaks=peaks)


def _read(name, ctx, out, reduced):
    spec = Manifest().metric_file(name)
    return load_py("readers", spec["reader"]).read(ctx, out, reduced,
                                                   spec.get("args", {}))


def test_the_whole_steps_share_reads_100_at_the_peak_and_no_more(ctx):
    """A window just long enough for the counted operations at the bf16
    peak reads 100; the count holds nothing the traffic does not need
    (the recurrence in its least form, no padded bucket, no idle slot),
    so a real window, which is longer, reads under it."""
    counts = load_py("counts", "olmo-hybrid-7b")
    c = {"prompt_tokens": 40000, "tokens_out": 30000,
         "attention_positions": 30e6}
    flops = counts.serve_flops(ctx.cfg, c)
    at_peak = {"counters": c, "window_s": flops / ctx.peaks["bf16_flops"]}
    assert _read("serve_mfu.hybrid", ctx, at_peak, None) == \
        pytest.approx(100.0)
    assert 0 < _read("serve_mfu.hybrid", ctx,
                     {"counters": c, "window_s": 30.0}, None) < 10
    # the least form: under a hundredth of a token's matmuls
    recurrence = 2 * 3 * 30 * 96 * 192 * 12
    assert recurrence < 0.01 * 2 * counts.matmul_params(ctx.cfg)
    nothing = {"counters": dict(c, prompt_tokens=0, tokens_out=0,
                                attention_positions=0), "window_s": 30.0}
    assert _read("serve_mfu.hybrid", ctx, nothing, None) is None


def test_the_decode_steps_share_counts_the_live_slots_state(ctx):
    """A step that took just the time its counted bytes need reads 100:
    the weights, the full layers' live K/V, and each live slot's state
    and tail read once and written once. The program replaces EVERY
    slot's state, so a real step reads under 100 even at the memory's
    pace; and the accepted reader, which has no such term, reads lower
    on the same step (why this cell is not on its list)."""
    counts = load_py("counts", "olmo-hybrid-7b")
    per_slot = counts.recurrent_bytes_per_slot(ctx.cfg, 4, 2)
    assert per_slot == 12 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    # what `recurrent_state_bytes` reads of the cell's cache, a slot
    model = load_py("models", ctx.cfg["model"]).Adapter(ctx.cfg, {}).model
    cache = jax.eval_shape(lambda: model.init_cache(32, 2048))
    assert 32 * per_slot == sum(
        s.size * s.dtype.itemsize for name in ("state", "tail")
        for s in cache[name] if s is not None)
    steps, live, requests = 1000, 20, 50
    positions = steps * live * 500
    out = {"counters": {"decode_steps": steps, "requests": requests,
                        "tokens_out": steps * live + requests,
                        "cache_positions_read": positions}}
    nbytes = counts.decode_weight_bytes(ctx.cfg, 2) \
        + counts.cache_bytes_per_position(ctx.cfg, 2) * positions / steps \
        + 2 * per_slot * live
    least = nbytes / ctx.peaks["hbm_bytes_per_s"]
    at_pace = {"modules": {"jit__decode_fn": [steps, steps * least]}}
    assert _read("decode_hbm_share.hybrid", ctx, out, at_pace) == \
        pytest.approx(100.0)
    assert 80 < _read("decode_hbm_share", ctx, out, at_pace) < 95
    idle = 2 * per_slot * (32 - live) / ctx.peaks["hbm_bytes_per_s"]
    every_slot = {"modules": {"jit__decode_fn": [steps,
                                                 steps * (least + idle)]}}
    assert 90 < _read("decode_hbm_share.hybrid", ctx, out, every_slot) < 100
    assert _read("decode_hbm_share.hybrid", ctx, out,
                 {"modules": {}}) is None
    # requests that got no token take nothing off the floor's sign
    none = {"counters": dict(out["counters"], tokens_out=0)}
    assert 0 < _read("decode_hbm_share.hybrid", ctx, none, at_pace) < 100
