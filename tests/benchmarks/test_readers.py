"""The per-layer readers on hand-made inputs: each returns its number,
or nothing where there is nothing to read (never a 0 for a share)."""

import types

import pytest

from benchmarks.files import Manifest, load_json, load_py, HERE
import os


@pytest.fixture(scope="module")
def ctx():
    m = Manifest()
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]["TPU v5 lite"]
    return types.SimpleNamespace(
        cfg=m.config("neox-3.6b"), mix=m.traffic("train-t2048"), chips=1,
        peaks=peaks)


def _read(name, ctx, out, reduced):
    spec = Manifest().metric_file(name)
    return load_py("readers", spec["reader"]).read(ctx, out, reduced,
                                                   spec.get("args", {}))


def test_counters(ctx):
    out = {"counters": {"step_ms": [90.0, 92.0, 94.0], "gen_late_ms": [],
                        "lowerings_in_window": 0, "decode_s": 2.0,
                        "decode_steps": 100}}
    assert _read("step_ms_p50.train", ctx, out, None) == 92.0
    assert _read("gen_late_ms_p99", ctx, out, None) is None
    assert _read("compiles_in_window.train", ctx, out, None) == 0
    assert _read("decode_step_ms", ctx, out, None) == 20.0
    out["counters"]["decode_steps"] = 0
    assert _read("decode_step_ms", ctx, out, None) is None
    # the tails that are too unsteady for a bound, read from every gap
    # and every request's first token
    out["counters"].update(itl_ms=[9.0] * 19 + [20.0], ttft_ms=[10.0, 30.0])
    assert _read("itl_p95_ms", ctx, out, None) == pytest.approx(9.55)
    assert _read("ttft_p95_ms", ctx, out, None) == pytest.approx(29.0)


def test_mfu_is_counted_flops_over_the_peak(ctx):
    out = {"counters": {"items_per_s_per_chip": 38000.0}, "window_s": 30.0}
    # 2.96 GFLOP a token x 38 000 tokens/s over 197 TFLOP/s
    assert _read("train_mfu", ctx, out, None) == pytest.approx(57.1, abs=0.5)
    c = {"prompt_tokens": 80000, "tokens_out": 38000,
         "attention_positions": 40e6}
    got = _read("serve_mfu", ctx, {"counters": c, "window_s": 30.0}, None)
    assert 0 < got < 5


def test_kernel_roofline_and_its_silence(ctx):
    q = "bf16[4,22,2048,128]{3,2,1,0}"
    text = (f"%c.1 = ({q}, f32[4,22,2048,1]{{3,2,1,0}}) custom-call({q} %q, "
            f"{q} %k, {q} %v), custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{{q}, {q}, {q}}}, "
            "frontend_attributes={kernel_metadata={}}")
    flops = 2 * 4 * 22 * 2048 * 2048 * 128
    least = flops / 197e12
    reduced = {"kernels": [(text, 2 * least), (text, 2 * least)]}
    assert _read("flash_roofline.train", ctx, None, reduced) == \
        pytest.approx(50.0)
    assert _read("bn_relu_roofline", ctx, None, reduced) is None
    assert _read("flash_roofline.train", ctx, None, {"kernels": []}) is None


def test_decode_hbm_share(ctx):
    out = {"counters": {"decode_steps": 10, "cache_positions_read": 10 * 5000}}
    reduced = {"modules": {"jit__decode_fn": [4, 4 * 0.013]}}
    # 0.94 GB of weights and 5000 positions of 45 KB in 13 ms
    want = 100 * (2 * (4 * 95.2e6 + 90.1e6) + 5000 * 45056) / (0.013 * 819e9)
    assert _read("decode_hbm_share", ctx, out, reduced) == \
        pytest.approx(want, rel=0.01)
    assert _read("decode_hbm_share", ctx, out, {"modules": {}}) is None


def test_spans_and_collectives(ctx):
    reduced = {"spans": {"generate prefill": [0.004, 0.006, 0.010]},
               "modules": {"jit_step_under_mesh": [5, 0.5]},
               "ops": {"%all-reduce-done.3 = f32[8] all-reduce-done(...)":
                       [5, 0.005],
                       "%fusion.1 = f32[8] fusion(%all-reduce-done.3)":
                       [5, 0.1]}}
    assert _read("prefill_ms_p50", ctx, None, reduced) == pytest.approx(6.0)
    # no cell reports a collective yet: the reader is driven by hand,
    # with the arguments a four-chip cell's metric file would give it
    exposed = load_py("readers", "collective_exposed").read
    args = {"module": "jit_step_under_mesh", "ops": ["all-reduce"]}
    assert exposed(ctx, None, reduced, args) == pytest.approx(1.0)
    reduced["ops"] = {}
    assert exposed(ctx, None, reduced, args) is None
    assert _read("prefill_ms_p50", ctx, None, {"spans": {}}) is None
