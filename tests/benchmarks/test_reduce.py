"""The trace reduction, on a small trace recorded on a v5e (three runs
of a jitted 1024^3 bf16 matmul, each under a `step dispatch` and a
`loss sync` annotation, 2 ms of sleep between them)."""

import os

import pytest

from benchmarks.files import HERE
from benchmarks.trace import reduce as tr

TRACE = os.path.join(HERE, "testdata", "tiny_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(TRACE, span_names={"step dispatch", "loss sync"})


def test_busy_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(9.87e-3, rel=0.01)
    assert reduced["busy_s"] == pytest.approx(35.7e-6, rel=0.01)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_a_kernels_time_and_the_modules(reduced):
    fusion = [(t, n, s) for t, (n, s) in reduced["ops"].items()
              if t.startswith("%fusion =")]
    assert len(fusion) == 1
    _, n, seconds = fusion[0]
    assert n == 3 and seconds == pytest.approx(35.6e-6, rel=0.01)
    # 2 * 1024^3 operations in 11.9 us: under the 197 TFLOP/s peak
    assert 2 * 1024 ** 3 / (seconds / 3) < 197e12
    assert reduced["modules"]["jit__lambda"][0] == 3
    assert reduced["kernels"] == []  # no Mosaic custom-call in this trace


def test_spans_and_gap_attribution(reduced):
    assert len(reduced["spans"]["step dispatch"]) == 3
    assert len(reduced["spans"]["loss sync"]) == 3
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert set(gaps) >= {"loss_sync", "step_dispatch", "_host_in_no_span_"}
    # the idle seconds are all accounted for, each once
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # most of a loss sync is the host waiting for a transfer, chip idle
    assert gaps["loss_sync"] == pytest.approx(
        sum(reduced["spans"]["loss sync"]), rel=0.05)
    ops = reduced["breakdown"]["device_ops"]
    assert ops[0][0].startswith("fusion") and len(ops) <= 10


def test_default_span_filter_finds_the_programs_spans():
    r = tr.reduce_file(TRACE)
    assert set(r["spans"]) == {"step dispatch", "loss sync"}


def test_interval_helpers():
    busy = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)] and tr.total(busy) == 6
    assert tr.gaps(busy, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.gaps(busy, 1, 6) == [(3, 5)]


def test_shapes_and_kernel_text():
    text = ("%jvp__.33 = bf16[1605632,64]{1,0:T(8,128)(2,1)} custom-call("
            "f32[1605632,64]{1,0:T(8,128)} %x, f32[1,64]{1,0} %a, "
            "f32[1,64]{1,0} %b), custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={f32[1605632,64]{1,0}, f32[1,64]{1,0},"
            " f32[1,64]{1,0}}, frontend_attributes={kernel_metadata={}}")
    assert tr.is_kernel(text) and not tr.is_kernel("%fusion = f32[2] fusion()")
    assert tr.op_name(text) == "jvp__.33_bf16_1605632_64"
    from benchmarks.files import load_py
    results, operands = load_py("readers", "kernel_roofline").split(text)
    assert results == [("bf16", (1605632, 64))]
    assert operands == [("f32", (1605632, 64)), ("f32", (1, 64)),
                        ("f32", (1, 64))]
    flops, nbytes = load_py("counts", "bn_relu").work(results, operands)
    assert nbytes == 1605632 * 64 * (2 + 4) + 2 * 64 * 4
    assert load_py("counts", "flash_attention").work(results, operands) is None


def test_a_trace_with_no_device_plane_is_refused():
    class Plane:
        name, lines = "/host:CPU", []
    with pytest.raises(ValueError, match="no /device:TPU"):
        tr.reduce_planes([Plane()])
