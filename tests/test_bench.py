"""Smoke coverage for the driver-facing entry points (bench.py ->
tools/bench_cli.py, chip_smoke.py): the framework-loop throughput path
runs on CPU; with no TPU both entry points exit non-zero quickly, name
the platform they found and print no metric; the compile cache is placed
by one rule; and the metric JSON contract holds."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_entry_point_on_cpu(script):
    """Run a repo-root entry point with the CPU pinned; returns
    (CompletedProcess, seconds)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    r = subprocess.run([sys.executable, os.path.join(REPO, script)],
                       capture_output=True, text=True, timeout=300, env=env)
    return r, time.perf_counter() - t0


def _json_lines(text):
    out = []
    for line in text.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return [o for o in out if isinstance(o, dict)]


def test_bench_lenet_framework_loop_runs():
    from bigdl_tpu.tools.bench_cli import bench_lenet
    tp, metrics, flops = bench_lenet(batch_size=64, warmup=1, iters=3)
    assert tp > 0
    assert "computing time average" in metrics.summary()
    assert flops is None or flops > 0


def test_bench_lenet_host_pipeline_variant():
    from bigdl_tpu.tools.bench_cli import bench_lenet
    tp, _, _ = bench_lenet(batch_size=64, warmup=1, iters=3,
                           resident=False)
    assert tp > 0


def test_bench_input_pipeline_ab_runs():
    """The --input-cost-ms A/B (serial vs prefetched input pipeline)
    produces the json contract; tiny segment counts keep it a smoke
    test — the real measurement is recorded in docs/PERF.md."""
    from bigdl_tpu.tools.bench_cli import bench_input_pipeline
    out = bench_input_pipeline(0.0, segments=2, seg_iters=3)
    assert out["metric"] == "input_pipeline_ab"
    assert out["serial_records_per_sec"] > 0
    assert out["prefetch_records_per_sec"] > 0
    assert out["speedup"] > 0
    assert out["workers"] == 1  # supply-rate matching at zero cost


def test_bench_serving_ab_runs():
    """The --serve A/B (closed-loop serial vs micro-batching engine)
    produces the json contract; tiny segment counts keep it a smoke
    test — the real measurement is recorded in docs/PERF.md."""
    from bigdl_tpu.tools.bench_cli import bench_serving_ab
    out = bench_serving_ab(clients=2, segments=2, seg_requests=8,
                           max_batch=8)
    assert out["metric"] == "serving_ab"
    assert out["serial_rps"] > 0
    assert out["engine_rps"] > 0
    assert out["speedup"] > 0
    assert out["engine_bucket_hit_rate"] == 1.0  # warmup covers all buckets


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_entry_point_refuses_to_run_without_a_tpu(script):
    """No TPU: exit non-zero within seconds, say which platform JAX
    found, print no result. A number from the CPU is not a device
    metric, and a smoke that passes off the chip proves nothing."""
    r, seconds = _run_entry_point_on_cpu(script)
    assert r.returncode != 0
    assert seconds < 60.0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert not [o for o in _json_lines(r.stdout)
                if "metric" in o or "ok" in o]


def test_metric_json_contract():
    # the driver parses ONE json line from stdout: {metric, value, unit,
    # vs_baseline}
    from bigdl_tpu.tools import bench_cli
    line = json.dumps({"metric": "m", "value": 1.0, "unit": "u",
                       "vs_baseline": 1.0})
    parsed = json.loads(line)
    assert set(parsed) >= {"metric", "value", "unit", "vs_baseline"}


def test_compile_cache_follows_the_environment(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set nothing is set in code;
    where it is not, the cache is one fixed directory in the checkout."""
    import jax
    from bigdl_tpu.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.configure() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        in_checkout = os.path.join(REPO, ".jax_cache")
        assert compile_cache.cache_dir() == in_checkout
        assert compile_cache.configure() == in_checkout
        assert jax.config.jax_compilation_cache_dir == in_checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_peak_table_matches_device_kinds_exactly():
    from bigdl_tpu.observability.costs import peak_flops
    assert peak_flops("TPU v5 lite") == 197e12
    # a v5 string the table does not hold is unknown, not v5p's 459e12
    assert peak_flops("TPU v5 lite pod") is None
    assert peak_flops("TPU v5x") is None


def test_headline_child_plumbing():
    """The result line is assembled from a child process so that the
    parent never holds the chip; exercise the real spawn -> exit-code ->
    error path. Off the chip the headline child refuses, and the parent
    surfaces its reason instead of a number."""
    from bigdl_tpu.tools.bench_cli import _headline_child
    with pytest.raises(RuntimeError, match="no TPU.*'cpu'"):
        _headline_child("resnet", 120.0)


def test_result_lines_name_their_device(capsys):
    import jax
    from bigdl_tpu.tools.bench_cli import _emit
    _emit({"metric": "m", "value": 1.0})
    out = json.loads(capsys.readouterr().out)
    dev = jax.devices()[0]
    assert out["device"] == \
        f"{dev.platform}:{dev.device_kind} x{jax.device_count()}"


def test_bench_telemetry_attribution_passthrough(tmp_path, monkeypatch,
                                                 capsys):
    """--attribution: a telemetry-wired bench run prints the metrics_cli
    attribution report to stderr after closing its JSONL stream."""
    import bigdl_tpu.nn as nn
    import bigdl_tpu.optim as optim
    from bigdl_tpu.dataset.dataset import LocalDataSet
    from bigdl_tpu.dataset.sample import MiniBatch
    from bigdl_tpu.optim.local_optimizer import LocalOptimizer
    from bigdl_tpu.tools.bench_cli import _bench_telemetry

    monkeypatch.setenv("BIGDL_TPU_TELEMETRY", str(tmp_path))
    monkeypatch.setenv("BIGDL_TPU_ATTRIBUTION", "1")
    rs = np.random.RandomState(0)
    batches = [MiniBatch(rs.rand(8, 6).astype(np.float32),
                         (rs.randint(0, 2, 8) + 1).astype(np.int32))
               for _ in range(2)]
    model = nn.Sequential().add(nn.Linear(6, 2)).add(nn.LogSoftMax())
    opt = LocalOptimizer(model, LocalDataSet(batches),
                         nn.ClassNLLCriterion())
    opt.set_optim_method(optim.SGD(learning_rate=0.05))
    opt.set_end_when(optim.max_iteration(2))
    with _bench_telemetry(opt):
        opt.optimize()
    err = capsys.readouterr().err
    assert "host vs device phase table" in err
    assert "flops_per_step" in err
    jsonls = list(tmp_path.glob("bench_*_r*.jsonl"))
    assert jsonls, "telemetry stream not recorded"
