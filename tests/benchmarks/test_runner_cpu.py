"""The runner refuses to measure without a TPU that peaks.json knows."""

import os
import subprocess
import sys

from benchmarks.files import ROOT


def test_exits_non_zero_and_prints_no_metric_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         "resnet50.train-b128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_an_unknown_workload_is_refused_by_name():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload", "no.such",
         "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "no workload 'no.such'" in p.stderr
    assert p.stdout.strip() == ""
