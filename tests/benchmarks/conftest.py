"""Shared fixtures for the benchmark's own tests (CPU, tiny sizes)."""

import os

import pytest


@pytest.fixture(scope="session")
def tiny_manifest():
    """BENCHMARK.json of a two-layer, 64-wide LM with one training and
    one serving cell: everything the harness does, at a size a test run
    can hold."""
    from benchmarks.files import HERE, Manifest
    base = os.path.join(HERE, "testdata", "tiny")
    return Manifest(os.path.join(base, "BENCHMARK.json"), base)


@pytest.fixture()
def run_tiny(tiny_manifest, tmp_path):
    """Drive one run of a tiny cell, past the look for a chip."""
    from benchmarks.harness import run_cell

    def run(cell, seed=2 ** 31 + 11, seconds=0.6, trace=False):
        return run_cell(cell, seed, seconds, trace, manifest=tiny_manifest,
                        require_chip=False, scratch=str(tmp_path))
    return run
