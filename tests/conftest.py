"""Test configuration.

Mirrors the reference's key testing trick (SURVEY.md §4.4): the reference
emulates a 4-node cluster in one JVM via local-mode Spark; we emulate an
8-chip TPU pod on CPU via XLA's host-platform device-count flag. Must be set
before jax initializes its backends.
"""

import os

# Force CPU even on a machine with a chip: unit tests need f32 determinism
# and the virtual 8-device mesh. The env var alone selects the backend.
os.environ["JAX_PLATFORMS"] = "cpu"

# Every Telemetry.emit of an undeclared record type is a hard error in
# the suite (the runtime twin of the `telemetry` static checker) — a new
# record type must land in RECORD_SCHEMAS before any test can emit it.
os.environ.setdefault("BIGDL_TPU_STRICT_TELEMETRY", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
jax.config.update("jax_default_matmul_precision", "highest")

import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _no_nondaemon_thread_leaks():
    """Fail the suite if any test leaks a non-daemon thread.

    The input pipeline's prefetch workers are deliberately non-daemon
    (dataset/prefetch.py) so a missed close() is a VISIBLE failure here
    instead of a silently accumulating pool — this guard is the
    structural backstop for every future pipeline regression. The check
    runs at session teardown with a short grace window for threads that
    are mid-join."""
    before = {t for t in threading.enumerate() if not t.daemon}
    yield
    deadline = time.time() + 10.0
    while True:
        leaked = [t for t in threading.enumerate()
                  if not t.daemon and t.is_alive() and t not in before]
        if not leaked or time.time() > deadline:
            break
        time.sleep(0.2)
    assert not leaked, (
        f"non-daemon threads leaked by the test session: {leaked} — "
        "a prefetch pipeline (or other worker pool) was not closed")
