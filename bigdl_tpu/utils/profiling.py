"""Per-layer timing + device profiler integration.

Parity: the reference builds wall-time accumulation into the module
contract — `forwardTime`/`backwardTime` in AbstractModule.forward:256 /
backward:283, exposed via `getTimes()/resetTimes()`, aggregated by
Container (SURVEY.md §5.1) — plus the named-phase `Metrics` table. On TPU a
jitted step has no per-layer boundaries, so per-layer timing runs the model
EAGERLY layer by layer (accurate for finding the hot layer, not for
absolute step cost) and the real trace comes from the XLA profiler
(`profile_trace`), viewable in TensorBoard/Perfetto/xprof.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np


def device_sync(val) -> None:
    """Device-completion barrier for timing code, by VALUE fetch: every
    array leaf is reduced to ONE combined scalar on device and fetched
    once (4 bytes). A value cannot arrive before the work that produces
    it, on any backend. On the directly attached v5e
    `jax.block_until_ready` is a barrier as well (chip_smoke.py's barrier
    phase prints both); whether this function should become that call is
    ROADMAP D5's."""
    jnp = jax.numpy
    leaves = [l for l in jax.tree_util.tree_leaves(val)
              if hasattr(l, "dtype") and getattr(l, "size", 0)]
    if leaves:
        np.asarray(sum(jnp.sum(l).astype(jnp.float32) for l in leaves))


def get_times(module, x, training: bool = False,
              rng: Optional[jax.Array] = None) -> List[Tuple[str, float]]:
    """Eager per-layer forward wall times, in execution order
    (reference AbstractModule.getTimes). Only Sequential-style chains are
    traversed layer-by-layer; other modules time as one unit."""
    from bigdl_tpu.nn.containers import Sequential
    from bigdl_tpu.nn.module import ApplyContext
    out: List[Tuple[str, float]] = []

    def run(m, val, params, path: str):
        if isinstance(m, Sequential):
            for key, child in zip(m._child_keys, m.children):
                val = run(child, val, params[key], f"{path}/{key}")
            return val
        ctx = ApplyContext(training=training, rng=rng, state=m._state or {})
        t0 = time.perf_counter()
        val = m.apply(params, val, ctx)
        device_sync(val)
        out.append((path or m.name, time.perf_counter() - t0))
        return val

    run(module, x, module.ensure_params(), "")
    return out


@contextlib.contextmanager
def profile_trace(logdir: str):
    """XLA device profiler trace (open in TensorBoard's profile plugin /
    xprof). The TPU answer to the reference's Metrics phase table:
    compiler-scheduled ops are only observable through the device trace."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class TimedPhases:
    """Named-phase wall-time accumulators (reference Metrics,
    DL/optim/Metrics.scala:36-103 — 'get weights average', 'computing time'
    ... table). The optimizer's Metrics class already records the hot
    phases; this is the standalone user-facing variant."""

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    def summary(self) -> str:
        lines = [f"{name}: total {self.totals[name]:.4f}s over "
                 f"{self.counts[name]} calls "
                 f"(avg {self.totals[name] / self.counts[name]:.4f}s)"
                 for name in sorted(self.totals)]
        return "\n".join(lines)
