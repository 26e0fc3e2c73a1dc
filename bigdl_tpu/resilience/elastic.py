"""Elastic training policy: survivors -> mesh plan, and the replay rule.

The reference's recovery granularity is the JOB: a lost executor fails
the iteration, the whole job retries from the newest snapshot
(DL/optim/DistriOptimizer.scala:862-943). Elastic training recovers at
the WINDOW: when a replica disappears mid-step the run rolls back to the
last committed sync boundary, rebuilds over the survivors, replays the
interrupted batches, and keeps going — degraded, not dead. This module
is the policy half of that story; the mechanism (per-shard dispatch,
commit/rollback/replay) lives in `DistriOptimizer._optimize_elastic_impl`,
around the iteration's tail and the run's tail every loop shares
(`BaseOptimizer._finish_iteration` / `_finish_run`).

Two decisions:

- **Shape**: `plan(alive_devices)` maps the surviving device list to a
  valid mesh. Training runs `logical_replicas` fixed logical shards per
  global batch (the determinism unit — see DistriOptimizer.set_elastic);
  any survivor count from `min_devices` up to `logical_replicas` is a
  valid shape because shards map onto devices round-robin, so the plan
  is simply the first `min(alive, logical_replicas)` survivors in
  registry order, with a (data, 1) `jax.sharding.Mesh` built over them.
- **Replay boundary**: `replay_boundary(committed_step)` — rollback
  always lands on the last committed sync boundary; every step after it
  is replayed from the retained host batches. Commit points are cheap
  (one device_get per window) and the window is bounded by
  `sync_interval`, so lost work is at most one window.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


class InsufficientCapacityError(RuntimeError):
    """Fewer survivors than `min_devices` — elastic recovery cannot
    proceed; the failure surfaces to the job-level retry loop."""


class ElasticPlan:
    """One resolved shape: the devices to run on (registry order), the
    lead device (shard results reduce there, fixed order), and the mesh
    view over them."""

    __slots__ = ("devices", "mesh", "n_active", "degraded_capacity")

    def __init__(self, devices: Sequence, total_devices: int):
        from bigdl_tpu.parallel.mesh import build_mesh
        self.devices = tuple(devices)
        self.n_active = len(self.devices)
        self.mesh = build_mesh(data=self.n_active, model=1,
                               devices=list(self.devices))
        self.degraded_capacity = (
            round(1.0 - self.n_active / total_devices, 6)
            if total_devices else 0.0)

    @property
    def lead(self):
        return self.devices[0]

    def __repr__(self):
        return (f"ElasticPlan(n_active={self.n_active}, "
                f"degraded_capacity={self.degraded_capacity})")


class ElasticController:
    """Maps surviving capacity to a training shape.

    `logical_replicas` is the fixed number of logical gradient shards per
    global batch — the batch must divide by it, and it never changes
    across shrink/grow, which is what makes the loss trajectory
    mesh-shape-invariant. `min_devices` is the floor below which the run
    aborts to the job-level retry instead of limping on.
    """

    def __init__(self, logical_replicas: int, min_devices: int = 1):
        if logical_replicas < 1:
            raise ValueError(
                f"logical_replicas must be >= 1, got {logical_replicas}")
        if not 1 <= min_devices <= logical_replicas:
            raise ValueError(
                f"min_devices must be in [1, {logical_replicas}], "
                f"got {min_devices}")
        self.logical_replicas = int(logical_replicas)
        self.min_devices = int(min_devices)

    def plan(self, alive_devices: Sequence,
             total_devices: Optional[int] = None) -> ElasticPlan:
        """Shape for the current survivor set. Raises
        `InsufficientCapacityError` below the floor."""
        alive = list(alive_devices)
        if len(alive) < self.min_devices:
            raise InsufficientCapacityError(
                f"{len(alive)} device(s) alive, elastic floor is "
                f"{self.min_devices}")
        use = alive[:min(len(alive), self.logical_replicas)]
        return ElasticPlan(use, total_devices or len(alive))

    def shard_device(self, plan: ElasticPlan, shard_index: int):
        """The device logical shard `shard_index` runs on under `plan`:
        round-robin in plan order. Fixed given (plan, index), so a replan
        remaps shards deterministically."""
        return plan.devices[shard_index % plan.n_active]

    def replay_boundary(self, committed_step: int) -> int:
        """The step rollback lands on: the last committed sync boundary.
        (A method, not a constant, so a subclass can trade commit
        frequency against replay length.)"""
        return int(committed_step)

    def split_batch(self, value):
        """Split a host batch leaf (or a list/Table of leaves) into
        `logical_replicas` equal shards along axis 0. Raises ValueError
        when the batch does not divide — elastic determinism requires
        equal shards."""
        from bigdl_tpu.utils.table import Table
        R = self.logical_replicas
        if value is None:
            return [None] * R
        if isinstance(value, (list, tuple, Table)):
            elems = list(value.values()) if isinstance(value, Table) \
                else list(value)
            per_elem = [self.split_batch(v) for v in elems]
            return [Table(*[pe[i] for pe in per_elem]) for i in range(R)]
        arr = np.asarray(value)
        if arr.ndim == 0 or arr.shape[0] % R != 0:
            raise ValueError(
                f"global batch of shape {arr.shape} does not divide into "
                f"{R} logical replicas; pick a batch size divisible by "
                f"logical_replicas")
        return np.split(arr, R, axis=0)
