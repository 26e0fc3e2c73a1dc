"""Device mesh management.

Parity role: the reference `Engine` (DL/utils/Engine.scala:41) detects
node/core topology from SparkConf and owns execution resources. On TPU the
"cluster" is `jax.devices()` and resource ownership is a
`jax.sharding.Mesh`; multi-host (the reference's multi-executor) is the same
code path — jax process i sees its local chips, the mesh spans all.

Mesh axes convention (scaling-book style):
  data  — data parallelism (the reference's only strategy, SURVEY.md §2)
  model — tensor parallelism (beyond-parity, rides ICI)
Multi-slice DCN would prepend a 'dcn' axis; single-slice here.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def build_mesh(data: Optional[int] = None, model: int = 1,
               devices: Optional[Sequence] = None) -> Mesh:
    """Build a (data, model) mesh over the available devices.

    Example:
        >>> import jax
        >>> from bigdl_tpu.parallel.mesh import build_mesh
        >>> mesh = build_mesh(data=2, model=1, devices=jax.devices()[:2])
        >>> mesh.axis_names, mesh.devices.shape
        (('data', 'model'), (2, 1))
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if data is None:
        data = n // model
    if data * model != n:
        raise ValueError(f"mesh {data}x{model} != {n} devices")
    arr = np.array(devices).reshape(data, model)
    return Mesh(arr, axis_names=("data", "model"))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P("data"))


def replicate_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding replicating a value across the whole mesh."""
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch):
    """Place a host batch sharded over the data axis.

    Single-host: one async device_put. Multi-host: each process passes its
    LOCAL shard of the global batch (per-host feeding,
    DistriOptimizer.scala:211-212 / ZippedPartitionsWithLocalityRDD) and
    jax.make_array_from_process_local_data assembles the global jax.Array
    without any cross-host data motion."""
    import jax.numpy as jnp
    if int(np.prod(mesh.devices.shape)) == 1:
        # one-device mesh: plain placement keeps the backend's fastest
        # single-chip path (no SPMD annotations to honor)
        dev = mesh.devices.reshape(-1)[0]
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(jnp.asarray(x), dev), batch)
    sh = data_sharding(mesh)
    multi_host = jax.process_count() > 1

    def put(x):
        if multi_host:
            return jax.make_array_from_process_local_data(sh, np.asarray(x))
        return jax.device_put(jnp.asarray(x), sh)

    return jax.tree_util.tree_map(put, batch)
