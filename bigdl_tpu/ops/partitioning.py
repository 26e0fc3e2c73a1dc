"""How the Pallas kernels find the device mesh they run under.

A Mosaic custom call cannot be partitioned automatically: under a
multi-device `jit` its lowering raises, and libtpu offers no
`custom_partitioning`. So each kernel the TPU defaults route to
(ops/bn_relu_kernel.py, ops/attention_kernel.py) wraps itself in a
`jax.shard_map` over the mesh of the step being traced. That mesh is
JAX's own context mesh: a loop that jits over several devices traces its
step under `jax.sharding.use_abstract_mesh(mesh.abstract_mesh)`
(optim/distri_optimizer.py does). With no context mesh the kernels lower
as they are, which is right on one device and inside a fully manual
`shard_map`, and on several devices surfaces Mosaic's own refusal.
"""

from __future__ import annotations

from typing import Optional

import jax

#: parallel/mesh.py's axis convention: batch over 'data', features (conv
#: channels, attention heads) over 'model'.
DATA_AXIS, MODEL_AXIS = "data", "model"


def context_mesh():
    """The multi-device mesh the caller is being traced under, or None
    when there is nothing to split over: no mesh context, one device, or
    already inside a fully manual `shard_map` (the ring-attention hops)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.are_all_axes_manual:
        return None
    return mesh


def split_axis(name: str, dim: int, quantum: int = 1) -> Optional[str]:
    """`name` if the context mesh has that axis and it splits `dim` into
    equal shards that are multiples of `quantum`; else None (the dim
    stays whole on every device)."""
    mesh = context_mesh()
    size = mesh.shape.get(name, 1) if mesh is not None else 1
    return name if size > 1 and dim % (size * quantum) == 0 else None


def per_shard(fn, in_specs, out_specs):
    """`fn` run once per device on that device's shards under the context
    mesh (every mesh axis manual, which is what a Mosaic call needs); `fn`
    itself where there is no mesh to split over."""
    if context_mesh() is None:
        return fn
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)
