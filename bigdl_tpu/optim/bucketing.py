"""Size-bucketed gradient-exchange planning.

Parallax (arXiv 1808.02621) treats gradient exchange as a bandwidth
budget to overlap and shrink rather than a barrier; the TPU-native
translation for our explicit exchange plan (the elastic per-shard loop,
`DistriOptimizer._optimize_elastic_impl`, whose `_build_bucket_add`
chains feed `_build_elastic_update`) is: split the gradient tree
into size-bounded buckets ordered REVERSE-topologically (output-side
layers' gradients exist first during the backward pass, and the flat
param order follows the forward build), then launch each bucket's
cross-shard reduction as soon as that shard's results are dispatched —
chained by donation, never by `jax.block_until_ready` — so the lead
device reduces shard i's buckets while shard i+1's backward still runs.

The SPMD (single fused step) path needs none of this: XLA's SPMD
partitioner inserts per-parameter all-reduces and its combiner/latency-
hiding scheduler owns the bucketing there (see ParallelOptimizer's
docstring); this module is the same discipline for the exchange we
schedule ourselves.

Determinism: a bucket's accumulator is seeded from shard 0 and adds
shards 1..R-1 in logical order — per leaf exactly the sequential
reduction order of the barrier combine, so bucketed and barrier
exchanges are BIT-identical (the elastic replay contract survives with
bucketing on; suite-asserted).

Compile discipline: one jitted accumulate executable per distinct bucket
LAYOUT (the tuple of leaf shapes/dtypes), reused every shard and every
step — no recompile storm (suite-asserted via the compile-telemetry
records).
"""

from __future__ import annotations

from typing import Any, List, Sequence, Tuple

import jax
import numpy as np


class GradientBucketPlan:
    """Reverse-topological, size-bounded bucketing of a gradient pytree.

    Built once per run from the (placed) parameter tree; `split` slices a
    same-structure gradient tree into per-bucket leaf tuples, `join`
    reassembles the full tree from per-bucket results.
    """

    def __init__(self, params_tree: Any, bucket_bytes: int = 4 * 2 ** 20):
        leaves, self._treedef = jax.tree_util.tree_flatten(params_tree)
        self.n_leaves = len(leaves)
        self.bucket_bytes = int(bucket_bytes)
        sizes = [int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                 if hasattr(l, "shape") else 0 for l in leaves]
        # reverse of the flat (forward/topological) order: the bucket that
        # fills first is the one whose gradients the backward produces
        # first, so its exchange overlaps the rest of the backward
        order = list(range(self.n_leaves))[::-1]
        self.buckets: List[Tuple[int, ...]] = []
        cur: List[int] = []
        cur_bytes = 0
        for i in order:
            if cur and cur_bytes + sizes[i] > self.bucket_bytes:
                self.buckets.append(tuple(cur))
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += sizes[i]
        if cur:
            self.buckets.append(tuple(cur))
        #: distinct (shape, dtype) layouts — the compile budget: one
        #: accumulate executable per entry, however many steps run
        self.layouts = sorted({
            tuple((tuple(leaves[i].shape), str(leaves[i].dtype))
                  for i in b)
            for b in self.buckets})
        self.total_bytes = sum(sizes)

    def __len__(self) -> int:
        return len(self.buckets)

    def split(self, tree: Any) -> List[Tuple]:
        """Per-bucket leaf tuples of a tree with the plan's structure."""
        leaves = jax.tree_util.tree_flatten(tree)[0]
        if len(leaves) != self.n_leaves:
            raise ValueError(
                f"tree has {len(leaves)} leaves; plan was built for "
                f"{self.n_leaves}")
        return [tuple(leaves[i] for i in b) for b in self.buckets]

    def join(self, bucket_leaves: Sequence[Sequence]) -> Any:
        """Inverse of `split`: reassemble the full tree."""
        flat: List = [None] * self.n_leaves
        for b, vals in zip(self.buckets, bucket_leaves):
            for i, v in zip(b, vals):
                flat[i] = v
        return jax.tree_util.tree_unflatten(self._treedef, flat)

    def describe(self) -> dict:
        """Telemetry-ready summary of the plan."""
        return {"n_buckets": len(self.buckets),
                "n_layouts": len(self.layouts),
                "bucket_bytes": self.bucket_bytes,
                "total_bytes": self.total_bytes}
