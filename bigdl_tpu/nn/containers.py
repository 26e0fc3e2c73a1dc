"""Containers and graph execution.

Parity: reference `Container`/`Sequential`/`Concat`/`ConcatTable`/
`ParallelTable`/`CAddTable`-family (DL/nn/*.scala) and the graph containers
`Graph`/`StaticGraph` (DL/nn/Graph.scala:72, StaticGraph.scala:38). TPU-first
translation: containers compose pure `apply` functions; graph execution is a
pre-computed topological sort traced once under jit (no per-step scheduling).
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from bigdl_tpu.nn.module import ApplyContext, Module, Node, topo_sort
from bigdl_tpu.utils.table import T, Table


class Container(Module):
    """Base for modules that hold submodules (DL/nn/Container.scala)."""
    # bumped on every structural mutation anywhere; predictor caches store
    # the value they were built at, so a nested add() invalidates ancestors
    # whose _params dict was extended in place (identity check can't see it)
    _structure_epoch = 0

    def __init__(self, name: Optional[str] = None):
        super().__init__(name)
        self.children: List[Module] = []
        self._child_keys: List[str] = []

    def add(self, module: Module) -> "Container":
        key = f"{len(self.children)}_{module.name}"
        self.children.append(module)
        self._child_keys.append(key)
        self._predictor_cache = None  # structure changed
        Container._structure_epoch += 1
        if self._params is not None:
            # params already materialized (e.g. after a predict): extend
            # them for the new child so the facade keeps working
            self._params[key] = module._params if module._params is not None \
                else module.init(jax.random.PRNGKey(len(self.children)))
            self._state = {**self._state,
                           **{(key,) + k: v
                              for k, v in (module.state_init() or {}).items()}}
        return self

    def init(self, rng: jax.Array) -> Dict:
        params = {}
        for key, child in zip(self._child_keys, self.children):
            rng, sub = jax.random.split(rng)
            # a child pre-loaded with weights (set_params before add —
            # the interop loaders do this) keeps them; fresh init otherwise
            params[key] = child._params if child._params is not None \
                else child.init(sub)
        return params

    def _collect_state(self, out, path):
        for key, child in zip(self._child_keys, self.children):
            child._collect_state(out, path + (key,))

    def _apply_child(self, i: int, params: Dict, x, ctx: ApplyContext):
        key = self._child_keys[i]
        ctx.push(key)
        try:
            # freeze/stop-gradient gating lives in the subclass-wrapped
            # Module.apply itself (module.py __init_subclass__)
            return self.children[i].apply(params[key], x, ctx)
        finally:
            ctx.pop()


class Sequential(Container):
    """Feed-forward chain of children (DL/nn/Sequential.scala).

    Example:
        >>> import jax.numpy as jnp
        >>> from bigdl_tpu.nn import Sequential, Linear, ReLU, LogSoftMax
        >>> m = Sequential().add(Linear(4, 8)).add(ReLU()).add(Linear(8, 3))
        >>> out = m.add(LogSoftMax()).forward(jnp.ones((2, 4)))
        >>> out.shape
        (2, 3)
        >>> bool(jnp.allclose(jnp.exp(out).sum(1), 1.0, atol=1e-5))
        True
    """

    #: {index of a child: the `jax.named_scope` that child and those after
    #: it run under, up to the next entry}; set by `scope`
    _scopes: Dict[int, str] = {}

    def scope(self, name: str) -> "Sequential":
        """Run the children added from here on, up to the next call, under
        `jax.named_scope(name)`: the path a device trace's operations of
        those children carry. A BN+ReLU pair split by a scope's edge is
        not fused."""
        self._scopes = {**self._scopes, len(self.children): name}
        return self

    def apply(self, params, input, ctx):
        x = input
        edges = sorted({0, len(self.children), *self._scopes})
        for lo, hi in zip(edges, edges[1:]):
            name = self._scopes.get(lo)
            with jax.named_scope(name) if name else contextlib.nullcontext():
                x = self._apply_children(lo, hi, params, x, ctx)
        return x

    def _apply_children(self, i, n, params, x, ctx):
        """Children i to n - 1 in turn on `x`."""
        from bigdl_tpu.nn.fusion import (fusible_activation, fusible_bn,
                                         fusion_enabled)
        fuse = fusion_enabled()
        while i < n:
            child = self.children[i]
            if fuse and i + 1 < n and fusible_bn(child) \
                    and fusible_activation(self.children[i + 1]):
                # BN+ReLU adjacency: one fused elementwise tail
                # (ops/bn_relu_kernel.py) under the BN child's state path;
                # the ReLU child is parameter- and state-less, so skipping
                # its dispatch changes nothing but the op count
                key = self._child_keys[i]
                ctx.push(key)
                try:
                    x = child.apply_with_activation(params[key], x, ctx)
                finally:
                    ctx.pop()
                i += 2
                continue
            x = self._apply_child(i, params, x, ctx)
            i += 1
        return x


class ConcatTable(Container):
    """Apply each child to the same input, return a Table of outputs.

    Example:
        >>> import jax.numpy as jnp
        >>> from bigdl_tpu.nn import ConcatTable, Linear
        >>> m = ConcatTable().add(Linear(4, 2)).add(Linear(4, 3))
        >>> out = m.forward(jnp.ones((1, 4)))
        >>> (out[1].shape, out[2].shape)  # Table is 1-based
        ((1, 2), (1, 3))
    """

    def apply(self, params, input, ctx):
        return T(*[self._apply_child(i, params, input, ctx)
                   for i in range(len(self.children))])


class ParallelTable(Container):
    """Apply child i to input[i] (Table input, Table output)."""

    def apply(self, params, input, ctx):
        vals = list(input) if isinstance(input, Table) else list(input)
        return T(*[self._apply_child(i, params, x, ctx)
                   for i, x in enumerate(vals)])


class MapTable(Container):
    """Apply the single shared child to every element of the input table."""

    def apply(self, params, input, ctx):
        vals = list(input) if isinstance(input, Table) else list(input)
        return T(*[self._apply_child(0, params, x, ctx) for x in vals])


class Concat(Container):
    """Concat children outputs along `dimension` (reference 1-based, default
    dim 2 = channel under NCHW batch layouts; here axis is 0-based)."""

    def __init__(self, axis: int = 1, name=None):
        super().__init__(name)
        self.axis = axis

    def apply(self, params, input, ctx):
        outs = [self._apply_child(i, params, input, ctx)
                for i in range(len(self.children))]
        return jnp.concatenate(outs, axis=self.axis)


class Bottle(Container):
    """Fold leading dims so the child sees `n_input_dim`-D input, then restore
    them (reference DL/nn/Bottle.scala). n_input_dim counts the child's
    expected rank including batch (Torch convention)."""

    def __init__(self, module: Module, n_input_dim: int = 2, name=None):
        super().__init__(name)
        self.add(module)
        if n_input_dim < 1:
            raise ValueError("n_input_dim must be >= 1")
        self.n_input_dim = n_input_dim

    def apply(self, params, input, ctx):
        shape = input.shape
        if len(shape) <= self.n_input_dim:
            return self._apply_child(0, params, input, ctx)
        trail = self.n_input_dim - 1
        lead = shape[:len(shape) - trail]
        x = jnp.reshape(input, (-1,) + (shape[len(shape) - trail:] if trail else ()))
        y = self._apply_child(0, params, x, ctx)
        return jnp.reshape(y, lead + y.shape[1:])


class Remat(Container):
    """Rematerialize the wrapped module under autodiff (jax.checkpoint).

    Beyond-parity TPU feature (SURVEY.md §7 design brief: "use
    jax.checkpoint to trade FLOPs for memory"): activations inside the
    wrapped subtree are recomputed during the backward pass instead of
    being stored, cutting peak HBM for deep blocks (wrap ResNet stages /
    transformer blocks). Forward math, BN state propagation, and rng
    threading are unchanged — the wrapper builds a pure inner function
    (params, x, rng, state) -> (out, new_state) so XLA can recompute it.
    """

    def __init__(self, module: Module, name=None):
        super().__init__(name)
        self.add(module)

    def apply(self, params, input, ctx):
        key = self._child_keys[0]
        child = self.children[0]
        base_path = ctx.path + (key,)
        state_in = {k: v for k, v in ctx.state.items()
                    if k[:len(base_path)] == base_path}
        # derive the subtree rng OUTSIDE the checkpointed fn so it is a
        # plain input (deterministic, replayable on recompute)
        sub_rng = ctx.make_rng() if ctx._rng is not None else None
        training = ctx.training

        def inner(p, x, rng, state):
            sub = ApplyContext(training=training, rng=rng, state=state)
            sub._path = list(base_path)
            out = child.apply(p, x, sub)
            return out, sub.new_state

        out, new_state = jax.checkpoint(inner)(
            params[key], input, sub_rng, state_in)
        ctx.new_state.update(new_state)
        return out


# ---------------------------------------------------------------------- #
# element-wise table reducers (CAddTable family)
# ---------------------------------------------------------------------- #

class _TableReduce(Module):
    def _reduce(self, a, b):
        raise NotImplementedError

    def apply(self, params, input, ctx):
        vals = list(input)
        out = vals[0]
        for v in vals[1:]:
            out = self._reduce(out, v)
        return out


class CAddTable(_TableReduce):
    """Elementwise sum of a Table of tensors (DL/nn/CAddTable.scala)."""
    def _reduce(self, a, b):
        return a + b


class CSubTable(_TableReduce):
    """Elementwise difference of two Table entries (DL/nn/CSubTable.scala)."""
    def _reduce(self, a, b):
        return a - b


class CMulTable(_TableReduce):
    """Elementwise product of a Table of tensors (DL/nn/CMulTable.scala)."""
    def _reduce(self, a, b):
        return a * b


class CDivTable(_TableReduce):
    """Elementwise quotient of two Table entries (DL/nn/CDivTable.scala)."""
    def _reduce(self, a, b):
        return a / b


class CMaxTable(_TableReduce):
    """Elementwise max over a Table of tensors (DL/nn/CMaxTable.scala)."""
    def _reduce(self, a, b):
        return jnp.maximum(a, b)


class CMinTable(_TableReduce):
    """Elementwise min over a Table of tensors (DL/nn/CMinTable.scala)."""
    def _reduce(self, a, b):
        return jnp.minimum(a, b)


class CAveTable(Module):
    """Elementwise mean of a Table of tensors (DL/nn/CAveTable.scala)."""
    def apply(self, params, input, ctx):
        vals = list(input)
        return sum(vals) / float(len(vals))


class JoinTable(Module):
    """Concatenate table elements along an axis (0-based; reference
    `JoinTable` uses 1-based dimension + nInputDims).

    Example:
        >>> import jax.numpy as jnp
        >>> from bigdl_tpu.nn import JoinTable
        >>> from bigdl_tpu.utils.table import T
        >>> JoinTable(1).forward(T(jnp.ones((2, 3)), jnp.ones((2, 5)))).shape
        (2, 8)
    """

    def __init__(self, axis: int = 1, name=None):
        super().__init__(name)
        self.axis = axis

    def apply(self, params, input, ctx):
        return jnp.concatenate(list(input), axis=self.axis)


class SplitTable(Module):
    """Split a tensor along a dim into a Table (DL/nn/SplitTable.scala)."""
    def __init__(self, axis: int = 1, name=None):
        super().__init__(name)
        self.axis = axis

    def apply(self, params, input, ctx):
        n = input.shape[self.axis]
        parts = jnp.split(input, n, axis=self.axis)
        return T(*[jnp.squeeze(p, axis=self.axis) for p in parts])


class FlattenTable(Module):
    """Flatten nested Tables into one flat Table (DL/nn/FlattenTable.scala)."""
    def apply(self, params, input, ctx):
        flat = []

        def rec(t):
            if isinstance(t, Table):
                for v in t:
                    rec(v)
            else:
                flat.append(t)

        rec(input)
        return T(*flat)


class SelectTable(Module):
    """Select element `index` (1-based like the reference) from a table."""

    def __init__(self, index: int, name=None):
        super().__init__(name)
        self.index = index

    def apply(self, params, input, ctx):
        vals = list(input)
        i = self.index - 1 if self.index > 0 else self.index
        return vals[i]


class NarrowTable(Module):
    """Slice a Table to [offset, offset+length) (DL/nn/NarrowTable.scala)."""
    def __init__(self, offset: int, length: int = 1, name=None):
        super().__init__(name)
        self.offset, self.length = offset, length

    def apply(self, params, input, ctx):
        vals = list(input)
        return T(*vals[self.offset - 1: self.offset - 1 + self.length])


class MixtureTable(Module):
    """input = T(gates [B,K], experts Table/Tensor); weighted sum of experts."""

    def apply(self, params, input, ctx):
        gates, experts = input[1], input[2]
        if isinstance(experts, Table):
            stacked = jnp.stack(list(experts), axis=1)  # [B, K, ...]
        else:
            stacked = experts
        g = gates.reshape(gates.shape + (1,) * (stacked.ndim - gates.ndim))
        return jnp.sum(stacked * g, axis=1)


# ---------------------------------------------------------------------- #
# Graph
# ---------------------------------------------------------------------- #

class Input(Module):
    """Graph input placeholder (reference DL/nn/Input.scala)."""

    def apply(self, params, input, ctx):
        return input


def InputNode(name: Optional[str] = None) -> Node:
    """Create a graph input placeholder node (DL/nn/Input.scala)."""
    return Node(Input(name or "Input"), [])


class Graph(Container):
    """Static DAG container (reference StaticGraph.scala:38).

    Build with the node DSL:
        inp = InputNode()
        h = Linear(10, 4).inputs(inp)
        out = Linear(4, 2).inputs(h)
        model = Graph([inp], [out])

    Example:
        >>> import jax.numpy as jnp
        >>> from bigdl_tpu.nn import Graph, InputNode, Linear, ReLU
        >>> inp = InputNode()
        >>> h = Linear(6, 4).inputs(inp)
        >>> out = Linear(4, 2).inputs(ReLU().inputs(h))
        >>> Graph([inp], [out]).forward(jnp.ones((3, 6))).shape
        (3, 2)

    Execution order is a topo sort computed once at construction; under jit
    the whole DAG is traced into a single XLA computation, so there is no
    runtime scheduler (the reference's Scheduler/FrameManager dynamic path is
    unnecessary under XLA — data-dependent control flow must use lax.cond).
    """

    def __init__(self, inputs: Sequence[Node], outputs: Sequence[Node], name=None):
        super().__init__(name)
        self.input_nodes = list(inputs)
        self.output_nodes = list(outputs)
        self.exec_order = topo_sort(self.output_nodes)
        for n in self.exec_order:
            self.children.append(n.module)
            self._child_keys.append(n.key)

    def _fusion_plan(self):
        """BN->ReLU adjacency over the DAG: a ReLU node whose sole input
        is a single-consumer BN node (and the BN is not itself a graph
        output) fuses. Returns (fused_bn_ids, skip: relu_id -> bn_id).
        Re-computed per apply — trace-time cost only."""
        from bigdl_tpu.nn.fusion import fusible_activation, fusible_bn
        consumers: Dict[int, int] = {}
        for node in self.exec_order:
            for p in node.prev:
                consumers[p.id] = consumers.get(p.id, 0) + 1
        out_ids = {n.id for n in self.output_nodes}
        fused, skip = set(), {}
        for node in self.exec_order:
            if fusible_activation(node.module) and len(node.prev) == 1:
                p = node.prev[0]
                if (fusible_bn(p.module) and consumers.get(p.id) == 1
                        and p.id not in out_ids):
                    fused.add(p.id)
                    skip[node.id] = p.id
        return fused, skip

    def apply(self, params, input, ctx):
        from bigdl_tpu.nn.fusion import fusion_enabled
        if isinstance(input, Table):
            inputs = list(input)
        elif isinstance(input, (list, tuple)):
            inputs = list(input)
        else:
            inputs = [input]
        if len(inputs) != len(self.input_nodes):
            raise ValueError(
                f"graph expects {len(self.input_nodes)} inputs, got {len(inputs)}")
        fused, skip = self._fusion_plan() if fusion_enabled() else (set(), {})
        values: Dict[int, any] = {}
        for node, x in zip(self.input_nodes, inputs):
            values[node.id] = x
        for i, node in enumerate(self.exec_order):
            if node.id in skip:
                # the ReLU already ran inside its BN's fused tail
                values[node.id] = values[skip[node.id]]
                continue
            if not node.prev:
                x = values.get(node.id)
            elif len(node.prev) == 1:
                x = values[node.prev[0].id]
            else:
                x = T(*[values[p.id] for p in node.prev])
            ctx.push(node.key)
            try:
                if node.id in fused:
                    values[node.id] = node.module.apply_with_activation(
                        params[node.key], x, ctx)
                else:
                    values[node.id] = node.module.apply(params[node.key], x,
                                                        ctx)
            finally:
                ctx.pop()
        outs = [values[n.id] for n in self.output_nodes]
        return outs[0] if len(outs) == 1 else T(*outs)


# Reference StaticGraph.scala IS this container (DynamicGraph is the
# data-dependent variant in dynamic_graph.py); export the name for parity.
StaticGraph = Graph


class Identity(Module):
    """Pass input through unchanged (DL/nn/Identity.scala)."""
    def apply(self, params, input, ctx):
        return input


class Echo(Module):
    """Debug pass-through (reference DL/nn/Echo.scala); prints at trace time."""

    def apply(self, params, input, ctx):
        shape = getattr(input, "shape", None)
        print(f"[Echo {self.name}] shape={shape}")
        return input


class BifurcateSplitTable(Module):
    """Split a tensor into two halves along `axis`
    (DL/nn/BifurcateSplitTable.scala; 0-based axis here)."""

    def __init__(self, axis: int = 1, name=None):
        super().__init__(name)
        self.axis = axis

    def apply(self, params, input, ctx):
        n = input.shape[self.axis]
        left = n // 2
        a, b = jnp.split(input, [left], axis=self.axis)
        return T(a, b)
