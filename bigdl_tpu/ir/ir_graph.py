"""Graph-level IR and backend conversion passes.

Parity: `DL/utils/intermediate/` (IRGraph.scala, IRElement.scala,
BlasToIR/IRToDnn/IRToBlas, ConversionUtils.scala — SURVEY.md C12) and the
MKL-DNN `Fusion` pass (DL/nn/mkldnn/Fusion.scala: conv+bn, conv+relu). The
reference uses the IR to retarget one model between its two CPU backends.
On TPU the "backends" are XLA-default vs Pallas-preferred kernels
(Engine.config['engine_type']), and the profitable graph rewrites are the
ones XLA can NOT do itself because they change the parameter values:

- **fold_batchnorm**: at inference, BN following Conv/Linear folds into the
  weights (w' = w * gamma/sqrt(var+eps)), removing a whole HBM round-trip.
  (conv+relu fusion, by contrast, XLA already does — no pass needed.)
- **drop_inference_noise**: Dropout/GaussianNoise/GaussianDropout vanish at
  inference instead of tracing an identity with an unused RNG.

`ConversionUtils.convert` is called on the inference path (Predictor) the
way the reference calls it in DistriOptimizer.scala:552.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn.module import Module


class IRElement:
    """One IR node: module type + ctor attrs + parameter subtree."""

    def __init__(self, op_type: str, module: Module, params):
        self.op_type = op_type
        self.module = module
        self.params = params

    def __repr__(self):
        return f"IRElement({self.op_type})"


class IRGraph:
    """IR over a module tree (children order = execution order for
    Sequential chains; Graph containers carry their own wiring)."""

    def __init__(self, root: Module, params):
        self.root = root
        self.params = params

    @staticmethod
    def from_module(module: Module) -> "IRGraph":
        return IRGraph(module, module.ensure_params())

    def to_module(self) -> Module:
        self.root.set_params(self.params)
        return self.root

    def elements(self) -> List[IRElement]:
        """Flatten leaf modules in execution order."""
        from bigdl_tpu.nn.containers import Container, Graph
        out: List[IRElement] = []

        def walk(m, p):
            if isinstance(m, Graph):
                for n in m.exec_order:
                    walk(n.module, p.get(n.key, {}))
            elif isinstance(m, Container):
                for key, c in zip(m._child_keys, m.children):
                    walk(c, p.get(key, {}))
            else:
                out.append(IRElement(type(m).__name__, m, p))

        walk(self.root, self.params)
        return out


class ConversionUtils:
    """convert(model, inference=True) — run the IR passes appropriate to the
    engine type and phase (reference ConversionUtils.convert)."""

    @staticmethod
    def convert(module: Module, inference: bool = True,
                restatements: bool = True) -> Module:
        ir = IRGraph.from_module(module)
        if inference:
            _drop_inference_noise(ir)
            _fold_batchnorm(ir)
        if restatements:
            _restate_s2d_stem(ir)
        return ir.to_module()

    @staticmethod
    def apply_tpu_restatements(module: Module) -> Module:
        """Run only the math-preserving TPU restatement passes (safe for
        TRAINING too — they re-express compute, never change parameter
        values). Home for graph rewrites XLA won't do itself (round-4 review
        weak #6: adoption belongs here, not in model-code hand-edits)."""
        ir = IRGraph.from_module(module)
        _restate_s2d_stem(ir)
        return ir.to_module()


# ------------------------------------------------------------------ passes
_NOISE = ("Dropout", "GaussianNoise", "GaussianDropout", "SpatialDropout1D",
          "SpatialDropout2D", "SpatialDropout3D")


def _drop_inference_noise(ir: IRGraph):
    """Replace noise layers with Identity in add()-style containers."""
    from bigdl_tpu.nn.containers import Container, Graph
    import bigdl_tpu.nn as nn

    def walk(m, p):
        if isinstance(m, Graph):
            for n in m.exec_order:
                walk(n.module, p.get(n.key, {}))
            for i, n in enumerate(m.exec_order):
                if type(n.module).__name__ in _NOISE:
                    n.module = nn.Identity(name=n.module.name)
                    m.children[i] = n.module
                    p[n.key] = {}
        elif isinstance(m, Container):
            for i, (key, c) in enumerate(
                    zip(list(m._child_keys), m.children)):
                if type(c).__name__ in _NOISE:
                    repl = nn.Identity(name=c.name)
                    m.children[i] = repl
                    new_key = f"{i}_{repl.name}"
                    m._child_keys[i] = new_key
                    p.pop(key, None)
                    p[new_key] = {}
                else:
                    walk(c, p.get(key, {}))

    walk(ir.root, ir.params)


def _fold_batchnorm(ir: IRGraph):
    """Fold an eval-mode BN into the immediately preceding Conv/Linear:
    w' = w * g, b' = (b - mean) * g + beta, g = gamma * rsqrt(var + eps)
    (the parameter-changing half of mkldnn Fusion.scala's conv+bn)."""
    from bigdl_tpu.nn.containers import Container, Graph, Sequential
    import bigdl_tpu.nn as nn

    def fold_pair(prev_mod, prev_params, bn_mod, bn_params, bn_state):
        gamma = np.asarray(bn_params.get(
            "weight", np.ones(bn_mod.n_output, np.float32)))
        beta = np.asarray(bn_params.get(
            "bias", np.zeros(bn_mod.n_output, np.float32)))
        mean = np.asarray(bn_state["mean"])
        var = np.asarray(bn_state["var"])
        g = gamma / np.sqrt(var + bn_mod.eps)
        w = np.asarray(prev_params["weight"])
        if isinstance(prev_mod, nn.SpatialConvolution):
            w2 = w * g.reshape(1, 1, 1, -1)          # HWIO, scale O
        else:                                         # Linear [in, out]
            w2 = w * g.reshape(1, -1)
        b = np.asarray(prev_params.get("bias",
                                       np.zeros(len(g), np.float32)))
        b2 = (b - mean) * g + beta
        prev_params["weight"] = jnp.asarray(w2)
        prev_params["bias"] = jnp.asarray(b2)
        return True

    def walk(m, p, state):
        if not isinstance(m, Container) or isinstance(m, Graph):
            # graph-container folding needs linear-chain detection; only
            # fold along Sequential chains (the common case; reference
            # Fusion likewise walks its sequential compile order)
            return
        if isinstance(m, Sequential):
            i = 1
            while i < len(m.children):
                prev, cur = m.children[i - 1], m.children[i]
                prev_key, cur_key = m._child_keys[i - 1], m._child_keys[i]
                is_prev_ok = type(prev) in (nn.SpatialConvolution, nn.Linear)
                is_bn = isinstance(cur, nn.BatchNormalization)
                bn_state = state.get((cur_key,)) if state else None
                # inference intent is stated by convert(inference=True);
                # per-child training_mode flags don't cascade from the root
                if is_prev_ok and is_bn and bn_state is not None:
                    if not prev.with_bias:
                        prev.with_bias = True  # folded bias appears
                        _patch_ctor_kwargs(prev, with_bias=True)
                    fold_pair(prev, p[prev_key], cur, p.get(cur_key, {}),
                              bn_state)
                    repl = nn.Identity(name=cur.name)
                    m.children[i] = repl
                    new_key = f"{i}_{repl.name}"
                    m._child_keys[i] = new_key
                    p.pop(cur_key, None)
                    p[new_key] = {}
                    state.pop((cur_key,), None)
                i += 1
        for key, c in zip(m._child_keys, m.children):
            sub_state = {k[1:]: v for k, v in (state or {}).items()
                         if k and k[0] == key}
            walk(c, p.get(key, {}), sub_state)

    walk(ir.root, ir.params, dict(ir.root._state or {}))
    # drop folded BN state entries from the root state
    ir.root._state = {k: v for k, v in (ir.root._state or {}).items()
                      if not _is_orphan_state(ir.root, k)}


def _restate_s2d_stem(ir: IRGraph):
    """Re-express an eligible stem conv through the 2x2 space-to-depth
    transform (`nn.SpaceToDepthStemConvolution`): bit-identical math and
    parameter tree, but the 7x7/s2-over-3-channels stem — the classic
    memory-bound MXU-hostile op — becomes a stride-1 conv over 4x the
    channels, which XLA tiles onto the 128-lane MXU far better.

    Eligibility (a real image stem, nothing else): a plain
    SpatialConvolution with square odd kernel k % 4 == 3, stride 2,
    SAME-style pad (k-1)//2, groups=1, NHWC, and a small input plane
    (<= 4 channels). Because the restated module's param tree has the
    SAME shapes, the swap is checkpoint-compatible in both directions.
    """
    from bigdl_tpu.nn.containers import Container, Graph
    import bigdl_tpu.nn as nn

    def eligible(c) -> bool:
        return (type(c) is nn.SpatialConvolution
                and c.kw == c.kh and c.kw % 4 == 3
                and c.sw == 2 and c.sh == 2
                and c.pad_w == c.pad_h == (c.kw - 1) // 2
                and c.groups == 1 and c.n_in <= 4
                and c.data_format == "NHWC")

    def restate(c) -> Module:
        repl = nn.SpaceToDepthStemConvolution(
            c.n_in, c.n_out, kernel=c.kw, with_bias=c.with_bias,
            weight_init=c.weight_init, bias_init=c.bias_init,
            name=c.name, dtype=c.dtype)
        repl._params = c._params
        return repl

    def walk(m):
        if isinstance(m, Graph):
            for i, n in enumerate(m.exec_order):
                if eligible(n.module):
                    n.module = restate(n.module)
                    m.children[i] = n.module
                else:
                    walk(n.module)
        elif isinstance(m, Container):
            for i, c in enumerate(m.children):
                if eligible(c):
                    # child key keeps the module's name, which restate
                    # preserves — the params dict needs no rekeying
                    m.children[i] = restate(c)
                else:
                    walk(c)

    walk(ir.root)


def _patch_ctor_kwargs(mod: Module, **updates):
    """Rewrite a module's captured ctor spec so the serializer rebuilds it
    with the given kwarg overrides (e.g. BN folding turns a bias-less layer
    into one WITH bias — the reconstruction must match or the folded bias
    tensor would be dropped on load)."""
    spec = getattr(mod, "_ctor_spec", None)
    if spec is None:
        return
    import inspect
    name, args, kwargs = spec
    try:
        sig = inspect.signature(type(mod).__init__)
        bound = sig.bind_partial(mod, *args, **kwargs)
        merged = {k: v for k, v in list(bound.arguments.items())[1:]}
        merged.pop("self", None)
        merged.update(updates)
        mod._ctor_spec = (name, (), merged)
    except TypeError:
        kwargs = dict(kwargs)
        kwargs.update(updates)
        mod._ctor_spec = (name, args, kwargs)


def _is_orphan_state(root, path: Tuple[str, ...]) -> bool:
    """True if `path` no longer resolves to a module in the tree."""
    from bigdl_tpu.nn.containers import Container, Graph
    m = root
    for part in path:
        if isinstance(m, Graph):
            nxt = next((n.module for n in m.exec_order if n.key == part),
                       None)
        elif isinstance(m, Container):
            nxt = next((c for k, c in zip(m._child_keys, m.children)
                        if k == part), None)
        else:
            nxt = None
        if nxt is None:
            return True
        m = nxt
    return False
