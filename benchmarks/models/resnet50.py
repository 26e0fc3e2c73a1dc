"""The program's side of the `resnet50` configuration.

Builds `bigdl_tpu.models.resnet.ResNet50` with the criterion and
optimizer the configuration states, and carries the benchmark's flat
weights (benchmarks/reference/resnet50.py makes them from the seed) into
the program's parameter tree and back. The tree is assembled by walking
the program's containers in order, so `model.init`, which builds 161
leaves one eager call at a time, is never run.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax


def _walk(module, path, leaves, out):
    """Assign the flat leaves, in layout order, to the modules that hold
    parameters; returns the module's parameter subtree."""
    import bigdl_tpu.nn as nn
    if hasattr(module, "children") and hasattr(module, "_child_keys"):
        return {key: _walk(child, path + (key,), leaves, out)
                for key, child in zip(module._child_keys, module.children)}
    if isinstance(module, nn.SpatialConvolution):
        slots = ("weight",)
    elif isinstance(module, (nn.SpatialBatchNormalization, nn.Linear)):
        slots = ("weight", "bias")
    else:
        return {}
    sub = {}
    for slot in slots:
        name = next(leaves)
        out.append((name, path + (slot,)))
        sub[slot] = name
    return sub


class Adapter:
    kind = "train"

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any]):
        from bigdl_tpu.models.resnet import ResNet50
        from benchmarks.files import load_py
        self.cfg, self.mix = cfg, mix
        self.model = ResNet50(class_num=cfg["num_classes"],
                              s2d_stem=cfg["program"]["s2d_stem"])
        layout = load_py("reference", cfg["reference"]).layout(cfg)
        self.paths: List[Tuple[str, Tuple[str, ...]]] = []
        self._skeleton = _walk(self.model, (), iter(n for n, _, _ in layout),
                               self.paths)
        if len(self.paths) != len(layout):
            raise RuntimeError(
                f"the program's ResNet-50 holds {len(self.paths)} leaves, "
                f"the reference's layout {len(layout)}")

    def to_program(self, weights: Dict[str, Any]):
        """The program's parameter tree over the flat `weights`."""
        return jax.tree_util.tree_map(lambda name: weights[name],
                                      self._skeleton)

    def from_program(self, tree) -> Dict[str, Any]:
        """Flat name -> leaf of a tree shaped like the program's."""
        out = {}
        for name, path in self.paths:
            node = tree
            for key in path:
                node = node[key]
            out[name] = node
        return out

    def criterion(self):
        import bigdl_tpu.nn as nn
        return nn.ClassNLLCriterion()

    def optim_method(self):
        import bigdl_tpu.optim as optim
        o = self.cfg["optimizer"]
        return optim.SGD(learning_rate=o["learning_rate"],
                         momentum=o["momentum"], dampening=o["dampening"])

    def first_gradient(self, opt_state) -> Dict[str, Any]:
        """The first gradient as the optimizer got it, from its state
        after one step: velocity_1 = (1 - dampening) * g_1."""
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["dampening"])
        return {k: v * scale for k, v in
                self.from_program(opt_state["velocity"]).items()}

    def items_per_row(self) -> int:
        return 1
