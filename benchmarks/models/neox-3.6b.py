"""The program's side of the `neox-3.6b` configuration: the repo's
`TransformerLM` at the widths of rinna/japanese-gpt-neox-3.6b, trained
through `optim.Optimizer` or served through `GenerationEngine`, over the
benchmark's flat weights (benchmarks/reference/neox-3.6b.py)."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

_ATTN = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo")
_MLP = ("w1", "b1", "w2", "b2")


class Adapter:
    kind = "lm"

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any]):
        from bigdl_tpu.models.transformer import TransformerLM
        self.cfg, self.mix = cfg, mix
        cache_dtype = jnp.dtype(cfg["serving"]["cache_dtype"])

        class ServedLM(TransformerLM):
            # the engine calls init_cache(slots, max_len); the cache type
            # the configuration states goes in through the model's own
            # `dtype` argument
            def init_cache(self, slots, max_len, dtype=cache_dtype):
                return super().init_cache(slots, max_len, dtype)

        self.model = ServedLM(
            cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            n_layer=cfg["num_hidden_layers"],
            n_head=cfg["num_attention_heads"],
            mlp_ratio=cfg["intermediate_size"] // cfg["hidden_size"],
            max_len=cfg["max_position_embeddings"],
            use_flash=cfg["program"]["use_flash"])

    def _names(self):
        """(flat name, path in the program's tree) of every leaf."""
        out = [("embed", ("embed",)), ("head", ("head",))]
        for i in range(self.cfg["num_hidden_layers"]):
            b = f"block{i}"
            out += [(f"l{i}.{n}", (b, "attn", n)) for n in _ATTN]
            out += [(f"l{i}.{n}", (b, n)) for n in _MLP]
            for ln in ("ln1", "ln2"):
                out += [(f"l{i}.{ln}.g", (b, ln, "weight")),
                        (f"l{i}.{ln}.b", (b, ln, "bias"))]
        return out

    def to_program(self, weights: Dict[str, Any]):
        tree: Dict[str, Any] = {}
        for name, path in self._names():
            node = tree
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = weights[name]
        return tree

    def from_program(self, tree) -> Dict[str, Any]:
        out = {}
        for name, path in self._names():
            node = tree
            for key in path:
                node = node[key]
            out[name] = node
        return out

    def criterion(self):
        import bigdl_tpu.nn as nn
        return nn.TimeDistributedMaskCriterion(nn.ClassNLLCriterion())

    def optim_method(self):
        import bigdl_tpu.optim as optim
        o = self.cfg["optimizer"]
        return optim.Adam(learning_rate=o["learning_rate"], beta1=o["beta1"],
                          beta2=o["beta2"], epsilon=o["epsilon"])

    def first_gradient(self, opt_state) -> Dict[str, Any]:
        """m_1 = (1 - beta1) * g_1."""
        scale = 1.0 / (1.0 - self.cfg["optimizer"]["beta1"])
        return {k: v * scale for k, v in
                self.from_program(opt_state["m"]).items()}

    def items_per_row(self) -> int:
        return int(self.mix["sequence"])

    def served_params(self, weights: Dict[str, Any]):
        """The tree the engine serves: the flat weights in the type the
        configuration serves them in."""
        dt = jnp.dtype(self.cfg["serving"]["weight_dtype"])
        return self.to_program({k: v.astype(dt) for k, v in weights.items()})
