"""The program's side of the `smallthinker-21b` configuration: the
repo's `SparseDecoderLM` built from the configuration's layer pattern at
the widths of PowerInfer/SmallThinker-21BA3B-Instruct, served through
`GenerationEngine`, over the benchmark's flat weights
(benchmarks/reference/smallthinker-21b.py)."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

_BLOCK = (("ln1.g", ("ln1", "weight")), ("ln2.g", ("ln2", "weight")),
          ("wq", ("attn", "wq")), ("wk", ("attn", "wk")),
          ("wv", ("attn", "wv")), ("wo", ("attn", "wo")),
          ("router", ("router",)), ("wg", ("experts", "wg")),
          ("wu", ("experts", "wu")), ("wd", ("experts", "wd")))


class Adapter:
    kind = "lm"

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any]):
        from bigdl_tpu.models.decoder import LayerSpec, SparseDecoderLM
        self.cfg, self.mix = cfg, mix
        n = cfg["num_hidden_layers"]
        layers = [LayerSpec(
            window=cfg["sliding_window_size"] if win else None,
            rope_base=float(cfg["rope_theta"]) if rot else None)
            for win, rot in zip(cfg["sliding_window_layout"][:n],
                                cfg["rope_layout"][:n])]
        self.model = SparseDecoderLM(
            cfg["vocab_size"], embed_dim=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            layers=layers, n_experts=cfg["moe_num_primary_experts"],
            expert_dim=cfg["moe_ffn_hidden_size"],
            top_k=cfg["moe_num_active_primary_experts"],
            eps=cfg["rms_norm_eps"], max_len=cfg["max_position_embeddings"],
            cache_dtype=jnp.dtype(cfg["serving"]["cache_dtype"]))

    def _names(self):
        """(flat name, path in the program's tree) of every leaf."""
        out = [("embed", ("embed",)), ("head", ("head",)),
               ("norm.g", ("norm", "weight"))]
        for i in range(self.cfg["num_hidden_layers"]):
            out += [(f"l{i}.{n}", (f"block{i}",) + path)
                    for n, path in _BLOCK]
        return out

    def to_program(self, weights: Dict[str, Any]):
        tree: Dict[str, Any] = {}
        for name, path in self._names():
            node = tree
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = weights[name]
        return tree

    def served_params(self, weights: Dict[str, Any]):
        """The tree the engine serves: the reference keeps every leaf in
        the type the configuration serves it in already."""
        return self.to_program(weights)
