"""The program's side of the `lfm2-8b-a1b` configuration: the repo's
`DecoderLM` built from the configuration's `layer_types` at the widths
of LiquidAI/LFM2-8B-A1B (gated short convolutions beside grouped-query
attention with an RMSNorm a head on q and k; the first
`num_dense_layers` layers a dense SwiGLU FFN, the rest sigmoid-routed
SwiGLU experts whose router reads the FFN's own normed input; the head
tied to the embedding), served through `GenerationEngine`, over the
benchmark's flat weights (benchmarks/reference/lfm2-8b-a1b.py)."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

_NORMS = (("ln1.g", ("ln1", "weight")), ("ln2.g", ("ln2", "weight")))
_CONV = (("win", ("attn", "in_proj")), ("conv", ("attn", "conv")),
         ("wout", ("attn", "out_proj")))
_ATTENTION = (("wq", ("attn", "wq")), ("wk", ("attn", "wk")),
              ("wv", ("attn", "wv")), ("wo", ("attn", "wo")),
              ("qn.g", ("attn", "q_norm")), ("kn.g", ("attn", "k_norm")))
_DENSE = tuple((n, ("ffn", n)) for n in ("wg", "wu", "wd"))
_EXPERTS = (("router", ("router",)), ("router_bias", ("router_bias",))) \
    + tuple((n, ("experts", n)) for n in ("wg", "wu", "wd"))


class Adapter:
    kind = "lm"

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any]):
        from bigdl_tpu.models.decoder import DecoderLM, ExpertsKind, LayerSpec
        self.cfg, self.mix = cfg, mix
        n, self.dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
        self.kinds = list(cfg["layer_types"][:n])
        theta = float(cfg["rope_theta"])

        def spec(i, kind):
            mixer = dict(mixer="short_conv") if kind == "conv" \
                else dict(rope_base=theta)
            if i < self.dense:
                return LayerSpec(ffn="dense", **mixer)
            return LayerSpec(router_reads="ffn", **mixer)
        heads = cfg["num_attention_heads"]
        self.model = DecoderLM(
            cfg["vocab_size"], embed_dim=cfg["hidden_size"], n_head=heads,
            n_kv_head=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // heads,
            layers=[spec(i, k) for i, k in enumerate(self.kinds)],
            n_experts=cfg["num_experts"],
            expert_dim=cfg["moe_intermediate_size"],
            top_k=cfg["num_experts_per_tok"], eps=cfg["norm_eps"],
            max_len=cfg["max_position_embeddings"],
            cache_dtype=jnp.dtype(cfg["serving"]["cache_dtype"]),
            ffn_dim=cfg["intermediate_size"], qk_norm=True,
            qk_norm_per_head=True, conv_taps=cfg["conv_L_cache"],
            tie_head=True,
            experts=ExpertsKind(gate="silu", scoring="sigmoid",
                                scale=cfg["routed_scaling_factor"]))

    def _names(self):
        """(flat name, path in the program's tree) of every leaf."""
        out = [("embed", ("embed",)), ("norm.g", ("norm", "weight"))]
        for i, kind in enumerate(self.kinds):
            leaves = _NORMS + (_CONV if kind == "conv" else _ATTENTION) \
                + (_DENSE if i < self.dense else _EXPERTS)
            out += [(f"l{i}.{n}", (f"block{i}",) + path)
                    for n, path in leaves]
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
