"""The program's side of the `kanana-2-30b-a3b` configuration: the
repo's `DecoderLM` built from the configuration's keys at the widths of
kakaocorp/kanana-2-30b-a3b-instruct-2601 (latent attention in every
layer; the first `first_k_dense_replace` layers a dense SiLU FFN, the
rest sigmoid-routed SiLU experts beside a shared expert, their router
reading the FFN's own normed input), served through `GenerationEngine`,
over the benchmark's flat weights
(benchmarks/reference/kanana-2-30b-a3b.py)."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

_BLOCK = (("ln1.g", ("ln1", "weight")), ("ln2.g", ("ln2", "weight")),
          ("wq", ("attn", "wq")), ("wkva", ("attn", "wkva")),
          ("kvn.g", ("attn", "kv_norm")), ("wo", ("attn", "wo")))
_DENSE = tuple((n, ("ffn", n)) for n in ("wg", "wu", "wd"))
_EXPERTS = (("router", ("router",)), ("router_bias", ("router_bias",))) \
    + tuple((n, ("experts", n)) for n in ("wg", "wu", "wd")) \
    + (("wsg", ("shared", "wg")), ("wsu", ("shared", "wu")),
       ("wsd", ("shared", "wd")))


class Adapter:
    kind = "lm"

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any]):
        from bigdl_tpu.models.decoder import (DecoderLM, ExpertsKind,
                                              LatentDims, LayerSpec)
        self.cfg, self.mix = cfg, mix
        n, self.dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
        theta = float(cfg["rope_theta"])
        shared = cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
        layers = [LayerSpec(mixer="latent", rope_base=theta, ffn="dense")
                  if i < self.dense else
                  LayerSpec(mixer="latent", rope_base=theta, shared=shared,
                            router_reads="ffn") for i in range(n)]
        heads = cfg["num_attention_heads"]
        self.model = DecoderLM(
            cfg["vocab_size"], embed_dim=cfg["hidden_size"], n_head=heads,
            n_kv_head=heads, head_dim=cfg["qk_head_dim"], layers=layers,
            n_experts=cfg["n_routed_experts"],
            expert_dim=cfg["moe_intermediate_size"],
            top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
            max_len=cfg["max_position_embeddings"],
            cache_dtype=jnp.dtype(cfg["serving"]["cache_dtype"]),
            ffn_dim=cfg["intermediate_size"],
            latent=LatentDims(cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                              cfg["kv_lora_rank"]),
            experts=ExpertsKind(gate="silu", scoring=cfg["scoring_func"],
                                scale=cfg["routed_scaling_factor"]))

    def _names(self):
        """(flat name, path in the program's tree) of every leaf but the
        latent's up-projection."""
        out = [("embed", ("embed",)), ("head", ("head",)),
               ("norm.g", ("norm", "weight"))]
        for i in range(self.cfg["num_hidden_layers"]):
            leaves = _BLOCK + (_DENSE if i < self.dense else _EXPERTS)
            out += [(f"l{i}.{n}", (f"block{i}",) + path)
                    for n, path in leaves]
        return out

    def to_program(self, weights: Dict[str, Any]):
        cfg = self.cfg
        tree: Dict[str, Any] = {}
        for name, path in self._names():
            node = tree
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = weights[name]
        # the published `kv_b_proj` holds each head's key and value
        # columns side by side; the program keeps the two apart
        h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
        for i in range(cfg["num_hidden_layers"]):
            wkvb = weights[f"l{i}.wkvb"]
            per_head = wkvb.reshape(wkvb.shape[0], h, -1)
            attn = tree[f"block{i}"]["attn"]
            attn["wuk"] = per_head[:, :, :nope].reshape(wkvb.shape[0], -1)
            attn["wuv"] = per_head[:, :, nope:].reshape(wkvb.shape[0], -1)
        return tree

    def served_params(self, weights: Dict[str, Any]):
        """The tree the engine serves: the reference keeps every leaf in
        the type the configuration serves it in already."""
        return self.to_program(weights)
