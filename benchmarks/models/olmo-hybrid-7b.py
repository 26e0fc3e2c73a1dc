"""The program's side of the `olmo-hybrid-7b` configuration: the repo's
`DecoderLM` built from the configuration's `layer_types` at the widths
of allenai/Olmo-Hybrid-7B (gated delta-rule layers beside full-attention
ones, a dense gated FFN, the norms on each sub-layer's output), served
through `GenerationEngine`, over the benchmark's flat weights
(benchmarks/reference/olmo-hybrid-7b.py)."""

from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

_FFN = (("n1.g", ("ln1", "weight")), ("n2.g", ("ln2", "weight")),
        ("wg", ("ffn", "wg")), ("wu", ("ffn", "wu")), ("wd", ("ffn", "wd")))
_FULL = tuple((n, ("attn", n)) for n in ("wq", "wk", "wv", "wo")) \
    + (("qn.g", ("attn", "q_norm")), ("kn.g", ("attn", "k_norm")))
_LINEAR = tuple((n, ("attn", n)) for n in (
    "wq", "wk", "wv", "wz", "wa", "wb", "a_log", "dt_bias", "wo")) \
    + (("gn.g", ("attn", "norm")),)


class Adapter:
    kind = "lm"

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any]):
        from bigdl_tpu.models.decoder import DecoderLM, LayerSpec
        self.cfg, self.mix = cfg, mix
        self.kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
        layers = [LayerSpec(mixer="gated_delta" if k == "linear_attention"
                            else "attention", ffn="dense", norm="output")
                  for k in self.kinds]
        heads = cfg["num_attention_heads"]
        self.model = DecoderLM(
            cfg["vocab_size"], embed_dim=cfg["hidden_size"], n_head=heads,
            n_kv_head=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // heads, layers=layers,
            eps=cfg["rms_norm_eps"], max_len=cfg["max_position_embeddings"],
            cache_dtype=jnp.dtype(cfg["serving"]["cache_dtype"]),
            ffn_dim=cfg["intermediate_size"], qk_norm=True,
            linear_heads=cfg["linear_num_value_heads"],
            linear_key_dim=cfg["linear_key_head_dim"],
            linear_value_dim=cfg["linear_value_head_dim"],
            conv_taps=cfg["linear_conv_kernel_dim"])

    def _names(self):
        """(flat name, path in the program's tree) of every leaf but the
        convolutions' taps."""
        out = [("embed", ("embed",)), ("head", ("head",)),
               ("norm.g", ("norm", "weight"))]
        for i, kind in enumerate(self.kinds):
            leaves = _FFN + (_LINEAR if kind == "linear_attention" else _FULL)
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
        # the reference convolves q, k and v apart; the program's one
        # depthwise convolution runs over the three side by side
        for i, kind in enumerate(self.kinds):
            if kind == "linear_attention":
                tree[f"block{i}"]["attn"]["conv"] = jnp.concatenate(
                    [weights[f"l{i}.c{n}"] for n in "qkv"], axis=1)
        return tree

    def served_params(self, weights: Dict[str, Any]):
        """The tree the engine serves: the reference keeps every leaf in
        the type the configuration serves it in already."""
        return self.to_program(weights)
