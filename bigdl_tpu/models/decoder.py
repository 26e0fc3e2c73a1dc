"""A decoder-only language model assembled from a layer pattern.

Each layer is a `LayerSpec`: what its attention keeps (a sliding
`window` or everything) and how it encodes position (a rotary base or
nothing at all). Every block is pre-norm with RMSNorm, grouped-query
attention (`nn.GroupedQueryAttention`) and dropless routed experts
(`nn.experts.RoutedExperts`) whose router reads the ATTENTION block's
normed input, before attention runs:

    h = norm1(x);  r = h @ router;  x = x + attn(h)
    u = norm2(x);  x = x + experts(u, routed by r)

The serving side is what `GenerationEngine` calls (`init_cache`,
`apply_prefill`, `apply_step`): the cache gives each layer the depth its
kind needs (nn/kv_cache.py), prefill takes the head over each prompt's
last real position only, and the cache pytree carries device-side
counters of the routing and of the window (`cache_stats`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.experts import RoutedExperts
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.normalization import RMSNorm


@dataclass(frozen=True)
class LayerSpec:
    """One layer of the pattern: `window` positions kept (None: all),
    `rope_base` of its rotary encoding (None: no positional encoding)."""
    window: Optional[int] = None
    rope_base: Optional[float] = None


class SparseDecoderBlock(Module):
    def __init__(self, embed_dim: int, n_head: int, n_kv_head: int,
                 head_dim: int, spec: LayerSpec, n_experts: int,
                 expert_dim: int, top_k: int, eps: float = 1e-6, name=None):
        super().__init__(name)
        self.e, self.n_experts = embed_dim, n_experts
        self.attn = GroupedQueryAttention(
            embed_dim, n_head, n_kv_head, head_dim, window=spec.window,
            rope_base=spec.rope_base)
        self.experts = RoutedExperts(embed_dim, expert_dim, n_experts, top_k)
        self.ln1, self.ln2 = RMSNorm(embed_dim, eps), RMSNorm(embed_dim, eps)

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        return {"ln1": self.ln1.init(None), "ln2": self.ln2.init(None),
                "attn": self.attn.init(k1),
                "router": Xavier()(k2, (self.e, self.n_experts)),
                "experts": self.experts.init(k3)}

    def _route(self, params, h):
        """Router logits in float32 from the attention block's normed
        input, itself float32 and unrounded (a float32 product at full
        precision: the top-k must not turn on a matmul's rounding)."""
        with jax.named_scope("moe route"):
            return jnp.dot(h.astype(jnp.float32),
                           params["router"].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)

    def _experts(self, params, x, logits):
        shape = x.shape
        u = self.ln2.apply(params["ln2"], x, None).reshape(-1, self.e)
        y, chosen = self.experts.apply_routed(
            params["experts"], u, logits.reshape(-1, self.n_experts))
        return x + y.reshape(shape), chosen.reshape(*shape[:-1], -1)

    def apply_prefill(self, params, x):
        """Whole-sequence inference apply: (out [B, T, E], this layer's
        k, v [B, Hkv, T, hd], each token's experts [B, T, top_k])."""
        h = self.ln1.apply(params["ln1"], x, None)
        logits = self._route(params, h)
        a, k, v = self.attn.apply_prefill(params["attn"], h)
        x, chosen = self._experts(params, x + a, logits)
        return x, k, v, chosen

    def apply(self, params, input, ctx):
        return self.apply_prefill(params, input)[0]

    def apply_step(self, params, x, k_cache, v_cache, positions):
        """One token a row against the layer's cache: (out [B, 1, E],
        k_cache, v_cache, experts [B, 1, top_k])."""
        h = self.ln1.apply(params["ln1"], x, None)
        logits = self._route(params, h)
        a, k_cache, v_cache = self.attn.apply_step(
            params["attn"], h, k_cache, v_cache, positions)
        x, chosen = self._experts(params, x + a, logits)
        return x, k_cache, v_cache, chosen


class SparseDecoderLM(Module):
    """[B, T] int tokens (1-based) -> [B, T, vocab] log-probs; `layers`
    is the pattern, one `LayerSpec` a layer. The residual stream, the
    norms, the router and the log-probs are float32 whatever the
    weights' type; the matmuls take their operands in the weights' type
    and add their float32 accumulators to the stream. (A bfloat16 stream
    rounds 2^-8 of every element away a layer, which is what turns a
    token's sixth and seventh router logits over: on the chip the
    served tokens then lay up to 0.35 under the float32 reference's best
    logit where an fp8 computation lies 0.26: PERF.md, PR 29.)"""

    def __init__(self, vocab_size: int, embed_dim: int, n_head: int,
                 n_kv_head: int, head_dim: int, layers: Sequence[LayerSpec],
                 n_experts: int, expert_dim: int, top_k: int,
                 eps: float = 1e-6, max_len: Optional[int] = None,
                 cache_dtype=jnp.float32, name=None):
        super().__init__(name)
        self.vocab, self.e, self.max_len = vocab_size, embed_dim, max_len
        self.cache_dtype = cache_dtype
        self.n_experts = n_experts
        self.blocks = [SparseDecoderBlock(embed_dim, n_head, n_kv_head,
                                          head_dim, spec, n_experts,
                                          expert_dim, top_k, eps)
                       for spec in layers]
        self.norm = RMSNorm(embed_dim, eps)

    def init(self, rng):
        keys = jax.random.split(rng, len(self.blocks) + 2)
        p = {"embed": jax.random.normal(keys[0], (self.vocab, self.e)) * 0.02,
             "head": Xavier()(keys[1], (self.e, self.vocab)),
             "norm": self.norm.init(None)}
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk.init(keys[i + 2])
        return p

    @staticmethod
    def _embed(params, tokens):
        """1-based ids -> the float32 residual stream's first value."""
        return params["embed"][tokens.astype(jnp.int32) - 1].astype(
            jnp.float32)

    def _logp(self, params, x):
        h = self.norm.apply(params["norm"], x, None)
        logits = jnp.dot(h.astype(params["head"].dtype), params["head"],
                         preferred_element_type=jnp.float32)
        return jax.nn.log_softmax(logits, axis=-1)

    def apply(self, params, input, ctx):
        if self.max_len is not None and input.shape[1] > self.max_len:
            raise ValueError(f"sequence length {input.shape[1]} exceeds "
                             f"max_len {self.max_len}")
        x = self._embed(params, input)
        for i, blk in enumerate(self.blocks):
            x = blk.apply(params[f"block{i}"], x, ctx)
        return self._logp(params, x)

    # ------------------------------------------------------------- serving
    def init_cache(self, slots: int, max_len: int, dtype=None):
        """Per layer K and V of `[slots, n_kv_head, depth, head_dim]`,
        depth `window` on window layers and `max_len` on full ones, and
        the counters a step and a prefill add to on the device."""
        kv = [blk.attn.init_cache(slots, max_len, dtype or self.cache_dtype)
              for blk in self.blocks]
        n = len(self.blocks)
        return {"k": [k for k, _ in kv], "v": [v for _, v in kv],
                "counters": {
                    "moe_expert_load": jnp.zeros((n, self.n_experts),
                                                 jnp.int32),
                    "moe_experts_touched": jnp.zeros((n,), jnp.int32),
                    "decode_steps": jnp.zeros((), jnp.int32),
                    "window_positions_skipped": jnp.zeros((), jnp.float32)}}

    def _load(self, chosen, counted):
        """[n_experts] pairs given to each expert by the tokens `counted`
        marks, of `chosen` [..., top_k]."""
        weight = jnp.broadcast_to(counted[..., None], chosen.shape)
        return jnp.zeros((self.n_experts,), jnp.int32).at[
            chosen.reshape(-1)].add(weight.reshape(-1).astype(jnp.int32))

    def apply_step(self, params, tokens, cache, positions):
        """One decode step over ALL cache slots: `tokens` [S] 1-based,
        `positions` [S] each slot's 0-based position (mixed ages). An
        idle slot rides along at position 0 (a live one is past its
        prompt): its experts are computed and read, so they count as
        touched, but it adds nothing to the experts' load."""
        x = self._embed(params, tokens)[:, None, :]
        live = positions > 0
        everyone = jnp.ones_like(live)
        ks, vs, loads, touched = [], [], [], []
        skipped = jnp.zeros((), jnp.float32)
        for i, blk in enumerate(self.blocks):
            x, k, v, chosen = blk.apply_step(
                params[f"block{i}"], x, cache["k"][i], cache["v"][i],
                positions)
            ks.append(k)
            vs.append(v)
            loads.append(self._load(chosen[:, 0], live))
            touched.append(jnp.sum(self._load(chosen[:, 0], everyone) > 0))
            if blk.attn.window is not None:
                skipped += kv_cache.positions_skipped(
                    jnp.where(live, positions, 0),
                    blk.attn.window).astype(jnp.float32)
        c = cache["counters"]
        counters = {
            "moe_expert_load": c["moe_expert_load"] + jnp.stack(loads),
            "moe_experts_touched": c["moe_experts_touched"]
            + jnp.stack(touched).astype(jnp.int32),
            "decode_steps": c["decode_steps"] + 1,
            "window_positions_skipped": c["window_positions_skipped"]
            + skipped}
        return self._logp(params, x[:, 0]), {"k": ks, "v": vs,
                                             "counters": counters}

    def apply_prefill(self, params, tokens, cache, slot_ids, lengths):
        """Prefill right-padded prompts `tokens` [B, T] of real `lengths`
        [B] into the slots `slot_ids` [B]; returns ([B, vocab] log-probs
        at each prompt's LAST real token, the updated cache). The head
        runs over that one position a row, not over T."""
        lengths = lengths.astype(jnp.int32)
        x = self._embed(params, tokens)
        # a bucket's padding repeats the last request's row, slot id
        # included: count a slot's tokens once, and no padded position
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 slot_ids[1:] != slot_ids[:-1]])
        valid = (jnp.arange(tokens.shape[1])[None, :] < lengths[:, None]) \
            & first[:, None]
        ks, vs, loads = [], [], []
        for i, blk in enumerate(self.blocks):
            x, k, v, chosen = blk.apply_prefill(params[f"block{i}"], x)
            w = blk.attn.window
            ks.append(kv_cache.commit(cache["k"][i],
                                      k.astype(cache["k"][i].dtype),
                                      slot_ids, lengths, w))
            vs.append(kv_cache.commit(cache["v"][i],
                                      v.astype(cache["v"][i].dtype),
                                      slot_ids, lengths, w))
            loads.append(self._load(chosen, valid))
        c = dict(cache["counters"])
        c["moe_expert_load"] = c["moe_expert_load"] + jnp.stack(loads)
        last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
        return self._logp(params, last[:, 0]), {"k": ks, "v": vs,
                                                "counters": c}

    def cache_stats(self, cache):
        """The counters of `cache` as plain numbers (one fetch):
        `moe_pairs_routed` token-expert pairs of real tokens;
        `moe_expert_load_max_over_mean` the busiest expert's load over
        the mean, of the layer where that is worst;
        `moe_experts_touched_per_step` distinct experts a decode step
        read, mean over steps and layers; `window_positions_skipped`
        cache positions that a one-depth cache would have given the
        decode steps to read and the ring did not."""
        c = jax.device_get(cache["counters"])
        load = np.asarray(c["moe_expert_load"], np.int64)
        steps = int(c["decode_steps"])
        mean = load.mean(axis=1)
        worst = (load.max(axis=1)[mean > 0] / mean[mean > 0])
        return {
            "moe_pairs_routed": int(load.sum()),
            "moe_expert_load_max_over_mean":
                round(float(worst.max()), 4) if worst.size else None,
            "moe_experts_touched_per_step":
                round(float(np.mean(c["moe_experts_touched"])) / steps, 4)
                if steps else None,
            "window_positions_skipped":
                float(c["window_positions_skipped"])}
