"""Decoder-only transformer language model (long-context flagship).

Beyond-parity model: the reference's sequence modeling stops at recurrent
nets (DL/models/rnn/SimpleRNN.scala, PTB LSTM — SURVEY.md §5.7 "no
attention layer of any kind exists in the tree"). This model exists to
exercise the long-context stack end-to-end: Pallas flash attention
(ops/attention_kernel.py), RoPE, pre-norm blocks, and — through
`parallel/sequence.py` — ring/Ulysses sequence parallelism over a mesh
axis. Causal LM over 1-based token ids, LogSoftMax output feeding
TimeDistributedCriterion(ClassNLLCriterion) like PTBModel.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import TransformerBlock
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.initialization import Xavier


class TransformerLM(Module):
    """[B, T] int tokens (1-based) -> [B, T, vocab] log-probs."""

    def __init__(self, vocab_size: int, embed_dim: int = 256,
                 n_layer: int = 4, n_head: int = 4, mlp_ratio: int = 4,
                 max_len: Optional[int] = None, use_flash: bool = True,
                 dropout: float = 0.0, name=None):
        super().__init__(name)
        self.vocab, self.e = vocab_size, embed_dim
        self.max_len = max_len  # optional sequence-length cap (RoPE is
        # length-free, so this is a guard, not a table size)
        self.blocks = [
            TransformerBlock(embed_dim, n_head, mlp_ratio=mlp_ratio,
                             causal=True, use_rope=True,
                             use_flash=use_flash, dropout=dropout)
            for _ in range(n_layer)
        ]
        self.n_layer = n_layer

    def init(self, rng):
        keys = jax.random.split(rng, self.n_layer + 2)
        xav = Xavier()
        p = {"embed": jax.random.normal(keys[0],
                                        (self.vocab, self.e)) * 0.02,
             "head": xav(keys[1], (self.e, self.vocab))}
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk.init(keys[i + 2])
        return p

    def apply(self, params, input, ctx):
        if self.max_len is not None and input.shape[1] > self.max_len:
            raise ValueError(
                f"sequence length {input.shape[1]} exceeds max_len "
                f"{self.max_len}")
        # 1-based token ids (reference label convention)
        x = self._embed(params, input)
        for i, blk in enumerate(self.blocks):
            x = blk.apply(params[f"block{i}"], x, ctx)
        return self._logp(params, x)

    @staticmethod
    def _embed(params, tokens):
        with jax.named_scope("embed"):
            return params["embed"][tokens.astype(jnp.int32) - 1]

    @staticmethod
    def _logp(params, x):
        with jax.named_scope("head"):
            return jax.nn.log_softmax(x @ params["head"], axis=-1)

    # ------------------------------------------------- incremental decoding
    # The O(1) autoregressive serving path (serving/generation.py): a
    # preallocated per-slot KV cache updated in place, so emitting one
    # token costs one single-position forward instead of a full-sequence
    # recompute. Portable constant-memory caching per arXiv 2603.09555.

    def init_cache(self, slots: int, max_len: int, dtype=jnp.float32):
        """Preallocated per-slot KV decode cache: a pytree of 2*n_layer
        fixed [slots, n_head, max_len, head_dim] buffers. Shapes never
        change across a serving run — the decode executable compiles
        exactly once and updates the buffers in place under donation."""
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        attn = self.blocks[0].attn
        shape = (slots, attn.h, max_len, attn.hd)
        return {"k": [jnp.zeros(shape, dtype) for _ in self.blocks],
                "v": [jnp.zeros(shape, dtype) for _ in self.blocks]}

    @staticmethod
    def decode_depths(max_len: int):
        """The depths a decode step over a cache of `max_len` reads to
        (`MultiHeadAttention.apply_step`'s ladder), for the engine's
        `decode_depth_share`."""
        return kv_cache.depth_rungs(max_len)

    def apply_step(self, params, tokens, cache, positions):
        """One decode step over ALL cache slots: `tokens` [S] (1-based
        ids, one per slot), `positions` [S] (each slot's 0-based token
        position — slots at MIXED ages batch into one fixed-shape step;
        the causal mask follows each slot's own position). Writes each
        token's K/V at its position and returns ([S, vocab] next-token
        log-probs, updated cache)."""
        x = self._embed(params, tokens)[:, None, :]
        ks, vs = [], []
        for i, blk in enumerate(self.blocks):
            x, k_c, v_c = blk.apply_step(params[f"block{i}"], x,
                                         cache["k"][i], cache["v"][i],
                                         positions)
            ks.append(k_c)
            vs.append(v_c)
        return self._logp(params, x[:, 0]), {"k": ks, "v": vs}

    def apply_prefill(self, params, tokens, cache, slot_ids, lengths):
        """Prefill a batch of prompts into cache slots: `tokens` [B, T]
        right-padded 1-based prompts, `slot_ids` [B] each prompt's cache
        slot, `lengths` [B] real prompt lengths. One full-sequence causal
        forward (same math as `apply` in eval mode — right-pad garbage
        sits at LATER positions, which causal attention never lets a real
        token see) whose per-layer K/V land in the cache. Returns
        ([B, vocab] log-probs at each prompt's LAST real token — the
        first generated token's distribution — and the updated cache)."""
        from bigdl_tpu.nn.attention import cache_commit
        x = self._embed(params, tokens)
        ks, vs = [], []
        for i, blk in enumerate(self.blocks):
            x, k, v = blk.apply_prefill(params[f"block{i}"], x)
            ks.append(cache_commit(cache["k"][i], k, slot_ids))
            vs.append(cache_commit(cache["v"][i], v, slot_ids))
        logp = self._logp(params, x)
        with jax.named_scope("head"):
            last = jnp.take_along_axis(
                logp, (lengths.astype(jnp.int32) - 1)[:, None, None], axis=1)
        return last[:, 0], {"k": ks, "v": vs}
