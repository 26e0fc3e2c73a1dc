"""Attention layers and transformer blocks.

Net-new vs the reference (SURVEY.md §5.7: no attention exists in BigDL);
designed TPU-first: head-major [B,H,T,D] attention on the flash/blockwise
kernels in ops/attention_kernel.py, bf16-friendly, fully jittable.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import ApplyContext, Module
from bigdl_tpu.nn.normalization import LayerNormalization, rms_norm
from bigdl_tpu.ops import gqa_decode_kernel
from bigdl_tpu.ops.attention_kernel import (blockwise_attention,
                                            causal_grouped_attention,
                                            flash_attention,
                                            grouped_attention,
                                            naive_attention)


def rope(x, positions=None, base: float = 10000.0):
    """Rotary position embedding over [B, H, T, D] (D even). Angles are
    computed in f32; the result keeps x's dtype (bf16 stays bf16).

    `positions` may be [T] (shared across the batch; default `arange(T)`)
    or [B, T] (per-row positions — the decode path, where every cache
    slot sits at its own token position)."""
    b, h, t, d = x.shape
    if positions is None:
        positions = jnp.arange(t)
    positions = jnp.asarray(positions)
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)  # [D/2]
    ang = positions.astype(jnp.float32)[..., :, None] * inv  # [(B,)T, D/2]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    if positions.ndim == 2:  # per-row positions: broadcast over heads
        sin, cos = sin[:, None], cos[:, None]  # [B, 1, T, D/2]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(b, h, t, d).astype(x.dtype)


def project_heads(o, wo):
    """[B, H, T, d] -> [B, T, E]: the heads side by side through the
    output projection `wo` [H d, E], its float32 accumulator unrounded
    (for a residual stream kept in float32)."""
    b, h, t, d = o.shape
    return jnp.dot(jnp.transpose(o, (0, 2, 1, 3)).reshape(b, t, h * d), wo,
                   preferred_element_type=jnp.float32)


# the K/V cache's format has one owner, nn/kv_cache.py; these are its
# write and commit under the names this module has always exported
cache_write = kv_cache.write
cache_commit = kv_cache.commit


class ScaledDotProductAttention(Module):
    """attention(T(q, k, v)) with optional causal mask; q,k,v [B,H,T,D]."""

    def __init__(self, causal: bool = False, use_flash: bool = True,
                 sm_scale: Optional[float] = None, name=None):
        super().__init__(name)
        self.causal, self.use_flash, self.sm_scale = causal, use_flash, sm_scale

    def apply(self, params, input, ctx):
        q, k, v = list(input)  # Table is 1-based; iterate instead of index
        if self.use_flash:
            return flash_attention(q, k, v, self.causal, self.sm_scale)
        return naive_attention(q, k, v, self.causal, self.sm_scale)


class MultiHeadAttention(Module):
    """Multi-head attention (separate q/k/v projections — the layout that
    shards cleanly over a tensor-parallel mesh axis).

    Input: [B, T, E] (self-attention) or Table(query [B,Tq,E],
    key_value [B,Tk,E]) for cross attention. bias optional; RoPE optional.

    Example:
        >>> import jax.numpy as jnp
        >>> from bigdl_tpu.nn import MultiHeadAttention
        >>> mha = MultiHeadAttention(32, n_head=4, causal=True,
        ...                          use_flash=False)
        >>> mha.forward(jnp.ones((2, 10, 32))).shape
        (2, 10, 32)
    """

    def __init__(self, embed_dim: int, n_head: int, causal: bool = False,
                 with_bias: bool = True, use_rope: bool = False,
                 use_flash: bool = True, kv_embed_dim: Optional[int] = None,
                 name=None):
        super().__init__(name)
        if embed_dim % n_head:
            raise ValueError(f"embed_dim {embed_dim} % n_head {n_head} != 0")
        self.e, self.h = embed_dim, n_head
        self.hd = embed_dim // n_head
        self.causal, self.with_bias = causal, with_bias
        self.use_rope, self.use_flash = use_rope, use_flash
        self.kv_e = kv_embed_dim or embed_dim

    def init(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        xav = Xavier()
        p = {"wq": xav(k1, (self.e, self.e)),
             "wk": xav(k2, (self.kv_e, self.e)),
             "wv": xav(k3, (self.kv_e, self.e)),
             "wo": xav(k4, (self.e, self.e))}
        if self.with_bias:
            for n in ("bq", "bk", "bv", "bo"):
                p[n] = jnp.zeros((self.e,))
        return p

    def _split(self, x):  # [B,T,E] -> [B,H,T,hd]
        b, t, _ = x.shape
        return jnp.transpose(x.reshape(b, t, self.h, self.hd), (0, 2, 1, 3))

    def _merge(self, x):  # [B,H,T,hd] -> [B,T,E]
        b, h, t, hd = x.shape
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b, t, h * hd)

    def project_qkv(self, params, xq, xkv=None, positions=None):
        """The q/k/v head of `apply`, factored so the serving prefill and
        decode paths share it: linear projections + bias + head split +
        (optional) RoPE at explicit `positions` ([T] shared, [B, T]
        per-row, or None = `arange`). Returns post-RoPE q, k, v
        [B, H, T, hd]."""
        if xkv is None:
            xkv = xq
        q = xq @ params["wq"]
        k = xkv @ params["wk"]
        v = xkv @ params["wv"]
        if self.with_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        q, k, v = self._split(q), self._split(k), self._split(v)
        if self.use_rope:
            q, k = rope(q, positions), rope(k, positions)
        return q, k, v

    def _attend(self, q, k, v):
        if self.use_flash:
            return flash_attention(q, k, v, self.causal)
        return naive_attention(q, k, v, self.causal)

    def _finish(self, params, o):
        o = self._merge(o) @ params["wo"]
        if self.with_bias:
            o = o + params["bo"]
        return o

    def apply(self, params, input, ctx):
        from bigdl_tpu.utils.table import Table
        if isinstance(input, (Table, list, tuple)):
            xq, xkv = list(input)  # Table is 1-based; iterate
        else:
            xq = xkv = input
        with jax.named_scope("full attention"):
            q, k, v = self.project_qkv(params, xq, xkv)
            return self._finish(params, self._attend(q, k, v))

    def apply_prefill(self, params, x):
        """(out [B, T, E], k, v [B, H, T, hd]) of the whole sequence:
        `apply`'s self-attention, with the post-RoPE K/V a serving
        prefill commits."""
        with jax.named_scope("full attention"):
            q, k, v = self.project_qkv(params, x)
            return self._finish(params, self._attend(q, k, v)), k, v

    def apply_step(self, params, x, k_cache, v_cache, positions):
        """Position-indexed single-step attention — the O(1)-per-token
        incremental apply shared by the serving decode loop (and, fed one
        token at a time, exactly reproducing `apply`; parity-tested at
        every position in tests/test_generation.py).

        `x` [B, 1, E] holds ONE new token per row; `k_cache`/`v_cache`
        [B, H, L, hd] are each row's KV history; `positions` [B] is each
        row's 0-based token position. Writes the new (post-RoPE) K/V at
        `positions` via `cache_write`, then attends over the causal cache
        prefix (key position <= row position) — mask-correct for MIXED
        row ages, so cache slots at different depths batch into one
        fixed-shape step. Returns (out [B, 1, E], k_cache, v_cache).

        The cache is read only as deep as the deepest row needs: to the
        rung of `kv_cache.depth_rungs` that covers `max(positions)`,
        chosen on the device by a `lax.switch` over one static prefix a
        rung, inside the one program. The positions dropped are masked
        for every row, so they weigh exp(-1e30 - m) = 0: the result is
        the whole depth's but for the order of a float sum."""
        with jax.named_scope("full attention"):
            q, k, v = self.project_qkv(params, x,
                                       positions=positions[:, None])
            k_cache = cache_write(k_cache, k, positions)
            v_cache = cache_write(v_cache, v, positions)

            def read(d, q, k_cache, v_cache, positions):
                mask = kv_cache.step_mask(d, positions)
                return naive_attention(q, k_cache[:, :, :d],
                                       v_cache[:, :, :d], mask=mask)
            rungs = kv_cache.depth_rungs(k_cache.shape[2])
            if len(rungs) == 1:  # no switch, and no index to compute
                o = read(rungs[0], q, k_cache, v_cache, positions)
            else:
                o = lax.switch(kv_cache.rung_index(rungs, positions),
                               [partial(read, d) for d in rungs],
                               q, k_cache, v_cache, positions)
            return self._finish(params, o), k_cache, v_cache


class GroupedQueryAttention(Module):
    """Causal self-attention whose sizes are its own: `n_head` query
    heads of `head_dim` (which the model width does not fix) over
    `n_kv_head` K/V heads (query head j reads K/V head
    j // (n_head // n_kv_head)), no bias; `window` keeps the last
    `window` positions (self included) or, None, all of them;
    `rope_base` is the rotary base or, None, no positional encoding at
    all; `qk_norm` puts an RMSNorm (its eps) over the WHOLE q and k
    projections, before the split into heads and the rotation, with
    scales `q_norm` and `k_norm`; None, the default, has neither and
    lowers to the program it always did. Input [B, T, E] in any float
    type: it is cast to the weights'
    type for the projections, and the result is float32 (the output
    projection's accumulator, unrounded), for a residual stream kept in
    float32. The serving cache of a layer is the pair `init_cache`
    gives: a ring of `window` positions or `max_len` deep
    (nn/kv_cache.py)."""

    def __init__(self, embed_dim: int, n_head: int, n_kv_head: int,
                 head_dim: int, window: Optional[int] = None,
                 rope_base: Optional[float] = None,
                 qk_norm: Optional[float] = None, name=None):
        super().__init__(name)
        if n_head % n_kv_head:
            raise ValueError(
                f"n_head {n_head} % n_kv_head {n_kv_head} != 0")
        if window is not None and window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.e, self.h, self.hk, self.hd = embed_dim, n_head, n_kv_head, \
            head_dim
        self.window, self.rope_base = window, rope_base
        self.qk_norm = qk_norm

    def init(self, rng):
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        xav = Xavier()
        p = {"wq": xav(k1, (self.e, self.h * self.hd)),
             "wk": xav(k2, (self.e, self.hk * self.hd)),
             "wv": xav(k3, (self.e, self.hk * self.hd)),
             "wo": xav(k4, (self.h * self.hd, self.e))}
        if self.qk_norm is not None:
            p["q_norm"] = jnp.ones((self.h * self.hd,))
            p["k_norm"] = jnp.ones((self.hk * self.hd,))
        return p

    def project_qkv(self, params, x, positions=None):
        """q [B, H, T, hd] and k, v [B, Hkv, T, hd], rotated at
        `positions` ([T], [B, T], or None = `arange`) where the layer
        has a rotary base."""
        b, t, _ = x.shape
        x = x.astype(params["wq"].dtype)  # a float32 residual stream

        def heads(w, n, norm=None):
            z = x @ params[w]
            if norm is not None and self.qk_norm is not None:
                z = rms_norm(z, params[norm], self.qk_norm)
            return jnp.transpose(z.reshape(b, t, n, self.hd), (0, 2, 1, 3))
        q = heads("wq", self.h, "q_norm")
        k = heads("wk", self.hk, "k_norm")
        v = heads("wv", self.hk)
        if self.rope_base is not None:
            q = rope(q, positions, self.rope_base)
            k = rope(k, positions, self.rope_base)
        return q, k, v

    def _finish(self, params, o):  # [B, H, T, hd] -> [B, T, E] float32
        return project_heads(o, params["wo"])

    def _scope(self):
        return jax.named_scope("full attention" if self.window is None
                               else "window attention")

    def apply_prefill(self, params, x, lengths=None):
        """(out [B, T, E], k, v [B, Hkv, T, hd]) of the whole sequence;
        k and v are what a serving prefill commits. `lengths` is the
        recurrent layers' argument and is not read: under the causal
        mask no real position sees a row's padding."""
        with self._scope():
            q, k, v = self.project_qkv(params, x)
            o = causal_grouped_attention(q, k, v, self.window)
            return self._finish(params, o), k, v

    def apply(self, params, input, ctx):
        return self.apply_prefill(params, input)[0]

    def init_cache(self, slots: int, max_len: int, dtype=jnp.float32):
        return tuple(kv_cache.init(slots, self.hk, max_len, self.hd,
                                   self.window, dtype) for _ in "kv")

    def apply_step(self, params, x, k_cache, v_cache, positions):
        """One new token a row: `x` [B, 1, E] at `positions` [B] against
        the layer's cache. Writes the token's K/V, then the `group` query
        heads of each K/V head read its cache once: through `gqa_decode`,
        only to each slot's length, where `gqa_decode_kernel.cache_block`
        gives a block; else whole, under the mask its kind of cache gives.
        Returns (out [B, 1, E], k_cache, v_cache)."""
        with self._scope():
            q, k, v = self.project_qkv(params, x,
                                       positions=positions[:, None])
            k_cache = kv_cache.write(k_cache, k.astype(k_cache.dtype),
                                     positions, self.window)
            v_cache = kv_cache.write(v_cache, v.astype(v_cache.dtype),
                                     positions, self.window)
            block = gqa_decode_kernel.cache_block(k_cache)
            if block is None:
                mask = kv_cache.step_mask(k_cache.shape[2], positions,
                                          self.window)
                o = grouped_attention(q, k_cache, v_cache, mask[:, :, None])
            else:
                o = gqa_decode_kernel.gqa_decode(q, k_cache, v_cache,
                                                 positions, block)
            return self._finish(params, o), k_cache, v_cache


class TransformerBlock(Module):
    """Pre-norm transformer block: x + MHA(LN(x)); x + MLP(LN(x))."""

    def __init__(self, embed_dim: int, n_head: int, mlp_ratio: int = 4,
                 causal: bool = False, use_rope: bool = False,
                 use_flash: bool = True, dropout: float = 0.0, name=None):
        super().__init__(name)
        self.attn = MultiHeadAttention(embed_dim, n_head, causal=causal,
                                       use_rope=use_rope, use_flash=use_flash)
        self.ln1 = LayerNormalization(embed_dim)
        self.ln2 = LayerNormalization(embed_dim)
        self.e, self.hidden = embed_dim, embed_dim * mlp_ratio
        self.dropout = dropout

    def init(self, rng):
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        xav = Xavier()
        return {"attn": self.attn.init(k1),
                "ln1": self.ln1.init(k2), "ln2": self.ln2.init(k3),
                "w1": xav(k4, (self.e, self.hidden)),
                "b1": jnp.zeros((self.hidden,)),
                "w2": xav(k5, (self.hidden, self.e)),
                "b2": jnp.zeros((self.e,))}

    def _norm(self, which, params, x, ctx=None):
        """The LayerNorm `which` ("ln1" or "ln2") of `x`."""
        with jax.named_scope("norm"):
            return getattr(self, which).apply(params[which], x, ctx)

    def apply(self, params, input, ctx):
        x = input
        h = self._norm("ln1", params, x, ctx)
        x = x + self.attn.apply(params["attn"], h, ctx)
        h = self._norm("ln2", params, x, ctx)
        with jax.named_scope("dense ffn"):
            h = jax.nn.gelu(h @ params["w1"] + params["b1"])
            if self.dropout and ctx.training:
                keep = 1.0 - self.dropout
                h = h * jax.random.bernoulli(ctx.make_rng(), keep,
                                             h.shape) / keep
            return x + (h @ params["w2"] + params["b2"])

    def _mlp(self, params, x):
        # inference-form MLP tail (no dropout) shared by the incremental
        # step and prefill applies; matches `apply`'s eval-mode math
        h = self._norm("ln2", params, x)
        with jax.named_scope("dense ffn"):
            h = jax.nn.gelu(h @ params["w1"] + params["b1"])
            return h @ params["w2"] + params["b2"]

    def apply_step(self, params, x, k_cache, v_cache, positions):
        """One-token incremental block apply (inference): x [B, 1, E] at
        per-row `positions` [B] against this layer's KV cache. Returns
        (out [B, 1, E], k_cache, v_cache)."""
        h = self._norm("ln1", params, x)
        a, k_cache, v_cache = self.attn.apply_step(
            params["attn"], h, k_cache, v_cache, positions)
        x = x + a
        return x + self._mlp(params, x), k_cache, v_cache

    def apply_prefill(self, params, x):
        """Full-sequence inference apply that ALSO returns this layer's
        post-RoPE K/V [B, H, T, hd], so a serving prefill can commit them
        into a decode cache. Same math as eval-mode `apply`."""
        h = self._norm("ln1", params, x)
        a, k, v = self.attn.apply_prefill(params["attn"], h)
        x = x + a
        return x + self._mlp(params, x), k, v
