"""A decoder-only language model assembled from a layer pattern.

Each layer is a `LayerSpec`: its token mixer (grouped-query attention,
`nn.GroupedQueryAttention`, over a sliding `window` or everything, with
a rotary base or no positional encoding at all; the gated delta
rule's linear attention, `nn.GatedDeltaRule`, which keeps a fixed-size
state instead of keys and values; latent attention,
`nn.LatentAttention`, which keeps one compressed latent a position for
all heads; or the gated short convolution, `nn.ShortConv`, which keeps
the last rows it convolved and nothing else), its feed-forward (dropless routed
experts, `nn.experts.RoutedExperts`, with or without an always-on
`shared` expert added to their sum, or one dense gated one,
`nn.experts.GatedFFN`) and where its RMSNorms sit: on each sub-layer's
input,

    h = norm1(x);  r = h @ router;  x = x + attn(h)
    u = norm2(x);  x = x + ffn(u)        experts routed by r

(`router_reads="attention"`: the router reads the ATTENTION block's
normed input, before attention runs; "ffn": r = u @ router, the
feed-forward's own), or on each sub-layer's output,

    x = x + norm1(attn(x));  x = x + norm2(ffn(x)).

The serving side is what `GenerationEngine` calls (`init_cache`,
`apply_prefill`, `apply_step`, `cache_stats`): the cache gives each
layer what its kind keeps a slot (nn/kv_cache.py: K and V as deep as
the kind needs, a recurrent state and its convolution's tail, the
latent and the shared rotary key, or a convolution's tail alone),
prefill takes the head over each
prompt's last real position only, and the cache pytree carries
device-side counters of the routing, of the window, of the recurrent
state, of the latent's reads and of the convolutions' tails. The head
is its own matrix, or the embedding's transpose (`tie_head`).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import GroupedQueryAttention
from bigdl_tpu.nn.experts import GatedFFN, RoutedExperts
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.linear_attention import GatedDeltaRule
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.normalization import RMSNorm
from bigdl_tpu.nn.short_conv import ShortConv
from bigdl_tpu.ops import gqa_decode_kernel, latent_decode_kernel

#: what a layer keeps a serving slot, by its mixer and by the cache's
#: names: an attention layer "k" and "v", a recurrent one "state" and
#: "tail", a latent one "latent" and "k_pe", a short convolution
#: "conv_tail" alone
_KEEPS = {"attention": ("k", "v"), "gated_delta": ("state", "tail"),
          "latent": ("latent", "k_pe"), "short_conv": ("conv_tail",)}
_KEPT = tuple(name for names in _KEEPS.values() for name in names)


@dataclass(frozen=True)
class LatentDims:
    """A latent-attention layer's widths: each head's query and key
    without position (`nope`) and rotary (`rope`), its value (`value`),
    and the latent's `rank`."""
    nope: int
    rope: int
    value: int
    rank: int


@dataclass(frozen=True)
class ExpertsKind:
    """How the routed experts gate ("relu", "silu") and how their router
    scores (`nn.experts.route`: "softmax"; or "sigmoid", chosen by score
    plus the block's `router_bias`, weighed by the score, times
    `scale`)."""
    gate: str = "relu"
    scoring: str = "softmax"
    scale: float = 1.0


@dataclass(frozen=True)
class LayerSpec:
    """One layer of the pattern. `mixer`: "attention" (over the last
    `window` positions, None: all; `rope_base` of its rotary encoding,
    None: no positional encoding), "gated_delta" (linear attention;
    neither applies), "latent" (latent attention over everything; its
    `rope_base` is a number) or "short_conv" (the gated short
    convolution; neither applies). `ffn`: "experts" (routed; beside them an
    always-on gated expert of width `shared`, 0: none; their router
    reads what `router_reads` names, the "attention" block's normed
    input or the "ffn"'s own) or "dense". `norm`: the RMSNorms on each
    sub-layer's "input" or "output"."""
    window: Optional[int] = None
    rope_base: Optional[float] = None
    mixer: str = "attention"
    ffn: str = "experts"
    norm: str = "input"
    shared: int = 0
    router_reads: str = "attention"

    def __post_init__(self):
        for field, known in (("mixer", tuple(_KEEPS)),
                             ("ffn", ("experts", "dense")),
                             ("norm", ("input", "output")),
                             ("router_reads", ("attention", "ffn"))):
            if getattr(self, field) not in known:
                raise ValueError(f"LayerSpec.{field} is one of {known}, "
                                 f"got {getattr(self, field)!r}")
        if self.mixer in ("gated_delta", "short_conv") and (
                self.window is not None or self.rope_base is not None):
            raise ValueError(f"LayerSpec.window and rope_base belong to "
                             f"mixer='attention'; a {self.mixer} layer "
                             f"keeps no positions")
        if self.mixer == "latent" and (self.window is not None
                                       or self.rope_base is None):
            raise ValueError("a latent layer attends to everything and "
                             "rotates its rotary part: window None, "
                             "rope_base a number")
        if self.ffn == "dense" and (self.shared
                                    or self.router_reads != "attention"):
            raise ValueError("LayerSpec.shared and router_reads belong to "
                             "ffn='experts'")


class DecoderBlock(Module):
    """`attn` is the layer's token mixer and `keeps` the names of the one
    or two things it keeps a serving slot (`_KEEPS`; where it keeps one,
    the second of the pair this block's entry points pass is None); the
    feed-forward is
    `experts` (with the block's `router`, a sigmoid router's
    `router_bias`, and the `shared` expert where the layer has one) or
    `ffn`."""

    def __init__(self, embed_dim: int, spec: LayerSpec, attn: Module,
                 ffn: Module, eps: float = 1e-6, name=None):
        super().__init__(name)
        self.e, self.window = embed_dim, spec.window
        self.attn = attn
        self.keeps = _KEEPS[spec.mixer]
        self.experts = ffn if spec.ffn == "experts" else None
        self.ffn = ffn if spec.ffn == "dense" else None
        self.shared = GatedFFN(embed_dim, spec.shared) if spec.shared \
            else None
        self.route_early = spec.router_reads == "attention"
        self.norm_output = spec.norm == "output"
        self.ln1, self.ln2 = RMSNorm(embed_dim, eps), RMSNorm(embed_dim, eps)

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        p = {"ln1": self.ln1.init(None), "ln2": self.ln2.init(None),
             "attn": self.attn.init(k1)}
        if self.experts is not None:
            p["router"] = Xavier()(k2, (self.e, self.experts.n_experts))
            p["experts"] = self.experts.init(k3)
            if self.experts.scoring == "sigmoid":
                p["router_bias"] = jnp.zeros((self.experts.n_experts,))
            if self.shared is not None:
                p["shared"] = self.shared.init(jax.random.fold_in(rng, 3))
        else:
            p["ffn"] = self.ffn.init(k3)
        return p

    def _route(self, params, h):
        """Router logits in float32 from a sub-layer's normed input,
        itself float32 and unrounded (a float32 product at full
        precision: the top-k must not turn on a matmul's rounding)."""
        with jax.named_scope("moe route"):
            return jnp.dot(h.astype(jnp.float32),
                           params["router"].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)

    def _mix(self, params, x, mixer):
        """x + the mixer's sub-layer, what the mixer keeps, and the
        router's logits (None with no router, or one that reads the
        feed-forward's input); `mixer(params, h)` is the layer's prefill
        or step."""
        h = x if self.norm_output else self._norm("ln1", params, x)
        logits = self._route(params, h) \
            if self.experts is not None and self.route_early else None
        a, *kept = mixer(params["attn"], h)
        kept_a, kept_b = (*kept, None)[:2]
        if self.norm_output:
            a = self._norm("ln1", params, a)
        return x + a, kept_a, kept_b, logits

    def _norm(self, which, params, x):
        """The RMSNorm `which` ("ln1" or "ln2") of `x`."""
        with jax.named_scope("norm"):
            return getattr(self, which).apply(params[which], x, None)

    def _feed(self, params, x, logits):
        """x + the feed-forward's sub-layer, and each token's experts
        [..., top_k] (None for a dense one)."""
        shape = x.shape
        u = x if self.norm_output else self._norm("ln2", params, x)
        u = u.reshape(-1, self.e)
        if self.experts is not None:
            if logits is None:
                logits = self._route(params, u)
            y, chosen = self.experts.apply_routed(
                params["experts"], u,
                logits.reshape(-1, self.experts.n_experts),
                params.get("router_bias"))
            if self.shared is not None:
                with jax.named_scope("shared expert"):
                    y = y + self.shared.apply(params["shared"], u, None)
        else:
            y, chosen = self.ffn.apply(params["ffn"], u, None), None
        y = y.reshape(shape)
        if self.norm_output:
            y = self._norm("ln2", params, y)
        x = x + y
        if chosen is not None:
            chosen = chosen.reshape(*shape[:-1], -1)
        return x, chosen

    def apply_prefill(self, params, x, lengths=None):
        """Whole-sequence inference apply over right-padded rows of real
        `lengths` (None: whole rows): (out [B, T, E], the two things
        this layer keeps a slot, each token's experts [B, T, top_k] or
        None)."""
        x, kept_a, kept_b, logits = self._mix(
            params, x, lambda p, h: self.attn.apply_prefill(p, h, lengths))
        x, chosen = self._feed(params, x, logits)
        return x, kept_a, kept_b, chosen

    def apply(self, params, input, ctx):
        return self.apply_prefill(params, input)[0]

    def apply_step(self, params, x, kept_a, kept_b, positions):
        """One token a row against what the layer keeps: (out [B, 1, E],
        the two kept things updated, experts [B, 1, top_k] or None)."""
        kept = (kept_a, kept_b)[:len(self.keeps)]
        x, kept_a, kept_b, logits = self._mix(
            params, x, lambda p, h: self.attn.apply_step(
                p, h, *kept, positions))
        x, chosen = self._feed(params, x, logits)
        return x, kept_a, kept_b, chosen


class DecoderLM(Module):
    """[B, T] int tokens (1-based) -> [B, T, vocab] log-probs; `layers`
    is the pattern, one `LayerSpec` a layer. Attention layers have
    `n_head` query heads of `head_dim` over `n_kv_head` K/V heads
    (`qk_norm`: an RMSNorm over the whole q and k projections, or with
    `qk_norm_per_head` over each head);
    "experts" layers `n_experts` of `expert_dim` with `top_k` active,
    gated and routed as `experts` says, "dense" ones `ffn_dim`;
    "gated_delta" layers `linear_heads` heads of
    `linear_key_dim` and `linear_value_dim` behind a convolution of
    `conv_taps`, prefilled in chunks of `chunk`; "latent" layers
    `n_head` heads of the widths `latent` gives; "short_conv" layers a
    convolution of `conv_taps` over `embed_dim` channels. `tie_head`:
    the log-probs are taken through the embedding's transpose, and the
    model holds no head of its own. The residual stream,
    the norms, the router, the recurrent state and the log-probs are
    float32 whatever the weights' type; the matmuls take their operands
    in the weights' type and add their float32 accumulators to the
    stream. (A bfloat16 stream rounds 2^-8 of every element away a
    layer, which is what turns a token's sixth and seventh router
    logits over: on the chip the served tokens then lay up to 0.35 under
    the float32 reference's best logit where an fp8 computation lies
    0.26: PERF.md, PR 29.)"""

    def __init__(self, vocab_size: int, embed_dim: int, n_head: int,
                 n_kv_head: int, head_dim: int, layers: Sequence[LayerSpec],
                 n_experts: int = 0, expert_dim: int = 0, top_k: int = 0,
                 eps: float = 1e-6, max_len: Optional[int] = None,
                 cache_dtype=jnp.float32, name=None, *, ffn_dim: int = 0,
                 qk_norm: bool = False, linear_heads: int = 0,
                 linear_key_dim: int = 0, linear_value_dim: int = 0,
                 conv_taps: int = 4, chunk: int = 64,
                 latent: Optional[LatentDims] = None,
                 experts: ExpertsKind = ExpertsKind(),
                 qk_norm_per_head: bool = False, tie_head: bool = False):
        super().__init__(name)
        self.vocab, self.e, self.max_len = vocab_size, embed_dim, max_len
        self.tie_head = tie_head
        self.cache_dtype = cache_dtype
        self.n_experts, self.chunk = n_experts, chunk

        def block(spec):
            if spec.mixer == "attention":
                attn = GroupedQueryAttention(
                    embed_dim, n_head, n_kv_head, head_dim,
                    window=spec.window, rope_base=spec.rope_base,
                    qk_norm=eps if qk_norm else None,
                    qk_norm_per_head=qk_norm_per_head)
            elif spec.mixer == "short_conv":
                attn = ShortConv(embed_dim, conv_taps)
            elif spec.mixer == "gated_delta":
                attn = GatedDeltaRule(
                    embed_dim, linear_heads, linear_key_dim,
                    linear_value_dim, conv_taps, chunk, eps)
            else:
                attn = LatentAttention(
                    embed_dim, n_head, latent.nope, latent.rope,
                    latent.value, latent.rank, spec.rope_base, eps)
            ffn = RoutedExperts(
                embed_dim, expert_dim, n_experts, top_k, gate=experts.gate,
                scoring=experts.scoring, scale=experts.scale) \
                if spec.ffn == "experts" else GatedFFN(embed_dim, ffn_dim)
            return DecoderBlock(embed_dim, spec, attn, ffn, eps)
        self.blocks = [block(spec) for spec in layers]
        self.norm = RMSNorm(embed_dim, eps)
        # what the cache's counters count, by the kinds of layer there are
        self._routed = [i for i, b in enumerate(self.blocks)
                        if b.experts is not None]
        self._recurrent = [i for i, b in enumerate(self.blocks)
                           if b.keeps == _KEEPS["gated_delta"]]
        self._latent = [i for i, b in enumerate(self.blocks)
                        if b.keeps == _KEEPS["latent"]]
        self._grouped = [i for i, b in enumerate(self.blocks)
                         if b.keeps == _KEEPS["attention"]]
        self._conv = [i for i, b in enumerate(self.blocks)
                      if b.keeps == _KEEPS["short_conv"]]
        self._windowed = any(b.window is not None for b in self.blocks)

    def init(self, rng):
        keys = jax.random.split(rng, len(self.blocks) + 2)
        p = {"embed": jax.random.normal(keys[0], (self.vocab, self.e)) * 0.02,
             "norm": self.norm.init(None)}
        if not self.tie_head:
            p["head"] = Xavier()(keys[1], (self.e, self.vocab))
        for i, blk in enumerate(self.blocks):
            p[f"block{i}"] = blk.init(keys[i + 2])
        return p

    @staticmethod
    def _embed(params, tokens):
        """1-based ids -> the float32 residual stream's first value."""
        with jax.named_scope("embed"):
            return params["embed"][tokens.astype(jnp.int32) - 1].astype(
                jnp.float32)

    def _logp(self, params, x):
        """The final norm, the projection to the vocabulary, log-probs."""
        with jax.named_scope("head"):
            h = self.norm.apply(params["norm"], x, None)
            if self.tie_head:  # h @ embed^T, the embedding as it lies
                w = params["embed"]
                logits = jax.lax.dot_general(
                    h.astype(w.dtype), w, (((h.ndim - 1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            else:
                logits = jnp.dot(h.astype(params["head"].dtype),
                                 params["head"],
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
        """Per layer what its kind keeps (`None` under the other kinds'
        names): "k" and "v" `[slots, n_kv_head, depth, head_dim]`, depth
        `window` on window layers and `max_len` on full ones; "state"
        `[slots, heads, key_dim, value_dim]` float32 and "tail"
        `[slots, taps - 1, channels]` on recurrent ones; "latent"
        `[slots, max_len, rank]` and "k_pe" `[slots, max_len, rope]` on
        latent ones; "conv_tail" `[slots, taps - 1, embed_dim]` on short
        convolutions; and the counters a step and a prefill add to on
        the device."""
        cache = {name: [None] * len(self.blocks) for name in _KEPT}
        for i, blk in enumerate(self.blocks):
            for name, buf in zip(blk.keeps, blk.attn.init_cache(
                    slots, max_len, dtype or self.cache_dtype)):
                cache[name][i] = buf
        c = {"decode_steps": jnp.zeros((), jnp.int32)}
        if self._routed:
            n = len(self._routed)
            c["moe_expert_load"] = jnp.zeros((n, self.n_experts), jnp.int32)
            c["moe_experts_touched"] = jnp.zeros((n,), jnp.int32)
        if self._windowed:
            c["window_positions_skipped"] = jnp.zeros((), jnp.float32)
        if self._recurrent:
            c["recurrent_slot_steps"] = jnp.zeros((), jnp.int32)
            c["recurrent_chunks_scanned"] = jnp.zeros((), jnp.int32)
            c["recurrent_state_absmax"] = jnp.zeros((), jnp.float32)
        if self._latent:
            # float32: slots x max_len a step passes int32 in hours
            c["latent_positions_live"] = jnp.zeros((), jnp.float32)
            c["latent_positions_read"] = jnp.zeros((), jnp.float32)
        if self._grouped:
            c["kv_positions_live"] = jnp.zeros((), jnp.float32)
            c["kv_positions_read"] = jnp.zeros((), jnp.float32)
        if self._conv:
            c["conv_slot_steps"] = jnp.zeros((), jnp.int32)
        cache["counters"] = c
        return cache

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
        touched, but it adds nothing to the experts' load; its recurrent
        state is replaced like any other's (a later prefill sets it
        whole) and counts neither as a slot-step nor towards the
        largest |S|."""
        x = self._embed(params, tokens)[:, None, :]
        live = positions > 0
        everyone = jnp.ones_like(live)
        new = {name: list(cache[name]) for name in _KEPT}
        loads, touched = [], []
        skipped = jnp.zeros((), jnp.float32)
        absmax = jnp.zeros((), jnp.float32)
        for i, blk in enumerate(self.blocks):
            kept = [cache[name][i] for name in blk.keeps] + [None]
            x, *kept, chosen = blk.apply_step(
                params[f"block{i}"], x, kept[0], kept[1], positions)
            for name, k in zip(blk.keeps, kept):
                new[name][i] = k
            a = blk.keeps[0]
            with jax.named_scope("counters"):
                if chosen is not None:
                    loads.append(self._load(chosen[:, 0], live))
                    touched.append(jnp.sum(
                        self._load(chosen[:, 0], everyone) > 0))
                if blk.window is not None:
                    skipped += kv_cache.positions_skipped(
                        jnp.where(live, positions, 0),
                        blk.window).astype(jnp.float32)
            if a == "state":
                # reduced in the fusion that writes the new state, which
                # takes this path: named for the layer whose state it is
                with jax.named_scope("linear attention"):
                    absmax = jnp.maximum(absmax, jnp.max(jnp.where(
                        live, jnp.max(jnp.abs(new[a][i]), axis=(1, 2, 3)),
                        0.0)))
        with jax.named_scope("counters"):
            c = dict(cache["counters"])
            if self._routed:
                c["moe_expert_load"] = c["moe_expert_load"] + jnp.stack(loads)
                c["moe_experts_touched"] = c["moe_experts_touched"] \
                    + jnp.stack(touched).astype(jnp.int32)
            c["decode_steps"] = c["decode_steps"] + 1
            if self._windowed:
                c["window_positions_skipped"] = \
                    c["window_positions_skipped"] + skipped
            if self._recurrent:
                c["recurrent_slot_steps"] = c["recurrent_slot_steps"] \
                    + jnp.sum(live).astype(jnp.int32)
                c["recurrent_state_absmax"] = absmax
            if self._latent:
                depth = cache["latent"][self._latent[0]].shape[1]
                c["latent_positions_live"] = c["latent_positions_live"] \
                    + jnp.sum(jnp.where(live, positions + 1, 0)).astype(
                        jnp.float32)
                c["latent_positions_read"] = c["latent_positions_read"] \
                    + latent_decode_kernel.positions_read(positions, depth)
            if self._grouped:
                ks = [cache["k"][i] for i in self._grouped]
                c["kv_positions_live"] = c["kv_positions_live"] + sum(
                    jnp.sum(jnp.where(live, jnp.minimum(
                        positions + 1, k.shape[2]), 0)) for k in ks).astype(
                            jnp.float32)
                c["kv_positions_read"] = c["kv_positions_read"] + sum(
                    gqa_decode_kernel.positions_read(positions, k)
                    for k in ks)
            if self._conv:
                c["conv_slot_steps"] = c["conv_slot_steps"] \
                    + jnp.sum(live).astype(jnp.int32)
        return self._logp(params, x[:, 0]), {**new, "counters": c}

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
        new = {name: list(cache[name]) for name in _KEPT}
        loads = []
        for i, blk in enumerate(self.blocks):
            x, kept_a, kept_b, chosen = blk.apply_prefill(
                params[f"block{i}"], x, lengths)
            for name, kept in zip(blk.keeps, (kept_a, kept_b)):
                new[name][i] = kv_cache.commit(
                    cache[name][i], kept.astype(cache[name][i].dtype),
                    slot_ids, lengths, blk.window)
            if blk.keeps[0] == "state":
                # a recurrent layer's state and tail land in the cache
                # before the next layer runs: left to itself the compiler
                # commits every layer at the program's end and holds
                # what each commit reads (the layer's whole input, for
                # its tail) until then: 1.2 GB over 4 x 2048 tokens
                x, new["state"][i], new["tail"][i] = \
                    jax.lax.optimization_barrier(
                        (x, new["state"][i], new["tail"][i]))
            if chosen is not None:
                with jax.named_scope("counters"):
                    loads.append(self._load(chosen, valid))
        with jax.named_scope("counters"):
            c = dict(cache["counters"])
            if self._routed:
                c["moe_expert_load"] = c["moe_expert_load"] + jnp.stack(loads)
            if self._recurrent:
                c["recurrent_chunks_scanned"] = \
                    c["recurrent_chunks_scanned"] + jnp.sum(
                        jnp.where(first, -(-lengths // self.chunk), 0))
        with jax.named_scope("head"):
            last = jnp.take_along_axis(x, (lengths - 1)[:, None, None],
                                       axis=1)
        return self._logp(params, last[:, 0]), {**new, "counters": c}

    def cache_stats(self, cache):
        """The counters of `cache` as plain numbers (one fetch), those of
        the kinds of layer the model has. Routed experts:
        `moe_pairs_routed` token-expert pairs of real tokens;
        `moe_expert_load_max_over_mean` the busiest expert's load over
        the mean, of the layer where that is worst;
        `moe_experts_touched_per_step` distinct experts a decode step
        read, mean over steps and layers. Window layers:
        `window_positions_skipped` cache positions that a one-depth
        cache would have given the decode steps to read and the ring did
        not. Recurrent layers: `recurrent_state_bytes` what the slots'
        states and tails hold (a constant of the cache);
        `recurrent_slot_steps` live slots over all decode steps, each of
        which had every recurrent layer's state replaced;
        `recurrent_chunks_scanned` chunks of real tokens the prefills
        scanned a layer (a bucket's padded chunks and padding rows not
        counted); `recurrent_state_absmax` the largest |S| of any live
        slot and layer after the last decode step. Latent layers:
        `latent_cache_bytes` what the slots' latents and rotary keys
        hold (a constant of the cache); over the decode steps, a layer,
        `latent_positions_live` the live slots' positions + 1 (what a
        read that follows the lengths would touch) and
        `latent_positions_read` the positions a step read (each slot's
        live blocks, whole, on the `mla_decode` kernel's path; slots x
        `max_len` on the plain-XLA one). Grouped-query attention layers,
        over the decode steps and those layers: `kv_positions_live` the
        live slots' `min(position + 1, depth)` (a ring holds `window`)
        and `kv_positions_read` the positions a step read (each slot's
        live blocks, whole, on the `gqa_decode` kernel's path; slots x
        depth on the plain-XLA one). Short convolutions:
        `conv_tail_bytes` what the slots' tails hold (a constant of the
        cache); `conv_slot_steps` live slots over all decode steps, each
        of which had every short convolution's tail shifted."""
        c = jax.device_get(cache["counters"])
        out = {}
        if self._routed:
            load = np.asarray(c["moe_expert_load"], np.int64)
            steps = int(c["decode_steps"])
            mean = load.mean(axis=1)
            worst = (load.max(axis=1)[mean > 0] / mean[mean > 0])
            out.update({
                "moe_pairs_routed": int(load.sum()),
                "moe_expert_load_max_over_mean":
                    round(float(worst.max()), 4) if worst.size else None,
                "moe_experts_touched_per_step":
                    round(float(np.mean(c["moe_experts_touched"])) / steps, 4)
                    if steps else None})
        if self._windowed:
            out["window_positions_skipped"] = \
                float(c["window_positions_skipped"])
        if self._recurrent:
            out.update({
                "recurrent_state_bytes": int(sum(
                    cache[name][i].nbytes for i in self._recurrent
                    for name in ("state", "tail"))),
                "recurrent_slot_steps": int(c["recurrent_slot_steps"]),
                "recurrent_chunks_scanned":
                    int(c["recurrent_chunks_scanned"]),
                "recurrent_state_absmax":
                    float(c["recurrent_state_absmax"])})
        if self._latent:
            out.update({
                "latent_cache_bytes": int(sum(
                    cache[name][i].nbytes for i in self._latent
                    for name in _KEEPS["latent"])),
                "latent_positions_live": float(c["latent_positions_live"]),
                "latent_positions_read": float(c["latent_positions_read"])})
        if self._grouped:
            out.update({
                "kv_positions_live": float(c["kv_positions_live"]),
                "kv_positions_read": float(c["kv_positions_read"])})
        if self._conv:
            out.update({
                "conv_tail_bytes": int(sum(
                    cache["conv_tail"][i].nbytes for i in self._conv)),
                "conv_slot_steps": int(c["conv_slot_steps"])})
        return out


#: the name the class had while every layer's feed-forward was routed
SparseDecoderLM = DecoderLM
