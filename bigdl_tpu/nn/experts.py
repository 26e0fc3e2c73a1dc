"""Dropless routed experts: every token's top-k experts are computed,
whatever the imbalance; no capacity, no dropped token.

`RoutedExperts` is the feed-forward half of a sparse decoder block. The
router is not in it: the block hands in the router's logits, which it
may compute from another tensor than the experts' own input (a router
placed before attention reads the attention block's normed input).
`parallel/moe.py::MoE` is the capacity-bound Switch/GShard layer with an
expert-parallel exchange; this one has neither.

Tokens are sorted by expert and each projection is ONE grouped product
over the sorted rows (`jax.lax.ragged_dot`); at a decode step's shape
(no more rows than experts, nearly every expert chosen by some row) each
projection is one batched product of every row with every expert, which
reads the weights once at the memory's pace (`_few_rows`: on the v5e XLA
makes two fusions a layer of it, the gate product and the up product
fused into the down projection as the producer of its left operand, and
both stream their weights at 92% of the memory's bandwidth: PERF.md
PR 35).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module


def route(logits, top_k: int, scoring: str = "softmax", bias=None,
          scale: float = 1.0):
    """Top-k of the router's logits [N, E] and their weights, in
    float32. Returns (experts [N, k] int32, weights [N, k] float32).

    "softmax": softmax over all E, top-k, renormalised to sum 1, which
    is the softmax over the k chosen logits. "sigmoid": each expert's
    score p = sigmoid(logit) stands alone; the k experts are CHOSEN by
    p + `bias` [E] (a buffer that balances the load and weighs nothing)
    and WEIGHED by p, normalised over the chosen and times `scale`:
    w_e = scale * p_e / (sum of the chosen p + 1e-20)."""
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        vals, idx = lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)
    if scoring != "sigmoid":
        raise ValueError(f"scoring is 'softmax' or 'sigmoid', got "
                         f"{scoring!r}")
    p = jax.nn.sigmoid(logits)
    _, idx = lax.top_k(p if bias is None else p + bias, top_k)
    w = jnp.take_along_axis(p, idx, axis=-1)
    w = scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w


class RoutedExperts(Module):
    """`n_experts` gated experts of width `d_hidden`:
    E_e(u) = (act(u @ wg_e) * (u @ wu_e)) @ wd_e, and the layer's result
    sum over a token's `top_k` experts of weight x E_e(u). `gate` names
    act: "relu", or "silu" (taken in float32, as `GatedFFN`'s);
    `scoring` and `scale` are `route`'s.

    `apply_routed(params, x [N, d], logits [N, E], bias=None)` returns
    (y [N, d] float32, experts [N, top_k] int32, each token's chosen
    experts); `bias` [E] is the sigmoid router's; x
    is cast to the weights' type for the products, whose last keeps its
    float32 accumulator. More than
    `token_chunk` rows are taken `token_chunk` at a time, which bounds
    the sorted copies a long prefill makes (k x N x d of them) and
    changes no number."""

    def __init__(self, d_model: int, d_hidden: int, n_experts: int,
                 top_k: int, token_chunk: int = 8192, name=None, *,
                 gate: str = "relu", scoring: str = "softmax",
                 scale: float = 1.0):
        super().__init__(name)
        if not 1 <= top_k <= n_experts:
            raise ValueError(f"top_k {top_k} of {n_experts} experts")
        if gate not in ("relu", "silu"):
            raise ValueError(f"gate is 'relu' or 'silu', got {gate!r}")
        self.d, self.hidden, self.n_experts, self.top_k = \
            d_model, d_hidden, n_experts, top_k
        self.token_chunk = int(token_chunk)
        self.gate, self.scoring, self.scale = gate, scoring, float(scale)

    def _gated(self, gate, up):
        """act(gate) * up, the down projection's left operand before its
        cast."""
        if self.gate == "relu":
            return jax.nn.relu(gate) * up
        return jax.nn.silu(gate.astype(jnp.float32)) \
            * up.astype(jnp.float32)

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        xav = Xavier()
        e, d, h = self.n_experts, self.d, self.hidden
        return {"wg": xav(k1, (e, d, h)), "wu": xav(k2, (e, d, h)),
                "wd": xav(k3, (e, h, d))}

    def _chunk(self, params, x, logits, bias=None):
        n, k = x.shape[0], self.top_k
        experts, weights = route(logits, k, self.scoring, bias, self.scale)
        x = x.astype(params["wg"].dtype)
        if self.n_experts <= n * k and n <= self.n_experts:
            return self._few_rows(params, x, experts, weights), experts
        flat = experts.reshape(-1)                         # [N k]
        order = jnp.argsort(flat)                          # by expert
        sizes = jnp.zeros((self.n_experts,), jnp.int32).at[flat].add(1)
        rows = x[order // k]                               # [N k, d]
        gate = lax.ragged_dot(rows, params["wg"], sizes)
        up = lax.ragged_dot(rows, params["wu"], sizes)
        out = lax.ragged_dot(self._gated(gate, up).astype(x.dtype),
                             params["wd"], sizes,
                             preferred_element_type=jnp.float32)
        # back to token order; the k weighted results summed in float32
        out = out[jnp.argsort(order)].reshape(n, k, self.d)
        return jnp.sum(out * weights[..., None], axis=1), experts

    def _few_rows(self, params, x, experts, weights):
        """A decode step's shape: no more rows than experts, yet enough
        pairs (N k >= E) that nearly every expert is chosen by some row.
        The step is then the reading of the weights, and a grouped
        product over sorted rows reads them at some 56% of the memory's
        bandwidth and slower the more experts are touched (0.49 ms a
        product at 32 rows on the v5e, PERF.md PR 29); here every row
        meets every expert in one batched product a projection, the
        weights streamed once whatever the routing, and the top-k's
        weights (zero for an expert not chosen) fold into the down
        projection's left operand, so nothing unchosen reaches the sum.

        The down projection is one product over both the expert and the
        width axis, `[N, E h] x [E h, d]` with a float32 accumulator.
        On the v5e (32 rows, 64 experts of 768 over 2560, bf16; PERF.md
        PR 35) XLA fuses the up product into it as the producer of its
        left operand: one fusion that reads `wu` AND `wd`, 503 MB in
        0.667 ms, beside the gate product's 252 MB in 0.335 ms, each at
        755 GB/s, 92% of the memory's pace, 1.003 ms a layer. Written
        as a 2-D product of reshaped operands it compiles to the same
        fusion (1.003); with `hidden` behind an optimization barrier it
        splits into 0.335 + 0.337 (1.007); with the top-k's weights
        applied after a batched product by expert it stays one fusion
        (1.003) and moves a rounding point. So this form stays.
        The top-k's weights `[N, E]` are built by comparison, not by a
        scatter: 192 serial updates took 12 us a layer, the comparison
        is lost in a neighbouring fusion; the values are the same bit
        for bit, a row's k experts being distinct."""
        with jax.named_scope("moe gate up"):
            gate = jnp.einsum("nd,edh->neh", x, params["wg"])
            up = jnp.einsum("nd,edh->neh", x, params["wu"])
        with jax.named_scope("moe down"):
            chosen = experts[..., None] == jnp.arange(
                self.n_experts, dtype=experts.dtype)       # [N, k, E]
            mix = jnp.sum(jnp.where(chosen, weights[..., None], 0.0), axis=1)
            hidden = self._gated(gate, up).astype(jnp.float32) \
                * mix[..., None]
            return jnp.einsum("neh,ehd->nd", hidden.astype(x.dtype),
                              params["wd"],
                              preferred_element_type=jnp.float32)

    def apply(self, params, input, ctx):
        """`input` = (x [N, d], router logits [N, E]) -> y [N, d]."""
        x, logits = list(input)
        return self.apply_routed(params, x, logits)[0]

    def apply_routed(self, params, x, logits, bias=None):
        with jax.named_scope("moe experts"):
            n, c = x.shape[0], self.token_chunk
            if n <= c or n % c:
                return self._chunk(params, x, logits, bias)
            y, experts = lax.map(
                lambda a: self._chunk(params, *a, bias),
                (x.reshape(n // c, c, -1), logits.reshape(n // c, c, -1)))
            return y.reshape(n, -1), experts.reshape(n, -1)


class GatedFFN(Module):
    """One dense gated feed-forward of width `d_hidden`, the same for
    every token: y = (silu(x @ wg) * (x @ wu)) @ wd, no bias. x [..., d]
    is cast to the weights' type for the products; the gate is taken in
    float32 and the last product keeps its float32 accumulator."""

    def __init__(self, d_model: int, d_hidden: int, name=None):
        super().__init__(name)
        self.d, self.hidden = d_model, d_hidden

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        xav = Xavier()
        return {"wg": xav(k1, (self.d, self.hidden)),
                "wu": xav(k2, (self.d, self.hidden)),
                "wd": xav(k3, (self.hidden, self.d))}

    def apply(self, params, input, ctx):
        with jax.named_scope("dense ffn"):
            x = input.astype(params["wg"].dtype)
            gate = jax.nn.silu((x @ params["wg"]).astype(jnp.float32))
            hidden = gate * (x @ params["wu"]).astype(jnp.float32)
            return jnp.dot(hidden.astype(x.dtype), params["wd"],
                           preferred_element_type=jnp.float32)
