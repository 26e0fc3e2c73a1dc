"""Plain reference for the `neox-3.6b` configuration.

A decoder-only transformer at the widths of rinna/japanese-gpt-neox-3.6b
(GPTNeoXForCausalLM: hidden 2816, 22 heads of 128, feed-forward 11264,
vocabulary 32000, rotary_pct 1.0, use_parallel_residual false, LayerNorm
eps 1e-5, biased projections and MLP), in straightforward `jax.numpy`
and float32: token embedding, pre-norm blocks x + Attn(LN1(x)) then
x + MLP(LN2(x)), causal softmax attention with rotary positions on every
head dimension, a GELU MLP, an untied output head, log-softmax, and the
mean negative log-likelihood of the next token. No kernels, no cache, no
batching tricks. It imports nothing of the program.

Departures from the published model, which the program's
`TransformerLM` makes and the configuration file lists under `assumed`:
rotary pairs are (2i, 2i+1) rather than (i, i + d/2), which is the same
function up to a fixed permutation of each head's q/k columns; GELU is
the tanh approximation; there is no final LayerNorm before the head.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from benchmarks.reference import numerics as nx

LN_EPS = 1e-5
ROPE_BASE = 10000.0


def layout(cfg: Dict[str, Any]):
    """(name, shape, std) of every leaf; std None marks a LayerNorm
    scale (ones plus noise), 0.0 a LayerNorm bias."""
    e, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    # embedding at unit scale so the residual stream is O(1); the head at
    # 1/sqrt(e) so logits are O(1); the rest at GPT-NeoX's 0.02
    out = [("embed", (v, e), 1.0), ("head", (e, v), 1.0 / math.sqrt(e))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        for n in ("wq", "wk", "wv", "wo"):
            out.append((p + n, (e, e), 0.02))
        for n in ("bq", "bk", "bv", "bo"):
            out.append((p + n, (e,), 0.02))
        out += [(p + "w1", (e, f), 0.02), (p + "b1", (f,), 0.02),
                (p + "w2", (f, e), 0.02), (p + "b2", (e,), 0.02)]
        for ln in ("ln1", "ln2"):
            out += [(p + ln + ".g", (e,), None), (p + ln + ".b", (e,), 0.0)]
    return out


@partial(jax.jit, static_argnums=(0,))
def _init(leaves, seed):
    key = jax.random.PRNGKey(seed)
    w = {}
    for i, (name, shape, std) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if std is None:
            w[name] = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif std == 0.0:
            w[name] = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            w[name] = std * jax.random.normal(k, shape, jnp.float32)
    return w


def init_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """All weights in one jitted call from the seed, float32."""
    return _init(tuple(layout(cfg)), jnp.uint32(seed % (2 ** 32)))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _batch(rows, seq, vocab, seed):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1000001)
    t = jax.random.randint(key, (rows, seq + 1), 1, vocab + 1, jnp.int32)
    return t[:, :-1], t[:, 1:]


def train_batch(cfg, mix, seed, chips):
    """Packed rows of `sequence` 1-based token ids and their next tokens."""
    return _batch(mix["per_chip_batch"] * chips, mix["sequence"],
                  cfg["vocab_size"], jnp.uint32(seed % (2 ** 32)))


def row_block(cfg, mix):
    """The loss is a plain mean over rows: accumulate one row at a time."""
    return 1


def _layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _rope(x):
    """[B, H, T, D]: rotate each pair (2i, 2i+1) by position * base^(-2i/D)."""
    d, t = x.shape[-1], x.shape[-2]
    inv = ROPE_BASE ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(w, x, n_head, precision):
    b, t, e = x.shape
    hd = e // n_head
    mm = partial(nx.matmul, precision=precision)

    def heads(z):
        return z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)

    h = _layer_norm(x, w["ln1.g"], w["ln1.b"])
    qh = _rope(heads(mm(h, w["wq"]) + w["bq"]))
    kh = _rope(heads(mm(h, w["wk"]) + w["bk"]))
    vh = heads(mm(h, w["wv"]) + w["bv"])
    s = mm(qh, kh.transpose(0, 1, 3, 2)) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), vh)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, e)
    x = x + mm(o, w["wo"]) + w["bo"]
    h = _layer_norm(x, w["ln2.g"], w["ln2.b"])
    h = _gelu(mm(h, w["w1"]) + w["b1"])
    return x + mm(h, w["w2"]) + w["b2"]


def hidden(cfg, w, tokens, precision="f32"):
    """[B, T] 1-based ids -> [B, T, E] residual stream after the last
    block, each block under `jax.checkpoint`."""
    x = w["embed"][tokens - 1]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        sub = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        x = jax.checkpoint(partial(_block, n_head=cfg["num_attention_heads"],
                                   precision=precision))(sub, x)
    return x


def loss(cfg, w, x, y, precision="f32"):
    """Mean NLL of next tokens `y` [B, T] given tokens `x` [B, T]."""
    logits = nx.matmul(hidden(cfg, w, x, precision), w["head"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, (y - 1)[..., None], axis=-1))


def logits_at(cfg, w, tokens, positions, precision="f32"):
    """Logits [B, P, V] of the full causal forward over `tokens` [B, T]
    at the `positions` [B, P] asked for."""
    h = hidden(cfg, w, tokens, precision)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    return nx.matmul(h, w["head"], precision)


def served_weights(cfg, w):
    """The weights as the configuration serves them: rounded to its
    `weight_dtype`, held in float32 for the reference's arithmetic."""
    dt = jnp.dtype(cfg["serving"]["weight_dtype"])
    return {k: v.astype(dt).astype(jnp.float32) for k, v in w.items()}
