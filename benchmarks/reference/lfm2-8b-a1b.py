"""Plain reference for the `lfm2-8b-a1b` configuration.

LiquidAI/LFM2-8B-A1B as its `config.json` gives it (`model_type`
lfm2_moe): a pre-norm decoder of hidden 2048 (RMSNorm, eps 1e-5) whose
layers are, by `layer_types`, gated short convolutions ("conv") or
grouped-query attention ("full_attention": 32 query heads over 8 K/V
heads of 64, an RMSNorm a head on q and on k, RoPE theta 1e6, no bias);
the first `num_dense_layers` layers have a dense SwiGLU FFN of 7168,
every other one 32 SwiGLU experts of 1792 with 4 active a token, scored
by sigmoid, no shared expert; the head is tied to the embedding over
65536 ids. Layer l, input x [T, E]:

    h = rmsnorm(x; g1)
    conv:       B, C, xx = split(h @ Win, 3);  u = B * xx
                z_t = sum_j w_j u_{t-2+j}  (zeros before 0);  x = x + (C * z) @ Wout
    attention:  q, k, v = h @ Wq, h @ Wk, h @ Wv   split into heads of 64
                q, k = rmsnorm(q_j; gq), rmsnorm(k_j; gk) a head; rope(q, k)
                x = x + merge(softmax(q k^T / 8 + causal) v) @ Wo   head j reads K/V head j // 4
    u = rmsnorm(x; g2)
    dense:      x = x + (silu(u @ Wg) * (u @ Wu)) @ Wd
    experts:    p = sigmoid(u @ Wr);  S = top-4 of (p + b)
                w_e = 1.0 * p_e / (sum_S p + 1e-6)
                x = x + sum_{e in S} w_e (silu(u @ Wg_e) * (u @ Wu_e)) @ Wd_e
    logits = rmsnorm(x_L; gf) @ Wembed^T

In straightforward `jax.numpy` and float32 (`Precision.HIGHEST`): no
kernel, no cache, no sort, no tail: the convolution over the whole
sequence with its zeros written out, the attention with an explicit
causal mask and K/V repeated to the query heads, every expert applied to
every token and masked by the top-4 weights. Attention goes a block of
queries at a time and the experts one at a time, so that a few thousand
positions fit on one chip. It imports nothing of the program.

What `config.json` does not say and this file assumes is listed in the
configuration file under `assumed`. Weights: 3.93 B float32 parameters
(15.7 GB) do not fit on the chip beside anything, so every leaf is
drawn in float32 and kept in the type the configuration serves it in
(bfloat16; the router, its bias and the norms float32), and widened to
float32 where it is used, one layer's at a time: the numbers are those
of float32 arithmetic over the served weights.

`precision` is "f32"; a control, "bf16" or "fp8" (every matmul operand
rounded, the float32 router's to bfloat16 under "fp8"); or a planted
fault at float32: "unbiased_choice" (the experts chosen by p, not by
p + b), "qk_whole" (the q/k norms over the whole projection, each scale
tiled over the heads, not a head at a time) or "no_conv_history" (the
convolution's first two taps left out: each position convolves itself
alone, as a decode step that lost its tail would).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import numerics as nx

FAULTS = ("unbiased_choice", "qk_whole", "no_conv_history")
F32 = jnp.float32

#: spread and mean of the router's bias `expert_bias` as drawn here,
#: the spread of the router's logits, and the routed experts' down
#: projection as a share of the other matrices' scale
BIAS_STD = 0.05
BIAS_MEAN = 0.0
ROUTER_SPREAD = 1.0
ROUTED_DOWN = 0.25
#: the normaliser's epsilon in the published code (1e-6); the program
#: adds its own 1e-20, which moves a weight by some 5e-7 of itself
#: (the chosen scores sum to about 2)
NORM_EPS = 1e-6


def layer_kinds(cfg):
    """`layer_types` of the layers held: "conv" or "full_attention"."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def layout(cfg: Dict[str, Any]):
    """(name, shape, std, kept in float32) of every leaf; std None marks
    an RMSNorm scale (ones plus noise), "bias" the router's bias."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = e // nh
    n, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    taps = cfg["conv_L_cache"]
    # the embedding is also the head: at 1/sqrt(e) the logits are O(1)
    # (an untied head is drawn so in the other configurations), and the
    # residual stream starts small and is built by the layers; the
    # matrices at 0.02 at the published width (and as 1/sqrt(e) at a
    # test's, so that each part weighs there what it weighs here), the
    # convolution's taps at 1/sqrt(taps) (u, z and the layer's output
    # then spread alike), the router at ROUTER_SPREAD/sqrt(e) (logits
    # spread by 1, scores in (0.27, 0.73)), its bias at a quarter of
    # the scores' spread: choosing by p + b then differs from choosing
    # by p for two tokens in three, and the load stays near balanced
    # (the busiest expert some twice the mean; a bias as wide as the
    # scores sends nearly every token to the same four experts, leaves
    # half of them idle, and makes a decode step's cost turn on whether
    # those four take more rows than one tile of the grouped product),
    # and the routed experts' down projection at ROUTED_DOWN of the rest:
    # under sigmoid scoring a token's fourth expert weighs about as much
    # as its first, so one that a rounding turns over for the fifth
    # moves the token by what that expert adds (PERF.md section 6 gives
    # the readings it was set from).
    s = 0.02 * math.sqrt(2048.0 / e)
    out = [("embed", (v, e), 1.0 / math.sqrt(e), False),
           ("norm.g", (e,), None, True)]
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"l{i}."
        out += [(p + "ln1.g", (e,), None, True),
                (p + "ln2.g", (e,), None, True)]
        if kind == "conv":
            out += [(p + "win", (e, 3 * e), s, False),
                    (p + "conv", (taps, e), 1.0 / math.sqrt(taps), False),
                    (p + "wout", (e, e), s, False)]
        else:
            out += [(p + "wq", (e, nh * hd), s, False),
                    (p + "wk", (e, nk * hd), s, False),
                    (p + "wv", (e, nk * hd), s, False),
                    (p + "wo", (nh * hd, e), s, False),
                    (p + "qn.g", (hd,), None, True),
                    (p + "kn.g", (hd,), None, True)]
        if i < cfg["num_dense_layers"]:
            fd = cfg["intermediate_size"]
            out += [(p + "wg", (e, fd), s, False),
                    (p + "wu", (e, fd), s, False),
                    (p + "wd", (fd, e), s, False)]
        else:
            out += [(p + "router", (e, n), ROUTER_SPREAD / math.sqrt(e), True),
                    (p + "router_bias", (n,), "bias", True),
                    (p + "wg", (n, e, f), s, False),
                    (p + "wu", (n, e, f), s, False),
                    (p + "wd", (n, f, e), s * ROUTED_DOWN, False)]
    return out


@partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape, std, dtype):
    z = jax.random.normal(key, shape, F32)
    if std is None:
        return (1.0 + 0.1 * z).astype(dtype)
    if std == "bias":
        return (BIAS_MEAN + BIAS_STD * z).astype(dtype)
    return (std * z).astype(dtype)


def init_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every leaf from the seed, drawn in float32 and kept in the type it
    is served in."""
    key = jax.random.PRNGKey(jnp.uint32(seed % (2 ** 32)))
    served = jnp.dtype(cfg["serving"]["weight_dtype"])
    return {name: _leaf(jax.random.fold_in(key, i), shape, std,
                        jnp.dtype(F32) if keep else served)
            for i, (name, shape, std, keep) in enumerate(layout(cfg))}


def served_weights(cfg, w):
    """`init_weights` already keeps each leaf as it is served."""
    return w


def _numeric(precision: str) -> str:
    return "f32" if precision in FAULTS else precision


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """[B, H, T, D]: rotate each pair (2i, 2i+1) by position * theta^(-2i/D)."""
    d, t = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _attention(q, k, v, mm, block: int = 512):
    """q [B, H, T, D] against k, v [B, Hkv, T, D] repeated to H heads, a
    block of queries at a time, causal."""
    b, h, t, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    block = block if t % block == 0 else t
    key_pos = jnp.arange(t)[None, :]

    def rows(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=2)
        s = mm(qb, k.transpose(0, 1, 3, 2)) / math.sqrt(d)
        keep = key_pos <= (i * block + jnp.arange(block))[:, None]
        return mm(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), v)

    o = lax.map(rows, jnp.arange(t // block))        # [n, B, H, block, D]
    return o.transpose(1, 2, 0, 3, 4).reshape(b, h, t, d)


def _short_conv(cfg, w, h, precision, mm):
    e, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    bcx = mm(h, w["win"].astype(F32))
    b, c, x = bcx[..., :e], bcx[..., e:2 * e], bcx[..., 2 * e:]
    u = b * x
    t = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    first = taps - 1 if precision == "no_conv_history" else 0
    z = sum(padded[:, j:j + t] * w["conv"][j].astype(F32)
            for j in range(first, taps))
    return mm(c * z, w["wout"].astype(F32))


def _full_attention(cfg, w, h, precision, mm):
    b, t, e = h.shape
    nh, nk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = e // nh, cfg["norm_eps"]
    theta = float(cfg["rope_theta"])

    def heads(z, n, g):
        if precision == "qk_whole":
            z = _rms(z, jnp.tile(g, n), eps)
        z = z.reshape(b, t, n, hd)
        if precision != "qk_whole":
            z = _rms(z, g, eps)
        return z.transpose(0, 2, 1, 3)

    q = _rope(heads(mm(h, w["wq"].astype(F32)), nh, w["qn.g"]), theta)
    k = _rope(heads(mm(h, w["wk"].astype(F32)), nk, w["kn.g"]), theta)
    v = mm(h, w["wv"].astype(F32)).reshape(b, t, nk, hd).transpose(
        0, 2, 1, 3)
    o = _attention(q, k, v, mm)
    return mm(o.transpose(0, 2, 1, 3).reshape(b, t, nh * hd),
              w["wo"].astype(F32))


def _gated(u, wg, wu, wd, mm):
    return mm(jax.nn.silu(mm(u, wg.astype(F32))) * mm(u, wu.astype(F32)),
              wd.astype(F32))


def _experts(cfg, w, u, precision, mm):
    """u [N, E] through all experts, one at a time, each masked by the
    top-4 weights of the sigmoid router."""
    num = _numeric(precision)
    k = cfg["num_experts_per_tok"]
    p = jax.nn.sigmoid(nx.matmul(u, w["router"],
                                 "bf16" if num == "fp8" else num))
    chooser = p if precision == "unbiased_choice" else p + w["router_bias"]
    _, idx = lax.top_k(chooser, k)
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    gate = cfg["routed_scaling_factor"] * chosen \
        / (jnp.sum(chosen, axis=-1, keepdims=True) + NORM_EPS)
    dense = jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                 idx].set(gate)      # [N, n_experts]

    def one(acc, xs):
        wg, wu, wd, g = xs
        return acc + g[:, None] * _gated(u, wg, wu, wd, mm), None

    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (w["wg"], w["wu"], w["wd"], dense.T))
    return out


def _block(cfg, w, x, layer: int, precision: str):
    b, t, e = x.shape
    mm = partial(nx.matmul, precision=_numeric(precision))
    eps = cfg["norm_eps"]
    h = _rms(x, w["ln1.g"], eps)
    mixer = _short_conv if layer_kinds(cfg)[layer] == "conv" \
        else _full_attention
    x = x + mixer(cfg, w, h, precision, mm)
    u = _rms(x, w["ln2.g"], eps).reshape(b * t, e)
    if layer < cfg["num_dense_layers"]:
        y = _gated(u, w["wg"], w["wu"], w["wd"], mm)
    else:
        y = _experts(cfg, w, u, precision, mm)
    return x + y.reshape(b, t, e)


def hidden(cfg, w, tokens, precision="f32"):
    """[B, T] 1-based ids -> [B, T, E] residual stream after the last
    block."""
    x = w["embed"][tokens - 1].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        sub = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        x = _block(cfg, sub, x, i, precision)
    return x


def logits_at(cfg, w, tokens, positions, precision="f32"):
    """Logits [B, P, V] of the full causal forward over `tokens` [B, T]
    at the `positions` [B, P] asked for, through the tied head."""
    h = hidden(cfg, w, tokens, precision)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    h = _rms(h, w["norm.g"], cfg["norm_eps"])
    return nx.matmul(h, w["embed"].astype(F32).T, _numeric(precision))
