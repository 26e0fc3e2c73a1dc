"""Plain reference for the `smallthinker-21b` configuration.

PowerInfer/SmallThinker-21BA3B-Instruct as its `config.json` gives it: a
decoder of hidden 2560 whose every layer is RMSNorm (eps 1e-6), 28 query
heads over 4 key/value heads of 128 without bias, and a mixture of 64
experts of width 768 with 6 active a token and no shared one; three
layers of every four attend to the last 4096 positions with rotary
positions (theta 1.5e6), the fourth to everything with no positional
encoding at all; untied head over 151936 ids. Layer l, input x [T, E]:

    h = rmsnorm(x; g1);  r = h @ Wr                     router BEFORE attention
    q, k, v = h @ Wq, h @ Wk, h @ Wv;  rope(q, k) where rope_layout[l] == 1
    a = softmax(q k^T / sqrt(128) + M) v                head j reads K/V head j // 7;
        M causal, and where sliding_window_layout[l] == 1 also key > query - 4096
    x = x + merge(a) @ Wo
    u = rmsnorm(x; g2);  S = top-6 of r;  w = softmax(r[S])
    x = x + sum_{e in S} w_e (relu(u @ Wg_e) * (u @ Wu_e)) @ Wd_e
    logits = rmsnorm(x_L; gf) @ Whead

In straightforward `jax.numpy` and float32 (`Precision.HIGHEST`): no
kernel, no cache, no sort: every expert is applied to every token and
masked by the top-6, the window is an explicit mask. Attention goes a
block of queries at a time and the experts one at a time so that 12 800
positions fit on one chip. It imports nothing of the program.

What `config.json` does not say and this file assumes is listed in the
configuration file under `assumed`. Weights: 3.97 B float32 parameters
(15.9 GB) do not fit on the chip beside anything, so every leaf is
drawn in float32 and kept in the type the configuration serves it in
(bfloat16; the router and the norms float32), and widened to float32
where it is used: the numbers are those of float32 arithmetic over the
served weights.

`precision` is "f32"; a control, "bf16" or "fp8" (every matmul operand
rounded, the float32 router's to bfloat16 under "fp8"); or a planted
fault at float32: "drop_expert" (each token's sixth expert left out of
the sum) or "no_window" (the window ignored).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import numerics as nx

FAULTS = ("drop_expert", "no_window")
F32 = jnp.float32


def layout(cfg: Dict[str, Any]):
    """(name, shape, std, kept in float32) of every leaf; std None marks
    an RMSNorm scale (ones plus noise)."""
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    nq, nk = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    # embedding at unit scale so the residual stream is O(1), the head at
    # 1/sqrt(e) so logits are O(1), the router at 2/sqrt(e) so that its
    # logits spread by 2 and a token's first expert takes about 0.45 of
    # the top-6's weight and its sixth 0.06 (at 1/sqrt(e) 0.29 and 0.11,
    # flatter than a trained router's, and twice as many tokens have
    # their sixth and seventh logits within a rounding), the rest at
    # 0.02 at the published width (and as 1/sqrt(e) at a test's, so that
    # attention and experts weigh there what they weigh here), but for
    # the experts' down projection at a quarter of that: a layer's
    # experts then add 0.03 to 0.05 of the stream's norm (attention 0.1
    # to 0.5). At 0.02 they add 0.12 to 0.20, and a token whose sixth
    # and seventh router logits a bfloat16 rounding turns over (one in
    # some twenty has one in some layer) jumps by up to 0.07 of the
    # stream, which is where a computation in fp8 puts EVERY token
    # (0.09): the widest gap of some 1 200 tokens then read 0.03 to 0.26
    # for the program and 0.31 to 0.35 for the fp8 control, and no limit
    # lay between (PERF.md, PR 29). The experts' share of the fp8
    # control's error is the smaller one, so it stays where it was.
    s = 0.02 * math.sqrt(2560.0 / e)
    out = [("embed", (v, e), 1.0, False),
           ("head", (e, v), 1.0 / math.sqrt(e), False),
           ("norm.g", (e,), None, True)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out += [(p + "ln1.g", (e,), None, True),
                (p + "ln2.g", (e,), None, True),
                (p + "wq", (e, nq), s, False),
                (p + "wk", (e, nk), s, False),
                (p + "wv", (e, nk), s, False),
                (p + "wo", (nq, e), s, False),
                (p + "router", (e, n), 2.0 / math.sqrt(e), True),
                (p + "wg", (n, e, f), s, False),
                (p + "wu", (n, e, f), s, False),
                (p + "wd", (n, f, e), s / 4, False)]
    return out


@partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape, std, dtype):
    z = jax.random.normal(key, shape, F32)
    return (1.0 + 0.1 * z if std is None else std * z).astype(dtype)


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


def _attention(q, k, v, window, mm, block: int = 512):
    """q [B, H, T, D] against k, v [B, Hkv, T, D] repeated to H heads, a
    block of queries at a time; `window` None or the positions kept."""
    b, h, t, d = q.shape
    k = jnp.repeat(k, h // k.shape[1], axis=1)
    v = jnp.repeat(v, h // v.shape[1], axis=1)
    block = block if t % block == 0 else t
    key_pos = jnp.arange(t)[None, :]

    def rows(i):
        qb = lax.dynamic_slice_in_dim(q, i * block, block, axis=2)
        s = mm(qb, k.transpose(0, 1, 3, 2)) / math.sqrt(d)
        qp = (i * block + jnp.arange(block))[:, None]
        keep = key_pos <= qp
        if window is not None:
            keep = keep & (key_pos > qp - window)
        return mm(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1), v)

    o = lax.map(rows, jnp.arange(t // block))        # [n, B, H, block, D]
    return o.transpose(1, 2, 0, 3, 4).reshape(b, h, t, d)


def _experts(cfg, w, u, r, precision, mm):
    """u [N, E] through all experts, one at a time, each masked by the
    top-6 weights of r [N, n_experts]."""
    k = cfg["moe_num_active_primary_experts"]
    vals, idx = lax.top_k(r, k)
    gate = jax.nn.softmax(vals, axis=-1)             # = softmax over all,
    if precision == "drop_expert":                   # top-k, renormalised
        gate = gate.at[:, -1].set(0.0)
    dense = jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None],
                                 idx].set(gate)      # [N, n_experts]

    def one(acc, xs):
        wg, wu, wd, g = xs
        y = mm(jax.nn.relu(mm(u, wg.astype(F32))) * mm(u, wu.astype(F32)),
               wd.astype(F32))
        return acc + g[:, None] * y, None

    out, _ = lax.scan(one, jnp.zeros_like(u),
                      (w["wg"], w["wu"], w["wd"], dense.T))
    return out


def _block(cfg, w, x, layer: int, precision: str):
    b, t, e = x.shape
    hd, nh, nk = cfg["head_dim"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    num = _numeric(precision)
    mm = partial(nx.matmul, precision=num)

    def heads(z, n):
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    h = _rms(x, w["ln1.g"], cfg["rms_norm_eps"])
    r = nx.matmul(h, w["router"], "bf16" if num == "fp8" else num)
    q = heads(mm(h, w["wq"].astype(F32)), nh)
    kk = heads(mm(h, w["wk"].astype(F32)), nk)
    v = heads(mm(h, w["wv"].astype(F32)), nk)
    if cfg["rope_layout"][layer]:
        q, kk = _rope(q, float(cfg["rope_theta"])), \
            _rope(kk, float(cfg["rope_theta"]))
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][layer] and precision != "no_window" \
        else None
    a = _attention(q, kk, v, window, mm)
    x = x + mm(a.transpose(0, 2, 1, 3).reshape(b, t, nh * hd),
               w["wo"].astype(F32))
    u = _rms(x, w["ln2.g"], cfg["rms_norm_eps"])
    y = _experts(cfg, w, u.reshape(b * t, e), r.reshape(b * t, -1),
                 precision, mm)
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
    at the `positions` [B, P] asked for."""
    h = hidden(cfg, w, tokens, precision)
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    h = _rms(h, w["norm.g"], cfg["rms_norm_eps"])
    return nx.matmul(h, w["head"].astype(F32), _numeric(precision))
