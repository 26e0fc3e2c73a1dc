"""Plain reference for the `olmo-hybrid-7b` configuration.

allenai/Olmo-Hybrid-7B as its `config.json` gives it: a decoder of
hidden 3840 whose layers come in periods of four, three of linear
attention (a gated delta rule: 30 heads with keys of 96 and values of
192, a causal depthwise convolution of 4 taps, `linear_allow_neg_eigval`)
and one of full attention (30 heads of 128 over 30 K/V heads, no
positional encoding), every layer with a dense SiLU-gated FFN of 11008,
RMSNorm (eps 1e-6) on each sub-layer's OUTPUT, no bias, untied head over
100352 ids. Input x [T, E], residual stream float32:

    x1 = x  + rmsnorm(mixer(x);  g1)
    x2 = x1 + rmsnorm((silu(x1 @ Wg) * (x1 @ Wu)) @ Wd;  g2)
    logits = rmsnorm(x_L; gf) @ Whead

    full attention:
      q, k, v = x @ Wq, x @ Wk, x @ Wv
      q = rmsnorm(q; gq);  k = rmsnorm(k; gk)          over the whole projection
      a = softmax(q k^T / sqrt(128) + causal) v;  mixer = merge(a) @ Wo

    linear attention (gated delta rule), per head, S_0 = 0:
      q^, k^, v^ = silu(conv4(x @ Wq)), silu(conv4(x @ Wk)), silu(conv4(x @ Wv))
          conv4(z)[t] = sum_{i<4} c[i] * z[t - 3 + i]     causal, depthwise
      q_t = l2norm(q^_t) / sqrt(96);  k_t = l2norm(k^_t)
      beta_t  = 2 * sigmoid(x_t @ Wb)
      alpha_t = exp(-exp(A_log) * softplus(x_t @ Wa + dt_bias))
      S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
      o_t = S_t^T q_t
      mixer = merge(rmsnorm(o_t; gn) * silu(x_t @ Wz)) @ Wo

In straightforward `jax.numpy` and float32 (`Precision.HIGHEST`): no
kernel, no cache, no chunks. The recurrence runs token by token exactly
as written, the convolution is an explicit sum over four taps, attention
has an explicit causal mask. It imports nothing of the program.

What `config.json` does not say and this file assumes is listed in the
configuration file under `assumed`. Weights: 4.10 B float32 parameters
do not fit on the chip beside anything, so every leaf is drawn in
float32 and kept in the type the configuration serves it in (bfloat16;
the norms, `A_log` and `dt_bias` float32), and widened to float32 where
it is used.

`precision` is "f32"; a control, "bf16" or "fp8" (every matmul operand
rounded); or a planted fault at float32: "state_lost" (every linear
layer's state zeroed at the prefill/decode seam: after the position
`logits_at` is first asked for, a prompt's last) or "beta_single" (beta
not doubled: `linear_allow_neg_eigval` ignored).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.reference import numerics as nx

FAULTS = ("state_lost", "beta_single")
F32 = jnp.float32
_LINEAR = "linear_attention"
#: the sub-layers' output norms' scales are drawn around this (`layout`)
SUB_NORM = 0.25


def _kinds(cfg):
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def layout(cfg: Dict[str, Any]):
    """(name, shape, how drawn, kept in float32) of every leaf; how: a
    std, None for an RMSNorm scale (ones plus noise), ("norm", s) for
    one of s times that, "a_log" for the log of uniform (0, 16),
    "dt_bias" for the inverse softplus of log-uniform (0.001, 0.1)."""
    e, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    nh, hd = cfg["num_attention_heads"], e // cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"] * hd
    h = cfg["linear_num_value_heads"]
    nk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    nv = h * cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    # embedding at unit scale, the head at 1/sqrt(e) so logits are O(1),
    # the matrices at 0.02 at the published width (and as 1/sqrt(e) at a
    # test's). Every sub-layer's output is normed, so what its matrices'
    # scale decides is the gates: Wa small enough that alpha keeps to
    # (0.1, 1) over a stream whose size grows to sqrt(2 L + 1) (a state
    # that the next token forgets cannot be missed at the seam), Wb so
    # that beta spreads over (0, 2) and does not sit at its ends.
    # The sub-layers' output norms at SUB_NORM, not 1: a normed output
    # of unit size is a kick of 1 / |x| to the stream's direction
    # whatever the sub-layer computed, and an untrained stack of them
    # carries a bfloat16 rounding on at a gain over 1 a sub-layer: at
    # scale 1 the float32 reference with bfloat16 operands lay 7% of the
    # stream from itself after 8 layers (one position's first linear
    # layer turned over whole: there o_0 = beta (k_0 . q_0) v_0 is
    # normed a head, so its SIGN is that of k_0 . q_0, near nought for
    # independent q and k) and no limit lay between the program and the
    # fp8 control; at 0.25, with q's projection and taps sharing half
    # their variance with k's (`init_weights`: a token's query finds its
    # own key, as a trained one's does), 0.5% (CPU, a test's size).
    s = 0.02 * math.sqrt(3840.0 / e)
    sub = ("norm", SUB_NORM)
    out = [("embed", (v, e), 1.0, False),
           ("head", (e, v), 1.0 / math.sqrt(e), False),
           ("norm.g", (e,), None, True)]
    for i, kind in enumerate(_kinds(cfg)):
        p = f"l{i}."
        out += [(p + "n1.g", (e,), sub, True),
                (p + "n2.g", (e,), sub, True),
                (p + "wg", (e, f), s, False), (p + "wu", (e, f), s, False),
                (p + "wd", (f, e), s, False)]
        if kind == _LINEAR:
            out += [(p + "wq", (e, nk), s, False),
                    (p + "wk", (e, nk), s, False),
                    (p + "wv", (e, nv), s, False),
                    (p + "wz", (e, nv), s, False),
                    (p + "wa", (e, h), s / 20, False),
                    (p + "wb", (e, h), s / 4, False),
                    (p + "cq", (taps, nk), taps ** -0.5, False),
                    (p + "ck", (taps, nk), taps ** -0.5, False),
                    (p + "cv", (taps, nv), taps ** -0.5, False),
                    (p + "a_log", (h,), "a_log", True),
                    (p + "dt_bias", (h,), "dt_bias", True),
                    (p + "gn.g", (cfg["linear_value_head_dim"],), None, True),
                    (p + "wo", (nv, e), s, False)]
        else:
            out += [(p + "wq", (e, nh * hd), s, False),
                    (p + "wk", (e, nkv), s, False),
                    (p + "wv", (e, nkv), s, False),
                    (p + "qn.g", (nh * hd,), None, True),
                    (p + "kn.g", (nkv,), None, True),
                    (p + "wo", (nh * hd, e), s, False)]
    return out


@partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape, how, dtype):
    if how == "a_log":
        z = jnp.log(jax.random.uniform(key, shape, F32, 1e-3, 16.0))
    elif how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        z = jnp.log(jnp.expm1(dt))
    else:
        z = jax.random.normal(key, shape, F32)
        if how is None or isinstance(how, tuple):
            z = (1.0 if how is None else how[1]) * (1.0 + 0.1 * z)
        else:
            z = how * z
    return z.astype(dtype)


@jax.jit
def _shared(a, b):
    """`a` with half its variance b's."""
    return ((a.astype(F32) + b.astype(F32)) * math.sqrt(0.5)).astype(a.dtype)


def init_weights(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every leaf from the seed, drawn in float32 and kept in the type it
    is served in."""
    key = jax.random.PRNGKey(jnp.uint32(seed % (2 ** 32)))
    served = jnp.dtype(cfg["serving"]["weight_dtype"])
    w = {name: _leaf(jax.random.fold_in(key, i), shape, how,
                     jnp.dtype(F32) if keep else served)
         for i, (name, shape, how, keep) in enumerate(layout(cfg))}
    for i, kind in enumerate(_kinds(cfg)):
        if kind == _LINEAR:
            for q, k in (("wq", "wk"), ("cq", "ck")):
                w[f"l{i}.{q}"] = _shared(w[f"l{i}.{q}"], w[f"l{i}.{k}"])
    return w


def served_weights(cfg, w):
    """`init_weights` already keeps each leaf as it is served."""
    return w


def _numeric(precision: str) -> str:
    return "f32" if precision in FAULTS else precision


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _l2norm(x, eps):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _attention(q, k, v, mm, block: int = 512):
    """q [B, H, T, D] against k, v [B, Hkv, T, D] repeated to H heads, a
    block of queries at a time, under the causal mask."""
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


def _full_attention(cfg, w, x, mm):
    b, t, e = x.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = e // nh, cfg["rms_norm_eps"]

    def heads(z, n):
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)
    q = _rms(mm(x, w["wq"].astype(F32)), w["qn.g"], eps)
    k = _rms(mm(x, w["wk"].astype(F32)), w["kn.g"], eps)
    v = mm(x, w["wv"].astype(F32))
    a = _attention(heads(q, nh), heads(k, nkv), heads(v, nkv), mm)
    return mm(a.transpose(0, 2, 1, 3).reshape(b, t, nh * hd),
              w["wo"].astype(F32))


def _conv(z, c):
    """Causal depthwise: out[t] = sum_i c[i] * z[t - (taps - 1) + i],
    zeros before position 0. z [B, T, C], c [taps, C]."""
    taps, t = c.shape[0], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(c[i].astype(F32) * zp[:, i:i + t] for i in range(taps))


def _linear_attention(cfg, w, x, mm, precision, seam):
    b, t, _ = x.shape
    h = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    eps = cfg["rms_norm_eps"]
    q = jax.nn.silu(_conv(mm(x, w["wq"].astype(F32)), w["cq"]))
    k = jax.nn.silu(_conv(mm(x, w["wk"].astype(F32)), w["ck"]))
    v = jax.nn.silu(_conv(mm(x, w["wv"].astype(F32)), w["cv"]))
    q = _l2norm(q.reshape(b, t, h, dk), eps) / math.sqrt(dk)
    k = _l2norm(k.reshape(b, t, h, dk), eps)
    v = v.reshape(b, t, h, dv)
    beta = jax.nn.sigmoid(mm(x, w["wb"].astype(F32)))
    if precision != "beta_single":
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(w["a_log"]) * jax.nn.softplus(
        mm(x, w["wa"].astype(F32)) + w["dt_bias"]))

    def token(s, xs):                       # s [B, H, dk, dv]
        q, k, v, alpha, beta, at = xs       # [B, H, .], [B, H], the index
        if precision == "state_lost":
            s = jnp.where((at == seam + 1)[:, None, None, None], 0.0, s)
        a = alpha[..., None, None]
        held = jnp.sum(s * k[..., None], axis=-2)                # S^T k
        s = a * s + beta[..., None, None] * k[..., None] \
            * (v - alpha[..., None] * held)[..., None, :]
        return s, jnp.sum(s * q[..., None], axis=-2)             # S^T q

    by_token = [z.swapaxes(0, 1) for z in (q, k, v, alpha, beta)]
    _, o = lax.scan(token, jnp.zeros((b, h, dk, dv), F32),
                    (*by_token, jnp.arange(t)))
    o = _rms(o.swapaxes(0, 1), w["gn.g"], eps)             # [B, T, H, dv]
    z = jax.nn.silu(mm(x, w["wz"].astype(F32))).reshape(b, t, h, dv)
    return mm((o * z).reshape(b, t, h * dv), w["wo"].astype(F32))


def _block(cfg, w, x, kind: str, precision: str, seam):
    mm = partial(nx.matmul, precision=_numeric(precision))
    eps = cfg["rms_norm_eps"]
    mixed = _linear_attention(cfg, w, x, mm, precision, seam) \
        if kind == _LINEAR else _full_attention(cfg, w, x, mm)
    x = x + _rms(mixed, w["n1.g"], eps)
    y = mm(jax.nn.silu(mm(x, w["wg"].astype(F32)))
           * mm(x, w["wu"].astype(F32)), w["wd"].astype(F32))
    return x + _rms(y, w["n2.g"], eps)


def hidden(cfg, w, tokens, precision="f32", seam=None):
    """[B, T] 1-based ids -> [B, T, E] residual stream after the last
    block. `seam` [B]: each row's last prompt position, which only the
    fault "state_lost" reads."""
    x = w["embed"][tokens - 1].astype(F32)
    if seam is None:
        seam = jnp.full((tokens.shape[0],), -2, jnp.int32)
    for i, kind in enumerate(_kinds(cfg)):
        p = f"l{i}."
        sub = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
        x = _block(cfg, sub, x, kind, precision, seam)
    return x


def logits_at(cfg, w, tokens, positions, precision="f32"):
    """Logits [B, P, V] of the full causal forward over `tokens` [B, T]
    at the `positions` [B, P] asked for, the first of which is a
    prompt's last."""
    h = hidden(cfg, w, tokens, precision, seam=positions[:, 0])
    h = jnp.take_along_axis(h, positions[..., None], axis=1)
    h = _rms(h, w["norm.g"], cfg["rms_norm_eps"])
    return nx.matmul(h, w["head"].astype(F32), _numeric(precision))
