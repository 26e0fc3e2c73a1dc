"""Plain reference of the hybrid decoder the tests compare
`bigdl_tpu.models.decoder.DecoderLM` with: float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, no kernel, no cache, no
chunks. The gated delta rule runs token by token exactly as written, the
convolution is an explicit sum over its taps, attention has an explicit
causal mask. Written from the layer equations (ISSUE 36;
benchmarks/reference/olmo-hybrid-7b.py is the benchmark's own copy) and
importing nothing of `bigdl_tpu`.

    x1 = x  + rmsnorm(mixer(x);  g1)                 the norm on the OUTPUT
    x2 = x1 + rmsnorm((silu(x1 Wg) * (x1 Wu)) Wd;  g2)
    logits = rmsnorm(x_L; gf) Whead

    "full":   q, k, v = x Wq, x Wk, x Wv;  q = rmsnorm(q; gq), k = rmsnorm(k; gk)
              over the whole projection;  no positional encoding
              a = softmax(q k^T / sqrt(d) + causal) v;  mixer = merge(a) Wo
    "linear": q^, k^, v^ = silu(conv(x Wq)), silu(conv(x Wk)), silu(conv(x Wv))
              conv(z)[t] = sum_i c[i] z[t - (taps - 1) + i]
              q_t = l2norm(q^_t) / sqrt(dk);  k_t = l2norm(k^_t)     per head
              beta_t = 2 sigmoid(x_t Wb);  alpha_t = exp(-exp(A_log) softplus(x_t Wa + dt_bias))
              S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T;  o_t = S_t^T q_t
              mixer = merge(rmsnorm(o_t; gn) * silu(x_t Wz)) Wo

`cfg`: vocab, hidden, heads, kv_heads, head_dim, ffn, lin_heads, lin_key,
lin_value, taps, eps, layers = ["linear" | "full", ...].
"""

import math

import jax
import jax.numpy as jnp

SMALL = {"vocab": 128, "hidden": 64, "heads": 4, "kv_heads": 4,
         "head_dim": 16, "ffn": 128, "lin_heads": 4, "lin_key": 8,
         "lin_value": 16, "taps": 4, "eps": 1e-6, "max_len": 64, "chunk": 8,
         "layers": ["linear", "linear", "linear", "full"] * 2}


def init_weights(cfg, seed):
    """Gates drawn so that the state matters: exp(A_log) uniform in
    (0, 16), softplus(dt_bias) log-uniform in (0.001, 0.1), Wa small, so
    alpha lies in (0.1, 1) and is not one number for every token."""
    e, hd, f = cfg["hidden"], cfg["head_dim"], cfg["ffn"]
    nq, nk = cfg["heads"] * hd, cfg["kv_heads"] * hd
    h = cfg["lin_heads"]
    lk, lv = h * cfg["lin_key"], h * cfg["lin_value"]
    shapes = {"embed": ((cfg["vocab"], e), 1.0),
              "head": ((e, cfg["vocab"]), e ** -0.5), "norm.g": ((e,), None)}
    for i, kind in enumerate(cfg["layers"]):
        p = f"l{i}."
        shapes.update({p + "n1.g": ((e,), None), p + "n2.g": ((e,), None),
                       p + "wg": ((e, f), 0.1), p + "wu": ((e, f), 0.1),
                       p + "wd": ((f, e), 0.1)})
        if kind == "linear":
            shapes.update({
                p + "wq": ((e, lk), 0.1), p + "wk": ((e, lk), 0.1),
                p + "wv": ((e, lv), 0.1), p + "wz": ((e, lv), 0.1),
                p + "wa": ((e, h), 0.02), p + "wb": ((e, h), 0.1),
                p + "cq": ((cfg["taps"], lk), 0.5),
                p + "ck": ((cfg["taps"], lk), 0.5),
                p + "cv": ((cfg["taps"], lv), 0.5),
                p + "a_log": ((h,), "a_log"), p + "dt_bias": ((h,), "dt_bias"),
                p + "gn.g": ((cfg["lin_value"],), None),
                p + "wo": ((lv, e), 0.1)})
        else:
            shapes.update({
                p + "wq": ((e, nq), 0.1), p + "wk": ((e, nk), 0.1),
                p + "wv": ((e, nk), 0.1), p + "wo": ((nq, e), 0.1),
                p + "qn.g": ((nq,), None), p + "kn.g": ((nk,), None)})
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, (shape, how)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if how == "a_log":
            out[name] = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                   1e-3, 16.0))
        elif how == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            out[name] = jnp.log(jnp.expm1(dt))
        else:
            z = jax.random.normal(k, shape, jnp.float32)
            out[name] = 1.0 + 0.1 * z if how is None else how * z
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _l2norm(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def full_attention(cfg, w, x):
    b, t, _ = x.shape
    hd, nh, nk = cfg["head_dim"], cfg["heads"], cfg["kv_heads"]

    def heads(z, n):
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)
    q = heads(_rms(x @ w["wq"], w["qn.g"], cfg["eps"]), nh)
    k = heads(_rms(x @ w["wk"], w["kn.g"], cfg["eps"]), nk)
    v = heads(x @ w["wv"], nk)
    k, v = jnp.repeat(k, nh // nk, axis=1), jnp.repeat(v, nh // nk, axis=1)
    s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1) @ v
    return a.transpose(0, 2, 1, 3).reshape(b, t, nh * hd) @ w["wo"]


def conv(z, c):
    taps, t = c.shape[0], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(c[i] * zp[:, i:i + t] for i in range(taps))


def gates(w, x):
    """(alpha, beta) [B, T, H]."""
    beta = 2.0 * jax.nn.sigmoid(x @ w["wb"])
    alpha = jnp.exp(-jnp.exp(w["a_log"])
                    * jax.nn.softplus(x @ w["wa"] + w["dt_bias"]))
    return alpha, beta


def delta_rule(q, k, v, alpha, beta):
    """The recurrence token by token: q, k [B, T, H, dk], v
    [B, T, H, dv], alpha, beta [B, T, H] -> (o [B, T, H, dv], every
    state [B, T, H, dk, dv])."""
    b, _, h, dk = q.shape

    def token(s, xs):
        q, k, v, alpha, beta = xs
        held = jnp.einsum("bhkv,bhk->bhv", s, k)               # S^T k
        s = alpha[..., None, None] * s + beta[..., None, None] \
            * k[..., None] * (v - alpha[..., None] * held)[..., None, :]
        return s, (jnp.einsum("bhkv,bhk->bhv", s, q), s)

    _, (o, states) = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(z.swapaxes(0, 1) for z in (q, k, v, alpha, beta)))
    return o.swapaxes(0, 1), states.swapaxes(0, 1)


def linear_qkv(cfg, w, x):
    b, t, _ = x.shape
    h, dk, dv = cfg["lin_heads"], cfg["lin_key"], cfg["lin_value"]
    q = jax.nn.silu(conv(x @ w["wq"], w["cq"])).reshape(b, t, h, dk)
    k = jax.nn.silu(conv(x @ w["wk"], w["ck"])).reshape(b, t, h, dk)
    v = jax.nn.silu(conv(x @ w["wv"], w["cv"])).reshape(b, t, h, dv)
    return _l2norm(q, cfg["eps"]) / math.sqrt(dk), _l2norm(k, cfg["eps"]), v


def linear_attention(cfg, w, x):
    b, t, _ = x.shape
    q, k, v = linear_qkv(cfg, w, x)
    o, _ = delta_rule(q, k, v, *gates(w, x))
    z = jax.nn.silu(x @ w["wz"]).reshape(o.shape)
    return (_rms(o, w["gn.g"], cfg["eps"]) * z).reshape(b, t, -1) @ w["wo"]


def block(cfg, w, x, kind):
    mixer = linear_attention if kind == "linear" else full_attention
    x = x + _rms(mixer(cfg, w, x), w["n1.g"], cfg["eps"])
    y = (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]
    return x + _rms(y, w["n2.g"], cfg["eps"])


def sub(w, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def logits(cfg, w, tokens):
    """[B, T] 1-based ids -> [B, T, vocab] logits of the full causal
    forward."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens - 1]
        for i, kind in enumerate(cfg["layers"]):
            x = block(cfg, sub(w, i), x, kind)
        return _rms(x, w["norm.g"], cfg["eps"]) @ w["head"]


def mixer_to_program(w, kind):
    """One layer's mixer weights as the program's layer takes them."""
    if kind == "full":
        return {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
                "q_norm": w["qn.g"], "k_norm": w["kn.g"]}
    return {**{n: w[n] for n in ("wq", "wk", "wv", "wz", "wa", "wb", "a_log",
                                 "dt_bias", "wo")},
            "conv": jnp.concatenate([w["cq"], w["ck"], w["cv"]], axis=1),
            "norm": w["gn.g"]}


def to_program(cfg, w):
    """The flat weights as `DecoderLM`'s parameter tree (a test's
    adapter, not the reference's business)."""
    tree = {"embed": w["embed"], "head": w["head"],
            "norm": {"weight": w["norm.g"]}}
    for i, kind in enumerate(cfg["layers"]):
        s = sub(w, i)
        tree[f"block{i}"] = {
            "ln1": {"weight": s["n1.g"]}, "ln2": {"weight": s["n2.g"]},
            "attn": mixer_to_program(s, kind),
            "ffn": {"wg": s["wg"], "wu": s["wu"], "wd": s["wd"]}}
    return tree
