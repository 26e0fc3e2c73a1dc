"""Plain reference of the sparse decoder the tests compare
`bigdl_tpu.models.decoder.SparseDecoderLM` with: float32 `jax.numpy`
under `jax.default_matmul_precision("highest")`, no kernel, no cache, no
sort. Every expert is applied to every token and masked by the top-k;
the window is an explicit mask. Written from the layer equations
(ISSUE 29; benchmarks/reference/smallthinker-21b.py is the benchmark's
own copy) and importing nothing of `bigdl_tpu`.

    h = rmsnorm(x; g1);  r = h @ Wr                  router BEFORE attention
    q, k, v = h Wq, h Wk, h Wv;  rope(q, k) where the layer has a base
    a = softmax(q k^T / sqrt(d) + M) v               query head j reads K/V head j // group
    x = x + merge(a) Wo
    u = rmsnorm(x; g2);  S = top-k of r;  w = softmax(r[S])
    x = x + sum_{e in S} w_e (relu(u Wg_e) * (u Wu_e)) Wd_e
    logits = rmsnorm(x_L; gf) Whead

`cfg`: vocab, hidden, heads, kv_heads, head_dim, experts, expert_dim,
top_k, eps, layers = [(window or None, rope base or None), ...].
"""

import math

import jax
import jax.numpy as jnp

SMALL = {"vocab": 128, "hidden": 64, "heads": 4, "kv_heads": 2,
         "head_dim": 16, "experts": 8, "expert_dim": 32, "top_k": 3,
         "eps": 1e-6, "max_len": 32,
         "layers": [(None, None), (8, 1.5e6), (8, 1.5e6), (8, 1.5e6)]}


def init_weights(cfg, seed):
    e, hd = cfg["hidden"], cfg["head_dim"]
    nq, nk = cfg["heads"] * hd, cfg["kv_heads"] * hd
    n, f = cfg["experts"], cfg["expert_dim"]
    shapes = {"embed": ((cfg["vocab"], e), 1.0),
              "head": ((e, cfg["vocab"]), e ** -0.5), "norm.g": ((e,), None)}
    for i in range(len(cfg["layers"])):
        p = f"l{i}."
        shapes.update({
            p + "ln1.g": ((e,), None), p + "ln2.g": ((e,), None),
            p + "wq": ((e, nq), 0.1), p + "wk": ((e, nk), 0.1),
            p + "wv": ((e, nk), 0.1), p + "wo": ((nq, e), 0.1),
            p + "router": ((e, n), e ** -0.5),
            p + "wg": ((n, e, f), 0.1), p + "wu": ((n, e, f), 0.1),
            p + "wd": ((n, f, e), 0.1)})
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = 1.0 + 0.1 * z if std is None else std * z
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    d, t = x.shape[-1], x.shape[-2]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def expert_mix(cfg, w, u, r, leave_out=None):
    """u [N, E] through ALL experts, masked by the top-k weights of the
    router logits r [N, n]; `leave_out` drops that expert (a fault)."""
    vals, idx = jax.lax.top_k(r, cfg["top_k"])
    gate = jnp.zeros_like(r).at[jnp.arange(r.shape[0])[:, None], idx].set(
        jax.nn.softmax(vals, axis=-1))
    out = jnp.zeros_like(u)
    for e in range(cfg["experts"]):
        if e == leave_out:
            continue
        y = (jax.nn.relu(u @ w["wg"][e]) * (u @ w["wu"][e])) @ w["wd"][e]
        out = out + gate[:, e:e + 1] * y
    return out


def block(cfg, w, x, window, theta):
    b, t, e = x.shape
    hd, nh, nk = cfg["head_dim"], cfg["heads"], cfg["kv_heads"]

    def heads(z, n):
        return z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    h = _rms(x, w["ln1.g"], cfg["eps"])
    r = h @ w["router"]
    q, k, v = heads(h @ w["wq"], nh), heads(h @ w["wk"], nk), \
        heads(h @ w["wv"], nk)
    if theta is not None:
        q, k = _rope(q, theta), _rope(k, theta)
    k, v = jnp.repeat(k, nh // nk, axis=1), jnp.repeat(v, nh // nk, axis=1)
    s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    qp, kp = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    keep = kp <= qp
    if window is not None:
        keep = keep & (kp > qp - window)
    a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1) @ v
    x = x + a.transpose(0, 2, 1, 3).reshape(b, t, nh * hd) @ w["wo"]
    u = _rms(x, w["ln2.g"], cfg["eps"])
    y = expert_mix(cfg, w, u.reshape(b * t, e), r.reshape(b * t, -1))
    return x + y.reshape(b, t, e)


def logits(cfg, w, tokens):
    """[B, T] 1-based ids -> [B, T, V] logits of the full causal forward."""
    with jax.default_matmul_precision("highest"):
        x = w["embed"][tokens - 1]
        for i, (window, theta) in enumerate(cfg["layers"]):
            p = f"l{i}."
            sub = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            x = block(cfg, sub, x, window, theta)
        return _rms(x, w["norm.g"], cfg["eps"]) @ w["head"]


def to_program(cfg, w):
    """The flat weights as `SparseDecoderLM`'s parameter tree (a test's
    plumbing, not part of the reference's arithmetic)."""
    tree = {"embed": w["embed"], "head": w["head"],
            "norm": {"weight": w["norm.g"]}}
    for i in range(len(cfg["layers"])):
        p = f"l{i}."
        tree[f"block{i}"] = {
            "ln1": {"weight": w[p + "ln1.g"]},
            "ln2": {"weight": w[p + "ln2.g"]},
            "attn": {n: w[p + n] for n in ("wq", "wk", "wv", "wo")},
            "router": w[p + "router"],
            "experts": {n: w[p + n] for n in ("wg", "wu", "wd")}}
    return tree
