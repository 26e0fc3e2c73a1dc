"""Plain reference of the latent-attention decoder the tests compare
`bigdl_tpu.models.decoder.DecoderLM` with: float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, no kernel, no cache, no
absorption, no sort. Attention is the EXPANDED form with an explicit
causal mask and the one rotary key a position broadcast to the heads;
every expert is applied to every token and masked by the top-k weights;
the shared expert is added. Written from the layer equations (ISSUE 39;
benchmarks/reference/kanana-2-30b-a3b.py is the benchmark's own copy)
and importing nothing of `bigdl_tpu`.

    h = rmsnorm(x; g1);  x1 = x + MLA(h);  u = rmsnorm(x1; g2);  x2 = x1 + FFN(u)
    logits = rmsnorm(x_L; gf) Whead

    MLA:   q = h Wq, per head [q_nope | q_pe];  a = h Wkva = [c~ | k_pe~]
           c = rmsnorm(c~; gkv);  k_pe = rope(k_pe~), q_pe = rope(q_pe)   pairs (2i, 2i+1)
           [k_nope_j | v_j] = c Wkvb[:, j]
           s_j = (q_nope_j k_nope_j^T + q_pe_j k_pe^T) / sqrt(nope + rope) + causal
           MLA = concat_j(softmax(s_j) v_j) Wo
    dense:   FFN = (silu(u Wg) * (u Wu)) Wd
    experts: p = sigmoid(u Wr);  S = top-k of (p + b);  w_e = scale p_e / (sum_S p + 1e-20)
             FFN = sum_{e in S} w_e (silu(u Wg_e) * (u Wu_e)) Wd_e + (silu(u Wsg) * (u Wsu)) Wsd

`cfg`: vocab, hidden, heads, nope, rope, value, rank, theta, ffn, experts,
expert_dim, top_k, shared, scale, eps, layers = ["dense" | "experts", ...].
"""

import math

import jax
import jax.numpy as jnp

SMALL = {"vocab": 128, "hidden": 64, "heads": 4, "nope": 16, "rope": 8,
         "value": 16, "rank": 32, "theta": 1e6, "ffn": 128, "experts": 16,
         "expert_dim": 32, "top_k": 3, "shared": 64, "scale": 2.448,
         "eps": 1e-6, "max_len": 64, "layers": ["dense"] + ["experts"] * 5}


def init_weights(cfg, seed):
    """The router spreads its logits by 2 and the bias by 0.2, so both
    tell in the choice; the rest at 0.1 (1/sqrt(hidden) and a little
    under)."""
    e, h, f = cfg["hidden"], cfg["heads"], cfg["expert_dim"]
    n = cfg["experts"]
    shapes = {"embed": ((cfg["vocab"], e), 1.0),
              "head": ((e, cfg["vocab"]), e ** -0.5), "norm.g": ((e,), None)}
    for i, kind in enumerate(cfg["layers"]):
        p = f"l{i}."
        shapes.update({
            p + "n1.g": ((e,), None), p + "n2.g": ((e,), None),
            p + "wq": ((e, h * (cfg["nope"] + cfg["rope"])), 0.1),
            p + "wkva": ((e, cfg["rank"] + cfg["rope"]), 0.2),
            p + "kvn.g": ((cfg["rank"],), None),
            p + "wkvb": ((cfg["rank"], h * (cfg["nope"] + cfg["value"])),
                         0.15),
            p + "wo": ((h * cfg["value"], e), 0.1)})
        if kind == "dense":
            shapes.update({p + "wg": ((e, cfg["ffn"]), 0.1),
                           p + "wu": ((e, cfg["ffn"]), 0.1),
                           p + "wd": ((cfg["ffn"], e), 0.1)})
        else:
            shapes.update({
                p + "router": ((e, n), 2.0 * e ** -0.5),
                p + "router_bias": ((n,), 0.2),
                p + "wg": ((n, e, f), 0.1), p + "wu": ((n, e, f), 0.1),
                p + "wd": ((n, f, e), 0.1),
                p + "wsg": ((e, cfg["shared"]), 0.1),
                p + "wsu": ((e, cfg["shared"]), 0.1),
                p + "wsd": ((cfg["shared"], e), 0.1)})
    key = jax.random.PRNGKey(seed)
    out = {}
    for i, (name, (shape, how)) in enumerate(sorted(shapes.items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = 1.0 + 0.1 * z if how is None else how * z
    return out


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """[..., T, D]: pair (2i, 2i+1) turned by position * theta^(-2i/D)."""
    t, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(cfg, w, x):
    b, t, _ = x.shape
    h, nope, dv, rank = cfg["heads"], cfg["nope"], cfg["value"], cfg["rank"]

    def heads(z):
        return z.reshape(b, t, h, -1).transpose(0, 2, 1, 3)
    q = heads(x @ w["wq"])
    a = x @ w["wkva"]
    c = _rms(a[..., :rank], w["kvn.g"], cfg["eps"])
    k_pe = rope(a[:, None, :, rank:], cfg["theta"])            # [B, 1, T, r]
    kv = heads(c @ w["wkvb"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], cfg["theta"])],
                        -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe, (b, h, t, cfg["rope"]))], -1)
    s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(nope + cfg["rope"])
    keep = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    o = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1) @ kv[..., nope:]
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * dv) @ w["wo"]


def gated(u, wg, wu, wd):
    return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd


def routing(cfg, w, u):
    """Dense weights [N, experts]: zero for an expert not chosen."""
    p = jax.nn.sigmoid(u @ w["router"])
    _, idx = jax.lax.top_k(p + w["router_bias"], cfg["top_k"])
    chosen = jnp.take_along_axis(p, idx, axis=-1)
    gate = cfg["scale"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(p).at[jnp.arange(p.shape[0])[:, None],
                                idx].set(gate)


def experts(cfg, w, u):
    weights = routing(cfg, w, u)
    out = gated(u, w["wsg"], w["wsu"], w["wsd"])
    for e in range(cfg["experts"]):
        out = out + weights[:, e:e + 1] * gated(u, w["wg"][e], w["wu"][e],
                                                w["wd"][e])
    return out


def block(cfg, w, x, kind):
    x = x + latent_attention(cfg, w, _rms(x, w["n1.g"], cfg["eps"]))
    u = _rms(x, w["n2.g"], cfg["eps"])
    if kind == "dense":
        return x + gated(u, w["wg"], w["wu"], w["wd"])
    return x + experts(cfg, w, u.reshape(-1, u.shape[-1])).reshape(x.shape)


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


def mixer_to_program(cfg, w):
    """One layer's latent attention as the program's layer takes it: the
    up-projection's key and value columns apart."""
    per_head = w["wkvb"].reshape(cfg["rank"], cfg["heads"], -1)
    return {"wq": w["wq"], "wkva": w["wkva"], "kv_norm": w["kvn.g"],
            "wuk": per_head[:, :, :cfg["nope"]].reshape(cfg["rank"], -1),
            "wuv": per_head[:, :, cfg["nope"]:].reshape(cfg["rank"], -1),
            "wo": w["wo"]}


def to_program(cfg, w):
    """The flat weights as `DecoderLM`'s parameter tree (a test's
    adapter, not the reference's business)."""
    tree = {"embed": w["embed"], "head": w["head"],
            "norm": {"weight": w["norm.g"]}}
    for i, kind in enumerate(cfg["layers"]):
        s = sub(w, i)
        blk = {"ln1": {"weight": s["n1.g"]}, "ln2": {"weight": s["n2.g"]},
               "attn": mixer_to_program(cfg, s)}
        if kind == "dense":
            blk["ffn"] = {n: s[n] for n in ("wg", "wu", "wd")}
        else:
            blk.update({
                "router": s["router"], "router_bias": s["router_bias"],
                "experts": {n: s[n] for n in ("wg", "wu", "wd")},
                "shared": {"wg": s["wsg"], "wu": s["wsu"], "wd": s["wsd"]}})
        tree[f"block{i}"] = blk
    return tree
