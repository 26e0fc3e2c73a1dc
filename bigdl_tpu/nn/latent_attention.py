"""Multi-head latent attention (MLA): every head's keys and values are
rebuilt from ONE low-rank latent a position, and a serving slot keeps
that latent and nothing a head.

`n_head` heads whose query and key have a part without position
(`nope_dim`) and a rotary part (`rope_dim`), and whose value is
`value_dim` wide. For the layer's input h [T, E]:

    q   = h @ wq                       per head [q_nope | q_pe]
    a   = h @ wkva                     [c~ (rank) | k_pe~ (rope_dim)]
    c   = rmsnorm(c~; kv_norm)         the latent, one a position
    k_pe = rope(k_pe~), q_pe = rope(q_pe)     k_pe one a position too
    k_nope_j = c @ wuk[:, j],  v_j = c @ wuv[:, j]
    s_j[t, t'] = (q_nope_j[t] . k_nope_j[t'] + q_pe_j[t] . k_pe[t'])
                 / sqrt(nope_dim + rope_dim),   t' <= t
    out = concat_j(softmax(s_j) v_j) @ wo

Two paths over that mathematics. A prefill computes it as written (the
EXPANDED form: 2 x (nope + rope + value) operations a pair and head)
through the grouped flash forward, whose `v` has a width of its own,
and hands back `c` and `k_pe` for the cache, never K or V. A decode
step ABSORBS `wuk` into the query and `wuv` into the result,

    q_lat_j = q_nope_j @ wuk[:, j]^T              [rank]
    s_j[t'] = (q_lat_j . c[t'] + q_pe_j . k_pe[t']) / sqrt(...)
    o_j = (softmax(s_j) @ c) @ wuv[:, j]

so that it reads the cached latent for all heads at once and never
rebuilds a key or a value (at 32 slots x 16384 positions that would be
terabytes of products a layer); the absorbed form costs 2 x (2 rank +
rope) a pair and head, which is why a prefill does not take it. On a
TPU the step's read is the `mla_decode` kernel (ops/
latent_decode_kernel.py): each position's latent and rotary key once
for the scores and the values, and only each slot's live blocks;
elsewhere it is `attend_absorbed`, plain XLA over the whole depth.

Input [B, T, E] in any float type: cast to the weights' type for the
projections; the latent's RMSNorm, the rotary angles, the scores, the
softmax and every accumulator are float32; the result is float32 (the
output projection's accumulator), for a float32 residual stream. The
cache of a layer is the pair `init_cache` gives (nn/kv_cache.py:
`init_latent`), position p at index p.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.attention import project_heads, rope
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module
from bigdl_tpu.nn.normalization import rms_norm
from bigdl_tpu.ops import latent_decode_kernel
from bigdl_tpu.ops.attention_kernel import NEG_INF, causal_grouped_attention


class LatentAttention(Module):
    """Causal self-attention over a shared latent of `rank`: prefill
    expanded, decode absorbed (the `mla_decode` kernel on a TPU, plain
    XLA elsewhere; see the module's text). Weights: `wq` [E, H (nope +
    rope)], `wkva` [E, rank + rope], `kv_norm` [rank] float32, `wuk`
    [rank, H nope], `wuv` [rank, H value] (the published `kv_b_proj`,
    its key and value columns apart so that a decode step slices
    nothing), `wo` [H value, E]; no bias."""

    def __init__(self, embed_dim: int, n_head: int, nope_dim: int,
                 rope_dim: int, value_dim: int, rank: int,
                 rope_base: float = 10000.0, eps: float = 1e-6, name=None):
        super().__init__(name)
        if rope_dim % 2:
            raise ValueError(f"rope_dim must be even, got {rope_dim}")
        self.e, self.h = embed_dim, n_head
        self.nope, self.rope, self.dv, self.rank = \
            nope_dim, rope_dim, value_dim, rank
        self.rope_base, self.eps = rope_base, eps
        self.sm_scale = (nope_dim + rope_dim) ** -0.5

    def init(self, rng):
        k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
        xav = Xavier()
        return {"wq": xav(k1, (self.e, self.h * (self.nope + self.rope))),
                "wkva": xav(k2, (self.e, self.rank + self.rope)),
                "kv_norm": jnp.ones((self.rank,)),
                "wuk": xav(k3, (self.rank, self.h * self.nope)),
                "wuv": xav(k4, (self.rank, self.h * self.dv)),
                "wo": xav(k5, (self.h * self.dv, self.e))}

    def _project(self, params, x, positions=None):
        """(q_nope [B, H, T, nope], q_pe [B, H, T, rope] rotated, the
        latent c [B, T, rank] normed, k_pe [B, T, rope] rotated), at
        `positions` ([T], [B, T] or None = `arange`); c and k_pe in the
        weights' type, as every head will read them."""
        with jax.named_scope("mla project"):
            b, t, _ = x.shape
            dtype = params["wq"].dtype
            x = x.astype(dtype)  # a float32 residual stream
            q = jnp.transpose((x @ params["wq"]).reshape(
                b, t, self.h, self.nope + self.rope), (0, 2, 1, 3))
            a = jnp.dot(x, params["wkva"],
                        preferred_element_type=jnp.float32)
            c = rms_norm(a[..., :self.rank], params["kv_norm"], self.eps)
            k_pe = rope(a[:, None, :, self.rank:], positions,
                        self.rope_base)[:, 0]
            q_pe = rope(q[..., self.nope:], positions, self.rope_base)
            return q[..., :self.nope], q_pe, c.astype(dtype), \
                k_pe.astype(dtype)

    def _heads(self, z, width):  # [B, T, H width] -> [B, H, T, width]
        b, t, _ = z.shape
        return jnp.transpose(z.reshape(b, t, self.h, width), (0, 2, 1, 3))

    def apply_prefill(self, params, x, lengths=None):
        """(out [B, T, E], c [B, T, rank], k_pe [B, T, rope]) of the
        whole sequence in the expanded form; c and k_pe are what a
        serving prefill commits. `lengths` is the recurrent layers'
        argument and is not read: under the causal mask no real position
        sees a row's padding."""
        with jax.named_scope("latent attention"):
            q_nope, q_pe, c, k_pe = self._project(params, x)
            with jax.named_scope("mla expand"):
                k_nope = self._heads(c @ params["wuk"], self.nope)
                v = self._heads(c @ params["wuv"], self.dv)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_pe[:, None], k_nope.shape[:3] + (self.rope,))], -1)
                q = jnp.concatenate([q_nope, q_pe], -1)
            o = causal_grouped_attention(q, k, v)
            return project_heads(o, params["wo"]), c, k_pe

    def apply(self, params, input, ctx):
        return self.apply_prefill(params, input)[0]

    def init_cache(self, slots: int, max_len: int, dtype=jnp.float32):
        return kv_cache.init_latent(slots, max_len, self.rank, self.rope,
                                    dtype)

    def attend_absorbed(self, q_lat, q_pe, c_cache, pe_cache, positions):
        """The plain-XLA read of the absorbed form: `q_lat` [B, H, rank]
        and `q_pe` [B, H, rope] in the weights' type score the whole
        depth of `c_cache` [B, L, rank] and `pe_cache` [B, L, rope], and
        the probabilities, cast to the weights' type, read `c_cache`
        again. Returns o_lat [B, H, rank] float32. What `mla_decode`
        computes in one pass over each slot's live blocks."""
        s = jnp.einsum("bhr,blr->bhl", q_lat, c_cache,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("bhp,blp->bhl", q_pe, pe_cache,
                         preferred_element_type=jnp.float32)
        keep = kv_cache.step_mask(c_cache.shape[1], positions)[:, 0]
        p = jax.nn.softmax(jnp.where(keep, s * self.sm_scale, NEG_INF),
                           axis=-1)
        return jnp.einsum("bhl,blr->bhr", p.astype(q_lat.dtype), c_cache,
                          preferred_element_type=jnp.float32)

    def apply_step(self, params, x, c_cache, pe_cache, positions):
        """One new token a row in the absorbed form: `x` [B, 1, E] at
        `positions` [B] against the layer's latent cache [B, L, rank]
        and rotary keys [B, L, rope]. Writes the token's c and k_pe,
        then all heads read the latent: through `mla_decode`, once and
        only to each slot's length, where `latent_decode_kernel.block_for`
        gives a block; else `attend_absorbed`. Returns (out [B, 1, E],
        c_cache, pe_cache)."""
        with jax.named_scope("latent attention"):
            q_nope, q_pe, c, k_pe = self._project(
                params, x, positions=positions[:, None])
            c_cache = kv_cache.write(c_cache, c.astype(c_cache.dtype),
                                     positions)
            pe_cache = kv_cache.write(pe_cache, k_pe.astype(pe_cache.dtype),
                                      positions)
            dtype = q_nope.dtype
            with jax.named_scope("mla absorb"):
                wuk = params["wuk"].reshape(self.rank, self.h, self.nope)
                q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0], wuk,
                                   preferred_element_type=jnp.float32)
            with jax.named_scope("mla attend"):
                block = latent_decode_kernel.block_for(c_cache.shape[1])
                attend = self.attend_absorbed if block is None else \
                    functools.partial(latent_decode_kernel.mla_decode,
                                      sm_scale=self.sm_scale, block=block)
                o_lat = attend(q_lat.astype(dtype), q_pe[:, :, 0], c_cache,
                               pe_cache, positions)
            with jax.named_scope("mla absorb"):
                wuv = params["wuv"].reshape(self.rank, self.h, self.dv)
                o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(dtype), wuv,
                               preferred_element_type=jnp.float32)
            out = project_heads(o.astype(dtype)[:, :, None], params["wo"])
            return out, c_cache, pe_cache
