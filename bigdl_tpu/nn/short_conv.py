"""The gated short convolution (LFM2's `conv` layers): a token mixer with
no attention and no state but the last few rows it convolved.

    B, C, x = split(h @ in_proj, 3)          three [T, E]
    u_t = B_t * x_t
    z_t = sum_{j < taps} w_j * u_{t - taps + 1 + j}    zeros before 0
    y   = (C * z) @ out_proj

a causal depthwise convolution of `taps` taps (3 in LFM2) over the gated
product, no activation and no bias anywhere. What a serving slot keeps
is the convolution's tail alone, the last `taps - 1` rows of u:
nn/kv_cache.py's tail kind. `ShortConv` has the attention layers' three
entry points:

* `apply_prefill`: right-padded rows of real `lengths`; the tail handed
  to the cache is the rows of u at each row's last `taps - 1` REAL
  positions (zeros before position 0). Causal: no real position reads a
  row's padding.
* `apply_step`: one token a slot from its tail; the tail is shifted by
  one row.
* `apply`: `apply_prefill` over whole rows.

The projections take their operands in the weights' type with float32
accumulators; B * x is rounded to the weights' type once, as it is
convolved and as the tail keeps it, so that the prefill and the step
convolve the same numbers; the taps, the gate by C and the sums are
float32; the result is float32 (the output projection's accumulator),
for a residual stream kept in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bigdl_tpu.nn import kv_cache
from bigdl_tpu.nn.initialization import Xavier
from bigdl_tpu.nn.module import Module

F32 = jnp.float32


class ShortConv(Module):
    """`embed_dim` channels through `in_proj` [E, 3E] (B, C, x in that
    order), a depthwise convolution `conv` [taps, E] over B * x, and
    `out_proj` [E, E]. Input [B, T, E] in any float type; the result is
    float32."""

    def __init__(self, embed_dim: int, taps: int = 3, name=None):
        super().__init__(name)
        if taps < 2:
            raise ValueError(f"taps must be >= 2, got {taps}")
        self.e, self.taps = embed_dim, taps

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        xav = Xavier()
        return {"in_proj": xav(k1, (self.e, 3 * self.e)),
                "conv": jax.random.normal(k2, (self.taps, self.e))
                * self.taps ** -0.5,
                "out_proj": xav(k3, (self.e, self.e))}

    def _gated_rows(self, params, x):
        """(u [B, T, E] in the weights' type, C [B, T, E] float32)."""
        with jax.named_scope("conv project"):
            x = x.astype(params["in_proj"].dtype)
            bcx = jnp.dot(x, params["in_proj"], preferred_element_type=F32)
            b, c, z = jnp.split(bcx, 3, axis=-1)
            return (b * z).astype(x.dtype), c

    def _taps(self, params, u, c):
        """`u` [B, taps - 1 + T, E], what came before in its first
        taps - 1 rows, and C [B, T, E] -> the output [B, T, E] float32."""
        with jax.named_scope("conv taps"):
            t = c.shape[1]
            w = params["conv"].astype(F32)
            z = sum(u[:, i:i + t].astype(F32) * w[i]
                    for i in range(self.taps))
            y = (c * z).astype(params["out_proj"].dtype)
        with jax.named_scope("conv project"):
            return jnp.dot(y, params["out_proj"], preferred_element_type=F32)

    def apply_prefill(self, params, x, lengths=None):
        """(out [B, T, E], tail [B, taps - 1, E]: u at each row's last
        taps - 1 real positions, zeros before position 0); `lengths` [B]
        or None for whole rows."""
        b, t, _ = x.shape
        if lengths is None:
            lengths = jnp.full((b,), t, jnp.int32)
        with jax.named_scope("short conv"):
            u, c = self._gated_rows(params, x)
            out = self._taps(params, jnp.pad(
                u, ((0, 0), (self.taps - 1, 0), (0, 0))), c)
            at = lengths.astype(jnp.int32)[:, None] - self.taps + 1 \
                + jnp.arange(self.taps - 1)[None, :]
            last = jnp.take_along_axis(u, jnp.maximum(at, 0)[..., None],
                                       axis=1)
            return out, jnp.where((at >= 0)[..., None], last, 0)

    def apply(self, params, input, ctx):
        return self.apply_prefill(params, input)[0]

    def init_cache(self, slots: int, max_len: int, dtype=jnp.float32):
        return (kv_cache.init_tail(slots, self.taps, self.e, dtype),)

    def apply_step(self, params, x, tail, positions=None):
        """One new token a row: `x` [B, 1, E] from the row's `tail`
        [B, taps - 1, E], which is shifted by the token's row of u.
        `positions` is the attention layers' argument and is not read.
        Returns (out [B, 1, E], tail)."""
        with jax.named_scope("short conv"):
            u, c = self._gated_rows(params, x)
            rows = jnp.concatenate([tail, u.astype(tail.dtype)], axis=1)
            return self._taps(params, rows, c), rows[:, 1:]
