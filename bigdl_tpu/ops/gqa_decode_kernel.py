"""Grouped-query attention's decode step as one Pallas kernel
(`gqa_decode`).

A decode step of `nn.GroupedQueryAttention` scores the `group` query
heads of each K/V head against that head's cached keys and reads its
values: plain XLA (`grouped_attention` under `kv_cache.step_mask`)
reads every slot's K and V to the buffer's whole depth, whatever the
slot's length. This kernel reads them only to each slot's length, on
the block loop `mla_decode` runs (ops/ragged_decode.py):

- slot b's live positions are `min(positions[b] + 1, depth)`, at
  indices 0 .. that - 1: `depth` is `max_len` for a full layer, and
  `window` for a ring, whose indices past the position before it has
  wrapped hold nothing yet and all of whose indices are live after;
- one program a slot reads its live blocks of K and V alone,
  `[kv_heads, block, head_dim]` each, every K/V head at once (the
  products batched over the heads, the group's query heads as each
  head's rows); an idle slot, at position 0, reads one block;
- the products are grouped_attention's: scores in float32 times
  `head_dim ** -0.5`, the probabilities in the cache's type against V,
  a float32 accumulator, the result in q's type; they differ only in
  the order of a float sum (the online softmax rescales per block) and
  in where the probabilities are rounded (the loop divides by their sum
  last).

`block_for(...)` says whether the kernel is the decode path and in
which block, from the shapes the call sees. Elsewhere the layer keeps
the plain-XLA form, which is also the tests' reference.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import ragged_decode

#: positions a block, smallest first
BLOCKS = (128, 256, 512)
#: bytes a block's K and V carry together at the least: a smaller block
#: pays the loop's turn (a DMA's start and wait, the softmax's rescale)
#: on too few bytes (on a v5e `mla_decode`'s blocks of 295 KB read 20%
#: slower than its 590 KB ones: PERF.md), a larger one reads more past
#: each slot's length
_MIN_BLOCK_BYTES = 512 << 10


def block_for(depth: int, kv_heads: int, head_dim: int, itemsize: int
              ) -> Optional[int]:
    """The block the kernel reads a K/V cache `depth` deep of `kv_heads`
    heads of `head_dim` in elements of `itemsize` bytes, or None where
    the decode step keeps the plain-XLA form: off the kernel's path, or
    where no block divides the depth."""
    if not ragged_decode.on_kernel_path():
        return None
    blocks = [b for b in BLOCKS if depth % b == 0]
    if not blocks:
        return None
    position = 2 * kv_heads * head_dim * itemsize
    return next((b for b in blocks if b * position >= _MIN_BLOCK_BYTES),
                blocks[-1])


def cache_block(k_cache) -> Optional[int]:
    """`block_for` a cache buffer [slots, kv_heads, depth, head_dim]."""
    _, hk, depth, hd = k_cache.shape
    return block_for(depth, hk, hd, jnp.dtype(k_cache.dtype).itemsize)


def positions_read(positions, k_cache):
    """Cache positions a decode step reads of the layer whose K buffer
    is `k_cache`, for the slots at `positions` [B], float32: each slot's
    live blocks on the kernel's path, slots x depth on the plain-XLA
    one."""
    return ragged_decode.positions_read(positions, k_cache.shape[2],
                                        cache_block(k_cache))


def _score(qs, blocks):
    q, k = qs[0], blocks[0]                  # [hk, g, hd], [hk, blk, hd]
    return jax.lax.dot_general(
        q, k.astype(q.dtype), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _value(p, blocks):
    v = blocks[1]                            # [hk, blk, hd]
    return jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def gqa_decode(q, k_cache, v_cache, positions, block: int,
               interpret: Optional[bool] = None):
    """softmax(mask(q . k / sqrt(hd))) @ v for one query row a slot:
    `q` [B, H, 1, hd] over the layer's `k_cache` / `v_cache`
    [B, Hkv, depth, hd] (query head j reads K/V head j // (H // Hkv)),
    each slot reading its `min(positions[b] + 1, depth)` live indices in
    blocks of `block`, one program a slot over all its K/V heads.
    Returns [B, H, 1, hd] in q's type."""
    from jax.experimental import pallas as pl

    b, h, t, hd = q.shape
    _, hk, depth, _ = k_cache.shape
    if t != 1 or h % hk or v_cache.shape != k_cache.shape \
            or depth % block:
        raise ValueError(f"q {q.shape} over K/V {k_cache.shape} / "
                         f"{v_cache.shape} against blocks of {block}")
    dtype = q.dtype
    q = q.astype(jnp.promote_types(dtype, k_cache.dtype))
    o = ragged_decode.ragged_decode(
        _score, _value, [q.reshape(b, hk, h // hk, hd)], [k_cache, v_cache],
        [lambda c, i, start: c.at[i, :, pl.ds(start, block), :]] * 2,
        [(hk, block, hd)] * 2, positions, depth=depth, block=block,
        sm_scale=hd ** -0.5,
        out_shape=jax.ShapeDtypeStruct((b, hk, h // hk, hd), dtype),
        name="gqa_decode", interpret=interpret)
    return o.reshape(b, h, 1, hd)
