"""Latent attention's decode step as one Pallas kernel (`mla_decode`).

A decode step of `nn.LatentAttention` scores every head of a slot
against the slot's cached latent `c` and rotary keys `k_pe`, and reads
the same latent again for the values (the absorbed form:
nn/latent_attention.py). Plain XLA makes two passes over the whole
padded cache for that, one a product, with the softmax between them.
This kernel makes one, and only to each slot's length:

- one program a slot, over the slot's LIVE blocks alone (the block loop
  of ops/ragged_decode.py, which `gqa_decode` runs too): slot b reads
  `ceil((positions[b] + 1) / block)` blocks of `block` positions (an
  idle slot, at position 0, reads one) and nothing past them;
- a block of `c` [block, rank] and of `k_pe` [rope, block] comes from
  HBM into VMEM once, by a double-buffered DMA started while the block
  before it is computed (a slot's last block starts the next slot's
  first), and serves both the scores and the values through an online
  softmax (`m`, `l`, `acc` in float32), as the flash kernels do;
- the heads are the products' rows: [H, rank] against a block's
  [block, rank] for the scores, the probabilities in the cache's type
  against the same block for the values.

`k_pe` is handed over positions-minor, [B, rope, L]: the compiler keeps
the cache's [B, L, rope] buffer with its positions minor (64 lanes
would be half-empty the other way), so the swap is a bitcast, not a
copy.

`block_for(max_len)` says whether the kernel is the decode path: on a
TPU backend (or under `attention_kernel.INTERPRET`, the tests' hook)
where `max_len` is a whole number of blocks of 512, 256 or 128 (512 by a
chip sweep in the serving cell's decode program: PERF.md).
Elsewhere the layer keeps its plain-XLA form, which is also the tests'
reference.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import ragged_decode

#: positions a block, largest first: the first that divides `max_len`
BLOCKS = (512, 256, 128)


def block_for(max_len: int) -> Optional[int]:
    """The block the kernel reads a cache `max_len` deep in, or None
    where the decode step keeps the plain-XLA form."""
    if not ragged_decode.on_kernel_path():
        return None
    return next((b for b in BLOCKS if max_len % b == 0), None)


def positions_read(positions, max_len: int):
    """Cache positions a decode step reads a latent layer `max_len` deep
    for the slots at `positions` [B], float32: each slot's live blocks,
    whole, on the kernel's path; slots x `max_len` on the plain-XLA
    one."""
    return ragged_decode.positions_read(positions, max_len,
                                        block_for(max_len))


def _score(qs, blocks):
    (q, qpe), (c, pe) = qs, blocks                   # [blk, rank], [rope, blk]
    s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s + jax.lax.dot_general(qpe, pe, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)


def _value(p, blocks):
    c = blocks[0]
    return jax.lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def mla_decode(q_lat, q_pe, c_cache, pe_cache, positions, sm_scale: float,
               block: int, interpret: Optional[bool] = None):
    """softmax(mask(sm_scale (q_lat . c + q_pe . k_pe))) @ c for every
    slot and head: `q_lat` [B, H, rank] and `q_pe` [B, H, rope] (taken
    in the cache's type), the layer's `c_cache` [B, L, rank] and
    `pe_cache` [B, L, rope], each slot reading positions 0 ..
    `positions` [B]; the probabilities meet `c` in the cache's type.
    Returns o_lat [B, H, rank] float32."""
    from jax.experimental import pallas as pl

    b, h, rank = q_lat.shape
    length, rope = c_cache.shape[1], pe_cache.shape[-1]
    if length % block or pe_cache.shape[:2] != (b, length):
        raise ValueError(f"caches {c_cache.shape} / {pe_cache.shape} "
                         f"against blocks of {block}")
    return ragged_decode.ragged_decode(
        _score, _value,
        [q_lat.astype(c_cache.dtype), q_pe.astype(pe_cache.dtype)],
        [c_cache, jnp.swapaxes(pe_cache, 1, 2)],
        [lambda c, i, start: c.at[i, pl.ds(start, block), :],
         lambda pe, i, start: pe.at[i, :, pl.ds(start, block)]],
        [(block, rank), (rope, block)], positions, depth=length,
        block=block, sm_scale=sm_scale,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        name="mla_decode", interpret=interpret)
