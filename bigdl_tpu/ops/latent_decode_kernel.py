"""Latent attention's decode step as one Pallas kernel (`mla_decode`).

A decode step of `nn.LatentAttention` scores every head of a slot
against the slot's cached latent `c` and rotary keys `k_pe`, and reads
the same latent again for the values (the absorbed form:
nn/latent_attention.py). Plain XLA makes two passes over the whole
padded cache for that, one a product, with the softmax between them.
This kernel makes one, and only to each slot's length:

- one program a slot, over the slot's LIVE blocks alone: slot b reads
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

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from bigdl_tpu.ops import attention_kernel
from bigdl_tpu.ops.attention_kernel import NEG_INF

#: positions a block, largest first: the first that divides `max_len`
BLOCKS = (512, 256, 128)


def block_for(max_len: int) -> Optional[int]:
    """The block the kernel reads a cache `max_len` deep in, or None
    where the decode step keeps the plain-XLA form."""
    if not (jax.default_backend() == "tpu" or attention_kernel.INTERPRET):
        return None
    return next((b for b in BLOCKS if max_len % b == 0), None)


def positions_read(positions, max_len: int):
    """Cache positions a decode step reads a latent layer `max_len` deep
    for the slots at `positions` [B], float32: each slot's live blocks,
    whole, on the kernel's path; slots x `max_len` on the plain-XLA
    one."""
    block = block_for(max_len)
    if block is None:
        return jnp.float32(positions.shape[0] * max_len)
    return jnp.sum((positions // block + 1) * block).astype(jnp.float32)


def _mla_decode_kernel(pos_ref, q_ref, qpe_ref, c_hbm, pe_hbm, o_ref,
                       c_buf, pe_buf, sem, count, *, block: int,
                       sm_scale: float):
    """One program = one slot. `count` holds the blocks read before this
    slot, so block g of the whole call lies in buffer g % 2."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, slots = pl.program_id(0), pl.num_programs(0)
    h = q_ref.shape[1]

    def fetch(slot, j, buf):
        return (pltpu.make_async_copy(
                    c_hbm.at[slot, pl.ds(j * block, block), :],
                    c_buf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(
                    pe_hbm.at[slot, :, pl.ds(j * block, block)],
                    pe_buf.at[buf], sem.at[1, buf]))

    @pl.when(b == 0)
    def _():
        count[0] = 0
        for copy in fetch(0, 0, 0):
            copy.start()

    pos = pos_ref[b]
    n = pos // block + 1
    first = count[0]
    q, qpe = q_ref[0], qpe_ref[0]                        # [H, rank], [H, rope]

    def body(j, carry):
        acc, m, l = carry
        buf = (first + j) % 2

        @pl.when(j + 1 < n)
        def _():
            for copy in fetch(b, j + 1, 1 - buf):
                copy.start()

        @pl.when(jnp.logical_and(j + 1 == n, b + 1 < slots))
        def _():
            for copy in fetch(b + 1, 0, 1 - buf):
                copy.start()

        for copy in fetch(b, j, buf):
            copy.wait()
        c, pe = c_buf[buf], pe_buf[buf]                  # [blk, rank], [rope, blk]
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qpe, pe, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        idx = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(idx <= pos, s * sm_scale, NEG_INF)
        # position 0 lies in every slot's first block: m is finite from
        # the first block on
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    init = (jnp.zeros((h, c_buf.shape[-1]), jnp.float32),
            jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32))
    acc, _, l = jax.lax.fori_loop(0, n, body, init)
    o_ref[0] = acc / l
    count[0] = first + n


def mla_decode(q_lat, q_pe, c_cache, pe_cache, positions, sm_scale: float,
               block: int, interpret: Optional[bool] = None):
    """softmax(mask(sm_scale (q_lat . c + q_pe . k_pe))) @ c for every
    slot and head: `q_lat` [B, H, rank] and `q_pe` [B, H, rope] (taken
    in the cache's type), the layer's `c_cache` [B, L, rank] and
    `pe_cache` [B, L, rope], each slot reading positions 0 ..
    `positions` [B]; the probabilities meet `c` in the cache's type.
    Returns o_lat [B, H, rank] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = attention_kernel.INTERPRET
    b, h, rank = q_lat.shape
    length, rope = c_cache.shape[1], pe_cache.shape[-1]
    if length % block or pe_cache.shape[:2] != (b, length):
        raise ValueError(f"caches {c_cache.shape} / {pe_cache.shape} "
                         f"against blocks of {block}")
    kernel = functools.partial(_mla_decode_kernel, block=block,
                               sm_scale=sm_scale)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, h, rank), lambda i, pos: (i, 0, 0)),
                      pl.BlockSpec((1, h, rope), lambda i, pos: (i, 0, 0)),
                      any_space, any_space],
            out_specs=pl.BlockSpec((1, h, rank), lambda i, pos: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, rank), c_cache.dtype),
                pltpu.VMEM((2, rope, block), pe_cache.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), jnp.float32),
        # a slot's last block fetches the next slot's first: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_decode",
    )(positions.astype(jnp.int32), q_lat.astype(c_cache.dtype),
      q_pe.astype(pe_cache.dtype), c_cache, jnp.swapaxes(pe_cache, 1, 2))
